"""Driver for the channel plane: plan a fleet, then realize its TDM rounds.

A trace is one placement planned on the host (Algorithm 2, run by the
``WirelessSimulator`` constructor), then ``rounds`` TDM rounds realized by
the jitted scan (``precompute_trace_scan``). The fading seed is the
traffic's and fixed, so one compiled program serves every placement. One
unit of work is one trace. The traffic's pool of ``placements`` fleets is
drawn once from its ``pool_seed``, larger than a window holds, and the
window takes them one by one in an order drawn from the run's seed: every
run draws its work from the same pool, and no fleet is planned twice in a
run until the pool is spent (then a new order starts). The warm-up trace
plans a placement of its own, drawn from the run's seed.

The traffic file gives the scenario and its overrides, the number of rounds,
the pool, and how many of the window's traces the reference redoes
(``check_traces``). The configuration file gives the message the nodes
exchange (``message_bits``).

``correct``: for a sample of the window's traces, drawn from the seed, the
reference (``refs/tdm_channel.py``) places the nodes again, checks that the
plan's links are Eq. 4's for its rates and that its density is within the
scenario's target, then realizes the same rounds and compares every round's
airtime and mixing matrix.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from refs import tdm_channel as ref
from run import Check

# limits; PERF.md gives the readings each was set from. Links and mixing
# weights are compared exactly: the host builds W from the delivered graph.
T_COMM_REL_LIMIT = 1e-7
W_ABS_LIMIT = 0.0
LINKS_LIMIT = 0


@dataclasses.dataclass
class Answer:
    """What one trace of the window produced."""
    placement_seed: int
    rates: np.ndarray
    lam: float
    feasible: bool
    intended: np.ndarray
    w_eff: np.ndarray
    t_comm_s: np.ndarray


class Driver:
    def __init__(self, ctx):
        from repro.sim import get_scenario

        self.ctx = ctx
        t = ctx.traffic
        self.rounds = int(t["rounds"])
        self.cfg0 = get_scenario(t["scenario"], **t["overrides"]).replace(
            model_bits=float(ctx.config["message_bits"]))
        self.order = np.random.default_rng(ctx.seed)
        self.pool = np.random.default_rng(int(t["pool_seed"])).integers(
            2 ** 31, size=int(t["placements"])).tolist()
        self.queue: list[int] = []
        self.answers: list[Answer] = []
        self.warm: dict = {}

    def _trace(self, placement_seed: int) -> Answer:
        from repro.sim import WirelessSimulator
        from repro.sim.jit_trace import precompute_trace_scan

        cfg = self.cfg0.replace(seed=int(placement_seed))
        with self.ctx.span("plan"):
            sim = WirelessSimulator(cfg)
        with self.ctx.span("scan"):
            tr = precompute_trace_scan(cfg, self.rounds, sim=sim)
        sol = sim.solution
        links = np.asarray(sim._intended, bool).copy()
        np.fill_diagonal(links, False)
        return Answer(cfg.seed, np.asarray(sol.rates_bps, np.float64),
                      float(sol.lam), bool(sol.feasible), links, tr.w_eff,
                      tr.t_comm_s)

    def setup(self) -> None:
        """Warm-up: one trace of the window's shape compiles the scan."""
        self._trace(self.order.integers(2 ** 31))
        self.warm = {f"warmup_{k}_s": v for k, v in
                     ((k, self.ctx.spans.total(k)[0]) for k in ("plan", "scan"))}

    def setup_info(self) -> dict:
        return self.warm

    def unit(self) -> float:
        if not self.queue:
            self.queue = [self.pool[k] for k in self.order.permutation(len(self.pool))]
        self.answers.append(self._trace(self.queue.pop(0)))
        return float(self.rounds)

    def release(self) -> None:
        """The program keeps nothing on the device between traces."""

    def reference(self, a: Answer, dtype=np.float64):
        """The reference's plan check and rounds for one answer."""
        c = self.cfg0
        pos = ref.placement(c.n_nodes, c.area_m, a.placement_seed)
        snr = ref.mean_snr(pos, c.p_tx_dbm, c.noise_floor_dbm, c.path_loss_exp)
        cap = ref.planning_capacity(snr, c.bandwidth_hz, c.fading_margin_bps)
        links = ref.intended(cap, a.rates)
        lam = ref.density(ref.mixing(links))
        n_full = int(c.model_bits // c.mac.packet_bits)
        tail = c.model_bits - n_full * c.mac.packet_bits
        sizes = [c.mac.packet_bits] * n_full + ([tail] if tail > 0 else [])
        delivered, t_comm = ref.tdm_rounds(
            a.rates, links, ref.mean_snr(pos, c.p_tx_dbm, c.noise_floor_dbm,
                                         c.path_loss_exp, dtype),
            sizes, 1 + c.mac.max_retx_rounds, c.fading.coherence_s,
            c.bandwidth_hz, c.mac.per_packet_overhead_s,
            c.compute_s_per_round, c.fading.seed, self.rounds, dtype)
        return links, lam, delivered, t_comm

    def compare(self, a: Answer, dtype=np.float64) -> dict:
        """The numbers compared for one answer."""
        links, lam, delivered, t_comm = self.reference(a, dtype)
        w_ref = ref.mixing(delivered.transpose(0, 2, 1))
        t_ref = np.asarray(t_comm, np.float64)
        return {
            "plan_lambda": lam if a.feasible else float("inf"),
            "plan_link_mismatch": int((links != a.intended).sum()),
            "round_link_mismatch": int(((a.w_eff > 0) != (w_ref > 0)).sum()),
            "t_comm_rel_err": float(np.max(np.abs(a.t_comm_s - t_ref) / t_ref)),
            "w_max_abs_err": float(np.max(np.abs(a.w_eff - w_ref))),
        }

    def sample(self) -> list[int]:
        k = min(int(self.ctx.traffic["check_traces"]), len(self.answers))
        rng = np.random.default_rng((self.ctx.seed, 0xC4EC))
        return sorted(rng.choice(len(self.answers), size=k, replace=False).tolist())

    def check(self) -> tuple[list[Check], int]:
        worst: dict = {}
        failed = 0
        for idx in self.sample():
            got = self.compare(self.answers[idx])
            failed += int(not all(ok for *_, ok in self.judge(got)))
            for k, v in got.items():
                worst[k] = max(worst.get(k, v), v)
        checks = [Check(name, value, limit, ok)
                  for name, value, limit, ok in self.judge(worst)] if worst else []
        return checks, failed

    def control(self) -> dict:
        """The numbers read from the reference computed in float32 (one step
        below the configuration's float64) in the program's place."""
        a = self.answers[self.sample()[0]]
        _, _, delivered, t_comm = self.reference(a, np.float32)
        return self.compare(dataclasses.replace(
            a, w_eff=ref.mixing(delivered.transpose(0, 2, 1)),
            t_comm_s=np.asarray(t_comm, np.float64)))

    def judge(self, got: dict) -> list[tuple]:
        target = float(self.cfg0.lambda_target)
        rules = [("plan_lambda", target), ("plan_link_mismatch", LINKS_LIMIT),
                 ("round_link_mismatch", LINKS_LIMIT),
                 ("t_comm_rel_err", T_COMM_REL_LIMIT),
                 ("w_max_abs_err", W_ABS_LIMIT)]
        return [(k, got[k], lim, bool(got[k] <= lim)) for k, lim in rules]
