"""Jitted TDM round loop: a whole trace as one compiled ``lax.scan``.

``WirelessSimulator.run`` drives rounds from a Python event loop — one
``tdm_round`` call, one channel fetch chain, and one ``RoundRecord`` per
round. At n=6 that loop is free; at n=1024 the host bookkeeping dominates
and a 30-round fading trace spends its time in Python, not in the channel.
This module moves the round loop into the jitted plane next to
``sim.batch``'s training scan: plan once on the host (the exact
``WirelessSimulator`` plan — Algorithm 2 through the elastic controller),
then realize every TDM round of the trace inside a single compiled program
(outer ``lax.scan`` over rounds, inner scan over transmitters, broadcast
passes unrolled), and synthesize the same ``TrainTrace``/``SimTrace``
containers the event loop emits.

Scope — the scan plane compiles the *stationary* TDM world:

* static placement (no mobility), no churn, no fault injection;
* ``tdm`` policy with a concrete payload (no per-replan joint planning);
* fading off, or Rayleigh block fading without shadowing (the AR(1)
  shadowing walk is sequential across coherence blocks — state the scan
  cannot redraw independently per block).

``scan_unsupported_reason`` names the first violated requirement;
``precompute_trace`` dispatches here under ``engine="scan"``/``"auto"``.

Numerics: the MAC semantics are ``mac.tdm_round``'s — every active node
airs all packets in pass 0, retransmission passes resend packets any
intended receiver still needs, a packet is decoded iff the instantaneous
capacity carries its rate, and the clock advances packet by packet in
float64 (the whole program is traced under ``jax.enable_x64``).
On the static scenario the round time reproduces Eq. 3 / the event loop to
relative float64 tolerance (the scan sums a transmitter's packet airtimes
before adding them to the clock, so the association differs in the last
bits). The program returns who decoded whom; the host turns each round's
delivered graph into W with the event loop's own Eq. 4 code, so W is
bit-identical to the event loop's for the same deliveries on any backend
(the TPU emulates float64 with fewer significant bits than IEEE binary64,
and 1/3 computed there is not numpy's 1/3). Under fading the Rayleigh
gains come from a stateless splitmix64 hash of ``(fading.seed, coherence block, unordered node pair)`` — per-block
independent, reciprocal, Exp(1)-distributed, deterministic across runs and
processes, but a *third* RNG scheme: realizations differ from the host
MAC's ``chunked``/``per_block`` streams (identical in distribution, not in
draw order).
"""
from __future__ import annotations

from functools import lru_cache
from typing import Optional

import numpy as np

from ..core import channel
from ..core.topology import ITERATIVE_MIN_N, paper_w, spectral_lambda, \
    spectral_lambda_iter_batch
from ..utils.spans import span
from .mac import _packets, mean_drift
from .scenario import ScenarioConfig, get_scenario

__all__ = ["scan_unsupported_reason", "precompute_trace_scan"]


def scan_unsupported_reason(cfg: ScenarioConfig) -> Optional[str]:
    """``None`` when ``cfg`` can run on the jitted scan plane, else the
    first requirement it violates (the message the dispatcher raises)."""
    if cfg.resolved_policy() != "tdm":
        return (f"policy {cfg.resolved_policy()!r}: only the TDM policy is "
                "compiled; RA/BASS rounds draw per-slot host randomness")
    if cfg.mobility_kind != "static":
        return (f"mobility {cfg.mobility_kind!r}: the scan freezes one "
                "placement; motion needs the event loop's per-round "
                "positions and drift replans")
    if cfg.churn_rate_per_s > 0:
        return ("churn reshapes the node set mid-trace; the scan is "
                "fixed-width")
    if cfg.faults is not None and cfg.faults.any_active():
        return ("fault injection (blackouts/crashes/stragglers) is realized "
                "by the event loop's per-round host state")
    if cfg.payload.mode == "auto":
        return ("payload.mode=\"auto\" re-picks the payload per replan; "
                "the scan bakes one wire size into the compiled program")
    if cfg.reference_mac:
        return "reference_mac pins the per-packet host loop by definition"
    if cfg.fading is not None and cfg.fading.shadowing_sigma_db > 0:
        return ("AR(1) shadowing advances sequentially across coherence "
                "blocks; the scan's stateless per-block RNG cannot "
                "reproduce it — use shadowing_sigma_db=0 (Rayleigh only) "
                "or the event loop")
    return None


def _check_scan_supported(cfg: ScenarioConfig) -> None:
    reason = scan_unsupported_reason(cfg)
    if reason is not None:
        raise ValueError(f"scenario {cfg.name!r} cannot run on the jitted "
                         f"scan plane: {reason}")


# -- stateless per-block Rayleigh gains --------------------------------------

def _mix64(z):
    """splitmix64 finalizer (Steele et al.) on uint64 lanes."""
    import jax.numpy as jnp
    z = (z + jnp.uint64(0x9E3779B97F4A7C15))
    z = (z ^ (z >> jnp.uint64(30))) * jnp.uint64(0xBF58476D1CE4E5B9)
    z = (z ^ (z >> jnp.uint64(27))) * jnp.uint64(0x94D049BB133111EB)
    return z ^ (z >> jnp.uint64(31))


def _rayleigh_gains(seed: int, blocks, i, n: int):
    """(P, n) Exp(1) power gains for transmitter ``i``'s packets: one draw
    per (coherence block, unordered pair), so the channel is reciprocal and
    block-fading exactly like the host generator — just keyed by a hash
    instead of a sequential stream."""
    import jax.numpy as jnp
    j = jnp.arange(n)
    pair = (jnp.minimum(i, j) * n + jnp.maximum(i, j)).astype(jnp.uint64)
    b = _mix64(jnp.uint64(seed & 0xFFFFFFFFFFFFFFFF)
               ^ _mix64(blocks.astype(jnp.uint64)))
    h = _mix64(b[:, None] ^ pair[None, :])
    # weak-typed float literal: promotes the uint64 mantissa to float64
    # under the enable_x64 scope this whole program is traced in
    u = (h >> jnp.uint64(11)) * (2.0 ** -53)                      # [0, 1)
    return -jnp.log1p(-u)                                         # Exp(1)


# -- the compiled round loop -------------------------------------------------

@lru_cache(maxsize=32)
def _round_scan(n: int, n_pkts: int, passes: int, fading_on: bool,
                coherence_s: float, bandwidth_hz: float, overhead_s: float,
                compute_s: float, seed: int, n_rounds: int):
    """Build (and cache) the jitted trace program for one static shape.

    The returned function maps ``(rates, sizes, recv, chan)`` to per-round
    ``(t_start, t_comm, delivered, retx)`` stacks plus the final clock.
    ``chan`` is the raw mean SNR matrix under fading, else the precomputed
    static decode table ``capacity >= rate_i``. Compile events and the
    profiler name the program ``channel_round_scan``.
    """
    import jax
    import jax.numpy as jnp

    def channel_round_scan(rates, sizes, recv, chan):
        active = jnp.isfinite(rates) & (rates > 0)
        durs = (sizes[None, :] / jnp.where(active, rates, 1.0)[:, None]
                + overhead_s)                                  # (n, P)
        idx = jnp.arange(n)

        def tx_step(clock, i):
            rate = rates[i]
            recv_i = recv[i]
            need = jnp.broadcast_to(recv_i[None, :], (n_pkts, n))
            retx = jnp.int64(0)
            for p in range(passes):
                send = (jnp.ones(n_pkts, dtype=bool) if p == 0
                        else need.any(axis=1)) & active[i]
                d = jnp.where(send, durs[i], 0.0)
                t_tx = clock + (jnp.cumsum(d) - d)             # launch times
                if fading_on:
                    blocks = jnp.floor(t_tx / coherence_s).astype(jnp.int64)
                    g = _rayleigh_gains(seed, blocks, i, n)
                    cap = bandwidth_hz * jnp.log2(
                        1.0 + chan[i][None, :] * g / bandwidth_hz)
                    ok = cap >= rate
                else:
                    ok = jnp.broadcast_to(chan[i][None, :], (n_pkts, n))
                need = need & ~(ok & send[:, None])
                if p > 0:
                    retx = retx + send.sum()
                clock = clock + d.sum()
            delivered_i = recv_i & ~need.any(axis=0)
            return clock, (delivered_i, retx)

        def round_step(clock, _):
            t_start = clock
            clock, (delivered, retx) = jax.lax.scan(tx_step, clock, idx)
            t_comm = clock - t_start
            return clock + compute_s, (t_start, t_comm, delivered,
                                       retx.sum())

        clock, outs = jax.lax.scan(round_step, jnp.asarray(0.0), None,
                                   length=n_rounds)
        return outs + (clock,)

    return jax.jit(channel_round_scan)


def precompute_trace_scan(cfg, n_rounds: int, sim=None, **overrides):
    """Realize one scenario's channel plane as a single compiled program
    and emit the same ``TrainTrace`` the event loop's ``precompute`` does.

    The plan is the event loop's own (the ``WirelessSimulator`` constructor
    runs the initial Algorithm 2 replan, so plan parity is by construction);
    every TDM round after that runs inside one jitted scan. Raises
    ``ValueError`` (via ``scan_unsupported_reason``) for configs that need
    the event loop's per-round host state.

    ``sim`` lets a caller that already paid the replan (``WirelessSimulator
    (cfg)``) hand it over instead of planning twice; it must have been built
    from this exact ``cfg`` (no ``overrides`` then).

    The realization is the host span ``repro.scan`` (placement ``seed``,
    ``n``, ``rounds``, ``packets``), in three parts: ``repro.scan.prepare``
    (the program's inputs), ``repro.scan.run`` (the compiled call and its
    readback) and ``repro.scan.records`` (Eq. 4 W, effective densities,
    round records).
    """
    import jax

    from .trace import WirelessSimulator

    if isinstance(cfg, str):
        cfg = get_scenario(cfg, **overrides)
    elif overrides:
        cfg = cfg.replace(**overrides)
    _check_scan_supported(cfg)

    if sim is None:
        sim = WirelessSimulator(cfg)
    elif overrides or sim.cfg is not cfg:
        raise ValueError("pass sim= only with the exact cfg it was built "
                         "from (and no overrides)")
    sizes = np.asarray(_packets(cfg.model_bits, cfg.mac.packet_bits),
                       dtype=np.float64)
    if sizes.size == 0:
        raise ValueError("zero-bit model: nothing to put on the air")
    with span("scan", seed=int(cfg.seed), n=cfg.n_nodes,
              rounds=int(n_rounds), packets=int(sizes.size)):
        with span("scan.prepare"):
            fn, rates, recv, chan = _scan_inputs(cfg, sim, sizes, n_rounds)
        with span("scan.run"), jax.enable_x64(True):
            out = [np.asarray(x) for x in fn(rates, sizes, recv, chan)]
        with span("scan.records"):
            return _host_train_trace(cfg, sim.solution, rates, recv,
                                     sizes, n_rounds, *out)


def _scan_inputs(cfg, sim, sizes: np.ndarray, n_rounds: int):
    """The compiled round loop for ``cfg``'s shape, and its inputs: the
    plan's rates, its intended links and the channel table."""
    rates = np.asarray(sim.solution.rates_bps, dtype=np.float64)
    if np.isnan(rates).any():
        raise ValueError("plan has NaN rates")
    recv = np.asarray(sim._intended, dtype=bool).copy()
    np.fill_diagonal(recv, False)
    pos = sim._positions()

    fading_on = cfg.fading is not None
    if fading_on:
        d = channel.pairwise_distances(pos)
        chan = channel.snr_linear(np.where(d > 0, d, 1.0),
                                  cfg.channel_params())
        coherence_s = float(cfg.fading.coherence_s)
        seed = int(cfg.fading.seed)
    else:
        cap = sim.channel.mean_capacity(pos)
        chan = cap >= rates[:, None]
        coherence_s = 1.0
        seed = 0
    fn = _round_scan(cfg.n_nodes, int(sizes.size),
                     1 + int(cfg.mac.max_retx_rounds),
                     fading_on, coherence_s, float(cfg.bandwidth_hz),
                     float(cfg.mac.per_packet_overhead_s),
                     float(cfg.compute_s_per_round), seed, int(n_rounds))
    return fn, rates, recv, chan


def _host_train_trace(cfg, sol, rates, recv, sizes, n_rounds, t_start,
                      t_comm, delivered, retx, t_end):
    """The ``TrainTrace`` of the scan's outputs: W of each round by Eq. 4
    from its delivered graph, effective densities and round records."""
    from .trace import RoundRecord, SimTrace, TrainTrace

    n = cfg.n_nodes
    # W from the delivered graph with the event loop's Eq. 4 code
    # (``RoundResult.effective_w``), batched over rounds
    a = delivered.transpose(0, 2, 1).astype(np.float64)  # a[j, i]: j got i
    a[:, np.arange(n), np.arange(n)] = 1.0
    if cfg.degrade == "renorm":
        w_eff = paper_w(a)
    else:                                                      # "naive"
        planned = recv.T.astype(np.float64)
        np.fill_diagonal(planned, 1.0)
        w_eff = paper_w(planned) * a

    # per-round effective density: exact eig at small n, the power-iteration
    # estimate (the solvers' pre-screen) above ITERATIVE_MIN_N — at n=1024 a
    # 30-round trace would otherwise pay 30 dense eigendecompositions
    if n_rounds == 0:
        lam_eff = np.zeros(0)
    elif n <= ITERATIVE_MIN_N:
        lam_eff = np.array([spectral_lambda(w) for w in w_eff])
    else:
        lam_eff = spectral_lambda_iter_batch(w_eff)

    n_intended = int(recv.sum())
    active = np.isfinite(rates) & (rates > 0)
    packets_first = int(active.sum()) * int(sizes.size)
    records = []
    for r in range(int(n_rounds)):
        good = int((delivered[r] & recv).sum())
        records.append(RoundRecord(
            round=r, n_live=n,
            t_start_s=float(t_start[r]), t_comm_s=float(t_comm[r]),
            t_compute_s=float(cfg.compute_s_per_round),
            lam_planned=float(sol.lam), lam_effective=float(lam_eff[r]),
            feasible=bool(sol.feasible),
            intended_links=n_intended,
            outage_links=n_intended - good,
            retx_packets=int(retx[r]),
            delivered_frac=(good / n_intended) if n_intended else 1.0,
            replanned=False,
            mean_drift=mean_drift(w_eff[r]),
            wire_bits=float(cfg.model_bits),
            payload_mode=cfg.payload.mode))
    trace = SimTrace(scenario=cfg.name, records=records, replans=0,
                     failures=[], t_end_s=float(t_end),
                     events_processed=int(n_rounds))
    ones = np.ones((int(n_rounds), n), dtype=bool)
    return TrainTrace(
        scenario=cfg.name, n_nodes=n,
        w_eff=w_eff if n_rounds else np.zeros((0, n, n)),
        live=ones, active=ones.copy(),
        t_start_s=t_start, t_comm_s=t_comm,
        t_end_s=t_start + t_comm + cfg.compute_s_per_round,
        wire_bits=np.full(int(n_rounds), float(cfg.model_bits)),
        trace=trace, cfg=cfg)
