#!/usr/bin/env python3
"""Chip smoke: the main path on the TPU, through its normal entry points.

    python chip_smoke.py                # one chip: plan + channel, train
    python chip_smoke.py --four-chips   # four chips: the sharded node axis

One chip:

* ``plan+channel`` — the host planner (Algorithm 2, run by the
  ``WirelessSimulator`` constructor) on the Rayleigh-only fading world at
  n = 256, then 30 TDM rounds on the jitted channel plane
  (``precompute_trace_scan``, what ``precompute_trace(engine="scan")``
  runs). Then the Eq. 3 anchor on the static scenario: the scan's ``w_eff``
  equals the event loop's and its ``t_comm_s`` agrees to 1e-9 relative.
* ``train`` — ``train_cnn_on_traces`` over a 16-seed fading family at
  n = 6: the paper's CNN at its published width, 2 epochs, once with the
  fp32 payload and once in the ``compressed_int8`` world (int8 + error
  feedback inside the scan). Losses and accuracies must be finite and the
  last eval round's accuracy above chance.

``--four-chips`` runs only what exists across chips: ``real_model_smoke``
with the node axis sharded over four chips against its unsharded
per-round reference, then stablelm-3b at its published widths, one node
per chip, depth cut to fit, in fp32 and with per-leaf int8 payloads.

Each phase prints one line. The last line is one JSON object naming the
device. The script exits non-zero, without that line, when JAX sees no
TPU, a phase raises, or a check fails. Compiled programs are kept in the
persistent cache (``repro.utils.compile_cache``), so a second run shows
its hits in ``compile_s`` and ``cache_hits``. All node-round and round
rates are host-clock smoke readings that end in ``block_until_ready``,
not benchmark metrics.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

CHANNEL_N, CHANNEL_ROUNDS = 256, 30
ANCHOR_ROUNDS = 6
TRAIN_SEEDS, TRAIN_EPOCHS = 16, 2
N_CLASSES = 10
# stablelm-3b at its published widths, one node per chip: the depth and
# sequence length that fit 16 GiB with one full replica per chip. The
# compiled four-chip step asks for 10.4 GiB per chip in fp32 and 12.5 GiB
# with int8-leaf payloads (15.5 GiB at three layers); the peak measured
# on four TPU v5e chips was 7.9 GiB.
LM_DEPTH, LM_SEQ, LM_ROUNDS, LM_NODES = 2, 256, 3, 4


class SmokeFailure(RuntimeError):
    """A phase ran but its output is wrong."""


def require(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


class CompileMeter:
    """Seconds spent in XLA compilation (a persistent-cache read counts
    under the same event) and persistent-cache hits, since the last
    ``take``."""

    _COMPILE = "/jax/core/compile/backend_compile_duration"
    _HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax

        self.seconds, self.hits = 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == self._COMPILE:
            self.seconds += duration

    def _event(self, event, **_):
        if event == self._HIT:
            self.hits += 1

    def take(self) -> dict:
        out = {"compile_s": self.seconds, "cache_hits": self.hits}
        self.seconds, self.hits = 0.0, 0
        return out


def report(phase: str, fields: dict) -> None:
    print(f"phase {phase}: {json.dumps(fields)}", flush=True)


def plan_and_channel(meter: CompileMeter, n_nodes: int = CHANNEL_N,
                     rounds: int = CHANNEL_ROUNDS) -> dict:
    import numpy as np

    from repro.sim import WirelessSimulator, get_scenario
    from repro.sim.jit_trace import precompute_trace_scan
    from repro.sim.trace import precompute_trace

    cfg = get_scenario("fading", n_nodes=n_nodes,
                       **{"fading.shadowing_sigma_db": 0.0})
    t0 = time.perf_counter()
    sim = WirelessSimulator(cfg)              # the constructor plans
    t_plan = time.perf_counter() - t0
    sol = sim.solution
    require(bool(sol.feasible) and sol.lam <= cfg.lambda_target + 1e-12,
            f"plan infeasible: lambda {sol.lam} vs {cfg.lambda_target}")
    meter.take()

    t0 = time.perf_counter()
    cold = precompute_trace_scan(cfg, rounds, sim=sim)
    t_cold = time.perf_counter() - t0
    compiled = meter.take()
    t0 = time.perf_counter()
    warm = precompute_trace_scan(cfg, rounds, sim=sim)
    t_warm = time.perf_counter() - t0
    require(warm.w_eff.shape == (rounds, n_nodes, n_nodes),
            f"w_eff shape {warm.w_eff.shape}")
    require(np.isfinite(warm.w_eff).all()
            and np.allclose(warm.w_eff.sum(axis=2), 1.0),
            "w_eff rows are not stochastic")
    require(np.isfinite(warm.t_comm_s).all() and (warm.t_comm_s > 0).all()
            and (np.diff(warm.t_start_s) > 0).all(),
            "round clock not finite and increasing")
    require(np.array_equal(cold.w_eff, warm.w_eff)
            and np.array_equal(cold.t_comm_s, warm.t_comm_s),
            "two runs of one seed differ")
    summary = warm.trace.summary()

    # Eq. 3 anchor: static world, scan on the chip vs the host event loop
    ev = precompute_trace("static", ANCHOR_ROUNDS)
    sc = precompute_trace("static", ANCHOR_ROUNDS, engine="scan")
    rel = float(np.max(np.abs(sc.t_comm_s - ev.t_comm_s) / ev.t_comm_s))
    w_equal = bool(np.array_equal(sc.w_eff, ev.w_eff))
    anchor = {"rounds": ANCHOR_ROUNDS, "w_eff_equal": w_equal,
              "t_comm_max_rel_err": rel, "tol": 1e-9,
              **meter.take()}
    require(w_equal, "static scan w_eff differs from the event loop")
    require(rel < 1e-9, f"static scan t_comm off Eq. 3 by {rel:.3e} rel")
    require(sc.trace.records[0].outage_links == 0, "static scan outage")
    return {
        "scenario": "fading (Rayleigh only)", "n_nodes": n_nodes,
        "rounds": rounds, "planner_s": t_plan, "lambda": float(sol.lam),
        "lambda_target": cfg.lambda_target, "cold_s": t_cold,
        **compiled, "warm_s": t_warm, "rounds_per_s": rounds / t_warm,
        "outage_rate": summary["outage_rate"], "anchor": anchor,
    }


def train(meter: CompileMeter, world: str, seeds: int = TRAIN_SEEDS,
          epochs: int = TRAIN_EPOCHS) -> dict:
    import jax
    import numpy as np

    from repro.sim import get_scenario, train_cnn_on_traces

    cfgs = [get_scenario(world, seed=s, solver="greedy")
            for s in range(seeds)]
    meter.take()
    t0 = time.perf_counter()
    traces, out = train_cnn_on_traces(cfgs, epochs=epochs)
    jax.block_until_ready(out["final_params"])
    t_cold = time.perf_counter() - t0
    compiled = meter.take()
    t0 = time.perf_counter()
    _, out = train_cnn_on_traces(cfgs, epochs=epochs, trace_batch=traces)
    jax.block_until_ready(out["final_params"])
    t_warm = time.perf_counter() - t0
    losses, acc = np.asarray(out["losses"]), np.asarray(out["acc"])
    require(np.isfinite(losses).all(), f"{world}: non-finite loss")
    require(np.isfinite(acc).all(), f"{world}: non-finite accuracy")
    last = float(acc[:, -1].min())
    require(last > 1.0 / N_CLASSES,
            f"{world}: last eval accuracy {last:.3f} is not above chance")
    node_rounds = traces.n_traces * traces.n_rounds * traces.n_nodes
    return {
        "world": world, "payload": cfgs[0].payload.mode, "seeds": seeds,
        "n_nodes": traces.n_nodes, "rounds": traces.n_rounds,
        "epochs": epochs, "cold_s": t_cold, **compiled, "warm_s": t_warm,
        "node_rounds_per_s": node_rounds / t_warm,
        "first_loss": float(losses[:, 0].mean()),
        "last_loss": float(losses[:, -1].mean()),
        "last_acc_mean": float(acc[:, -1].mean()), "last_acc_min": last,
    }


def mix_is_exact() -> dict:
    """An identity W must hand every node its parameters back bit for bit:
    the mixing matmul may not round float32 parameters on the device."""
    import jax
    import jax.numpy as jnp

    from repro.core.dpsgd import mix

    x = jax.random.normal(jax.random.key(0), (6, 21840), jnp.float32)
    out = jax.jit(mix)(x, jnp.eye(6, dtype=jnp.float32))
    err = float(jnp.max(jnp.abs(out - x)))
    require(err == 0.0, f"identity mix moved parameters by {err:.3e}")
    return {"identity_mix_max_err": err}


def sharded_smoke(meter: CompileMeter) -> dict:
    from repro.sim.real_model_smoke import run

    meter.take()
    t0 = time.perf_counter()
    rep = run(rounds=4, fleet=4, model=1, n_nodes=8, batch=2, seq_len=64)
    rep.update(seconds=time.perf_counter() - t0, **meter.take())
    require(rep["devices_spanned"] == 4 and rep["sharded_leaves"] > 0,
            f"parameters not sharded over 4 devices: {rep}")
    require(rep["ok"], f"sharded smoke parity failed: {rep['parity']}")
    return rep


def stablelm_per_chip(meter: CompileMeter, payload_mode: str) -> dict:
    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.core.compression import QuantConfig
    from repro.launch.mesh import make_fleet_mesh
    from repro.sim import get_scenario
    from repro.sim.batch import train_model_on_traces, transformer_adapter

    full = get_config("stablelm-3b")
    mcfg = dataclasses.replace(full, n_layers=LM_DEPTH)
    adapter = transformer_adapter(mcfg, batch=1, seq_len=LM_SEQ)
    payload = (QuantConfig(mode="none") if payload_mode == "fp32"
               else QuantConfig(mode="int8", granularity="leaf"))
    cfg = get_scenario("fading", n_nodes=LM_NODES, payload=payload,
                       model_bits=adapter.model_bits,
                       model_shapes=adapter.param_shapes,
                       eval_every_rounds=LM_ROUNDS)
    mesh = make_fleet_mesh(fleet=LM_NODES, model=1)
    meter.take()
    t0 = time.perf_counter()
    _, out = train_model_on_traces(adapter, [cfg], LM_ROUNDS, unroll=1,
                                   mesh=mesh)
    jax.block_until_ready(out["final_params"])
    seconds = time.perf_counter() - t0
    losses = np.asarray(out["losses"])
    require(np.isfinite(losses).all(),
            f"stablelm-3b {payload_mode}: non-finite loss {losses}")
    require(np.isfinite(np.asarray(out["acc"])).all(),
            f"stablelm-3b {payload_mode}: non-finite eval")
    spans = {d.id for leaf in jax.tree.leaves(out["final_params"][0])
             for d in leaf.sharding.device_set}
    return {
        "arch": full.name, "payload": payload_mode,
        "cut": f"{LM_DEPTH} of {full.n_layers} layers",
        "d_model": mcfg.d_model, "heads": f"{mcfg.n_heads}x{mcfg.head_dim}",
        "d_ff": mcfg.d_ff, "vocab": mcfg.vocab_size,
        "params_per_node": int(adapter.model_bits // 32),
        "n_nodes": LM_NODES, "nodes_per_chip": 1, "seq_len": LM_SEQ,
        "batch_per_node": 1, "rounds": LM_ROUNDS, "wire_bits": cfg.wire_bits(),
        "seconds": seconds, **meter.take(),
        "losses": losses[0].tolist(), "devices_spanned": len(spans),
        # None where the backend keeps no memory statistics
        "peak_bytes_in_use": [(d.memory_stats() or {}).get(
            "peak_bytes_in_use") for d in jax.devices()],
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__.splitlines()[0],
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the paths that span four chips")
    args = ap.parse_args(argv)

    from repro.utils.compile_cache import use_compile_cache

    cache_dir = use_compile_cache()
    import jax

    devices = jax.devices()
    dev = devices[0]
    need = 4 if args.four_chips else 1
    if dev.platform != "tpu" or len(devices) < need:
        print(f"chip_smoke: needs {need} TPU chip(s); JAX sees "
              f"{len(devices)} {dev.platform} device(s)", file=sys.stderr)
        return 1
    print(f"chip_smoke: {len(devices)} x {dev.device_kind}, jax "
          f"{jax.__version__}, compile cache {cache_dir}", flush=True)
    meter = CompileMeter()
    if args.four_chips:
        report("sharded", sharded_smoke(meter))
        for mode in ("fp32", "int8-leaf"):
            report(f"stablelm-3b/{mode}", stablelm_per_chip(meter, mode))
    else:
        report("plan+channel", plan_and_channel(meter))
        report("mix", mix_is_exact())
        for world in ("fading", "compressed_int8"):
            report(f"train/{world}", train(meter, world))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
