"""Training-plane perf harness: batched train-on-trace vs the per-round
Python driver, with scan-vs-driver parity pins.

The workload is the Monte-Carlo evaluation style of the paper's runtime
claim: the same scenario at many fading seeds, one accuracy-vs-simulated-
time curve per seed. Two implementations run it:

* ``driver`` — ``sim.trace.simulate_dpsgd_cnn`` per seed: the per-round
  path (one Python callback, one device dispatch, one ``block_until_ready``
  and a fresh jit binding per call), measured first in the fresh process —
  exactly what a sweep over this API costs today.
* ``scan``   — ``sim.batch.train_cnn_on_traces``: traces precomputed
  driver-less, then one jitted scan/vmap call for the whole seed family.
  ``t_scan_cold_s`` includes the one-off compile; ``t_scan_warm_s`` (median
  over fresh seed sets, which is how a Monte-Carlo sweep re-enters the
  cached executable) is the steady-state cost and the basis of ``speedup``.

Parity checks (``parity`` in the JSON, process exits 1 on any failure):

* static scenario: per-round scan losses within 1e-5 of the driver's,
  identical accuracy points and simulated-time stamps;
* churn scenario: masked fixed-shape rounds track the reshape-based driver
  (same live-node counts, losses within 1e-5, final surviving parameters
  within 1e-5).

The ``real_model`` section (also gated, including under ``--quick``) times
the unsharded scan of the smoke-reduced transformer on a fading trace for a
tokens-per-second figure and gates on finite losses. The sharded check
(``repro.sim.real_model_smoke``) runs in a process of its own: CI's
multi-device job on 8 host devices, and ``chip_smoke.py --four-chips`` on
the chip.

Prints the JSON to stdout; full runs also write it to ``--out`` (default
``BENCH_train.json`` at the repo root). ``--quick`` (the CI gate) runs a
smaller sweep and never touches the tracked snapshot unless ``--out`` is
given.

Usage:
    PYTHONPATH=src python -m benchmarks.bench_train [--quick] [--out PATH]
"""
from __future__ import annotations

import argparse
import json
import platform
import sys
import time
from pathlib import Path

import numpy as np

from repro.data import SyntheticFashion
from repro.sim import get_scenario, simulate_dpsgd_cnn, train_cnn_on_traces

__all__ = ["main"]

# Monte-Carlo sweep shape: many seeds x short traces x small local batches —
# the regime where the per-round driver is host-bound (per-call jit binding,
# per-round dispatch + sync) rather than FLOP-bound.
SWEEP = dict(epochs=1, batch=5, n_train=150, n_test=300)
SWEEP_ROUNDS = 5          # n_train/6 nodes = 25/node -> 5 rounds at batch 5
PARITY = dict(epochs=1, batch=25, n_train=600, n_test=150)


def _sweep_cfgs(seeds) -> list:
    return [get_scenario("fading", seed=s, solver="greedy",
                         eval_every_rounds=SWEEP_ROUNDS) for s in seeds]


def bench_sweep(n_seeds: int, scan_reps: int) -> dict:
    ds = SyntheticFashion(n_train=SWEEP["n_train"], n_test=SWEEP["n_test"],
                         seed=0)
    kw = dict(SWEEP, ds=ds)

    t0 = time.perf_counter()
    for cfg in _sweep_cfgs(range(n_seeds)):
        simulate_dpsgd_cnn(cfg, **kw)
    t_driver = time.perf_counter() - t0

    t0 = time.perf_counter()
    train_cnn_on_traces(_sweep_cfgs(range(100, 100 + n_seeds)), **kw)
    t_cold = time.perf_counter() - t0

    warm = []
    for rep in range(scan_reps):
        cfgs = _sweep_cfgs(range(200 + rep * n_seeds,
                                 200 + (rep + 1) * n_seeds))
        t0 = time.perf_counter()
        train_cnn_on_traces(cfgs, **kw)
        warm.append(time.perf_counter() - t0)
    t_warm = float(np.median(warm))

    rounds = n_seeds * SWEEP_ROUNDS
    return {
        "scenario": "fading", "seeds": n_seeds,
        "rounds_per_trace": SWEEP_ROUNDS, "batch": SWEEP["batch"],
        "n_train": SWEEP["n_train"], "n_test": SWEEP["n_test"],
        "t_driver_s": t_driver,
        "t_scan_cold_s": t_cold,
        "t_scan_warm_s": t_warm,
        "t_scan_warm_min_s": float(min(warm)),
        "scan_reps": scan_reps,
        "speedup": t_driver / t_warm,
        "speedup_cold": t_driver / t_cold,
        "traces_per_s": n_seeds / t_warm,
        "rounds_per_s": rounds / t_warm,
        "driver_rounds_per_s": rounds / t_driver,
    }


def check_parity() -> dict:
    import jax

    def param_diff(a, b):
        d = jax.tree.map(
            lambda x, y: float(np.abs(np.asarray(x) - np.asarray(y)).max()),
            a, b)
        return max(jax.tree.leaves(d))

    out: dict = {}

    cfg = get_scenario("static", compute_s_per_round=0.05,
                       eval_every_rounds=2)
    trace, params = simulate_dpsgd_cnn(cfg, **PARITY)
    _, scan = train_cnn_on_traces([cfg], **PARITY)
    drv_losses = np.array([r.loss for r in trace.records])
    out["static_max_loss_diff"] = float(
        np.abs(scan["losses"][0] - drv_losses).max())
    drv_acc = [(r.t_end_s, r.acc) for r in trace.records if r.acc is not None]
    out["static_acc_ok"] = bool(
        len(drv_acc) == len(scan["curves"][0])
        and all(abs(a_s - a_d) <= 1e-6 and abs(t_s - t_d) <= 1e-9 * (1 + t_d)
                for (t_d, a_d), (t_s, a_s) in zip(drv_acc, scan["curves"][0])))
    out["static_param_diff"] = param_diff(params, scan["final_params"][0])
    out["static_ok"] = bool(out["static_max_loss_diff"] <= 1e-5
                            and out["static_acc_ok"]
                            and out["static_param_diff"] <= 1e-5)

    # rate chosen so the pinned placement stream yields >= 2 failures inside
    # the PARITY horizon (the churn_failures >= 1 gate below must actually
    # exercise the masked/reshape paths, not vacuously pass)
    cfg = get_scenario("churn", churn_rate_per_s=1.5, solver="greedy",
                       compute_s_per_round=0.05, eval_every_rounds=2)
    trace, params = simulate_dpsgd_cnn(cfg, **PARITY)
    traces, scan = train_cnn_on_traces([cfg], **PARITY)
    drv_losses = np.array([r.loss for r in trace.records])
    out["churn_failures"] = trace.summary()["failures"]
    out["churn_max_loss_diff"] = float(
        np.abs(scan["losses"][0] - drv_losses).max())
    out["churn_param_diff"] = param_diff(params, scan["final_params"][0])
    out["churn_ok"] = bool(
        out["churn_failures"] >= 1
        and list(traces.traces[0].n_live) == [r.n_live for r in trace.records]
        and out["churn_max_loss_diff"] <= 1e-5
        and out["churn_param_diff"] <= 1e-5)
    return out


def bench_real_model(quick: bool) -> dict:
    """Real-model train-on-trace: steady-state tokens/s of the unsharded
    compiled scan. ``ok`` gates on finite losses."""
    from repro.sim.batch import train_model_on_traces, transformer_adapter

    rounds = 2 if quick else 4
    batch, seq_len = (2, 8) if quick else (2, 16)

    adapter = transformer_adapter(batch=batch, seq_len=seq_len)
    cfg = get_scenario("fading", model_bits=adapter.model_bits,
                       model_shapes=adapter.param_shapes,
                       eval_every_rounds=rounds)
    t0 = time.perf_counter()
    train_model_on_traces(adapter, [cfg], rounds)
    t_cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    _, out = train_model_on_traces(adapter, [cfg], rounds)
    t_warm = time.perf_counter() - t0
    tokens = rounds * cfg.n_nodes * batch * seq_len
    return {
        "arch": adapter.name,
        "rounds": rounds, "batch": batch, "seq_len": seq_len,
        "model_bits": adapter.model_bits,
        "wire_bits": cfg.wire_bits(),
        "t_scan_cold_s": t_cold,
        "t_scan_warm_s": t_warm,
        "tokens_per_s": tokens / t_warm,
        "final_loss": float(out["losses"][0][-1]),
        "ok": bool(np.isfinite(out["losses"]).all()),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke: small sweep, same parity pins")
    ap.add_argument("--out", default=None,
                    help="output JSON path (default: repo-root BENCH_train.json)")
    args = ap.parse_args(argv)

    from repro.utils.compile_cache import use_compile_cache

    use_compile_cache()
    import jax

    from repro.analysis import repo_is_clean

    n_seeds = 3 if args.quick else 16
    scan_reps = 1 if args.quick else 3
    result = {
        "schema": "bench_train/v1",
        "quick": bool(args.quick),
        "platform": platform.platform(),
        "numpy": np.__version__,
        "jax": jax.__version__,
        "device": jax.devices()[0].platform,
        "analysis_clean": repo_is_clean(),
        "sweep": bench_sweep(n_seeds, scan_reps),
        "parity": check_parity(),
        "real_model": bench_real_model(args.quick),
    }
    result["sweep"]["speedup_ok"] = bool(result["sweep"]["speedup"] >= 5.0)
    failed = not (result["parity"]["static_ok"]
                  and result["parity"]["churn_ok"]
                  and result["real_model"]["ok"])
    result["ok"] = not failed

    text = json.dumps(result, indent=2)
    print(text)
    if args.out:
        Path(args.out).write_text(text + "\n")
    elif not args.quick:
        # only full runs update the tracked perf trajectory
        out = Path(__file__).resolve().parent.parent / "BENCH_train.json"
        out.write_text(text + "\n")
    if failed:
        print("FAIL: scan/vmap path diverged from the per-round driver",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
