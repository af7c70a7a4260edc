"""Wireless-plane perf harness: batched solvers + vectorized MAC vs the
pinned pre-vectorization references, with exact-match cross-checks.

Measures (median + min over several runs each):

* ``solver``  — Algorithm 2 brute force on the paper's n=6 grid
  (``eps=5``, ``lambda_target=0.3``): sequential reference vs batched
  implementation, plus candidates/s of the batched pass.
* ``sim``     — a 30-round ``fading`` scenario run end to end
  ("pre" = per-packet loop MAC + one-rng-per-block channel + sequential
  solvers, i.e. the retained pre-PR hot path; "post" = vectorized MAC +
  chunked channel + batched solvers): rounds/s and packets/s.
* ``sweep``   — the ``sim.trace.sweep`` driver over a multi-seed,
  multi-scenario grid (Monte-Carlo style), rounds/s aggregate.
* ``n_sweep`` — large-n scaling: an Algorithm 2 replan (certified
  local-candidate sweep above ``ITERATIVE_MIN_N``) plus a 30-round
  scan-engine fading trace at n = 16/64/256/1024 (``--quick`` stops at
  256): solver time, rounds/s, lambda of the chosen plan, and whether the
  winner was certified by exact ``spectral_lambda``.
* ``mac_compare`` — TDM vs random access head to head: the paper's CNN
  trained through both MAC planes in one ``train_cnn_on_traces`` call,
  emitting the accuracy-vs-**simulated-wall-clock** traces (the axis the
  paper's runtime claim lives on) plus each plane's communication time.
* ``compression_compare`` — fp32 vs bf16 vs int8+error-feedback payloads on
  the dense ``fading`` world: per-mode exact wire bits, simulated
  communication time (the airtime drop tracks the exact ``payload_bits``
  ratio, ~3.9x for int8), and the accuracy-vs-simulated-time curves of the
  quantized train-on-trace path.
* ``policy_compare`` — the scheduling-policy plane head to head on the SAME
  fading world: TDM (``fading``) vs uniform random access (``ra_fading``)
  vs BASS subgraph sampling (``bass_fading``), one ``train_cnn_on_traces``
  call. Reports per-policy communication time, final accuracy, and
  **time-to-accuracy** (first simulated second reaching the best accuracy
  every policy attains) — the objective ``core.sched_opt`` optimizes.
* ``fault_compare`` — graceful degradation on the bursty-blackout world
  (``fault_burst``): fault-free baseline vs renorm degradation + watchdog
  vs naive W-degradation, one call per mode. The ``checks.fault`` gate pins
  renorm+watchdog within tolerance of the fault-free final accuracy while
  naive (rows leak mass on every lost link) measurably degrades.

Cross-checks (``checks`` in the JSON, process exits 1 on any failure):

* every batched solver == its ``*_reference`` (identical ``rates_bps``,
  ``t_com_s``, ``lam``) over random placements and lambda targets;
* ``access_opt.solve_access`` (batched (p, R) sweep) == its pinned
  sequential reference, same placements/targets;
* the joint rate x payload planners (``rate_opt.solve_joint``,
  ``access_opt.solve_access_joint``) == their sequential references,
  including the picked mode and exact wire bits;
* ``sched_opt.solve_schedule`` (batched accuracy-per-second sweep) == its
  pinned sequential reference over random placements, fraction grids, and
  duty cycles — and ``policy_compare``'s BASS policy must beat BOTH TDM and
  uniform RA on time-to-accuracy in the fading world (the scheduling
  plane's acceptance criterion);
* a fast-MAC and a reference-MAC simulator run of the same scenario produce
  identical round durations / retx / outage / delivered fractions;
* ``checks.scale`` — at every ``n_sweep`` size the winning plan's lambda is
  the exact eig of its W (certify-on-winner) and clears the density target,
  and the n=64 solve stays under ``MID_N_SOLVER_BUDGET_S`` (pins the mid-n
  greedy cliff fixed by the screened ``rate_opt.solve_greedy``);
* the static scenario still reproduces Eq. 3 to 1e-9 relative — and its
  int8 variant reproduces Eq. 3 *at the compressed wire bits* to 1e-9.

Prints the JSON to stdout; full runs also write it to ``--out`` (default
``BENCH_sim.json`` at the repo root) so every PR leaves a perf trajectory.
``--quick`` never touches the tracked snapshot unless ``--out`` is given.

Usage:
    PYTHONPATH=src python -m benchmarks.bench_sim [--quick] [--out PATH]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import platform
import sys
import time
from pathlib import Path

import numpy as np

from repro.core import channel, rate_opt
from repro.sim import WirelessSimulator, get_scenario, sweep

__all__ = ["main"]

M_BITS = 698_880.0  # paper CNN model size


def _timeit(fn, reps: int) -> tuple[float, float, object]:
    """(median_s, min_s, last_result) over ``reps`` runs — the median is the
    headline number (robust to scheduler noise on small containers), the min
    approximates the unloaded cost."""
    ts = []
    res = None
    for _ in range(reps):
        t0 = time.perf_counter()
        res = fn()
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts)), float(min(ts)), res


def bench_solver(reps: int) -> dict:
    pos = channel.random_placement(6, 200.0, seed=0)
    cap = channel.capacity_matrix(pos,
                                  channel.ChannelParams(path_loss_exp=5.0))
    n_candidates = int(np.prod(
        [rate_opt.candidate_rates(cap, i).size for i in range(6)]))

    def cold(fn):
        def run():
            rate_opt.clear_candidate_cache()
            return fn(cap, M_BITS, 0.3)
        return run

    t_ref, t_ref_min, sol_ref = _timeit(
        cold(rate_opt.solve_bruteforce_reference), reps)
    t_fast, t_fast_min, sol_fast = _timeit(cold(rate_opt.solve_bruteforce),
                                           reps)
    match = (np.array_equal(sol_ref.rates_bps, sol_fast.rates_bps)
             and sol_ref.t_com_s == sol_fast.t_com_s
             and sol_ref.lam == sol_fast.lam)
    return {
        "n": 6, "lambda_target": 0.3, "candidates": n_candidates,
        "t_reference_s": t_ref, "t_batched_s": t_fast,
        "t_reference_min_s": t_ref_min, "t_batched_min_s": t_fast_min,
        "speedup": t_ref / t_fast,
        "speedup_min": t_ref_min / t_fast_min,
        "candidates_per_s": n_candidates / t_fast,
        "match": bool(match),
    }


def check_solvers(quick: bool) -> dict:
    out: dict = {}
    seeds = range(2) if quick else range(5)
    for method in ("bruteforce", "common_rate", "k_nearest", "greedy"):
        ok = True
        for seed in seeds:
            n = 4 + seed % 3
            pos = channel.random_placement(n, 200.0, seed=seed)
            cap = channel.capacity_matrix(
                pos, channel.ChannelParams(path_loss_exp=3.5 + 0.5 * seed))
            for lam_t in (0.3, 0.7, -1.0):
                a = rate_opt._SOLVERS[method](cap, M_BITS, lam_t)
                b = rate_opt._SOLVERS[method + "_reference"](cap, M_BITS, lam_t)
                ok &= (np.array_equal(a.rates_bps, b.rates_bps)
                       and a.t_com_s == b.t_com_s and a.lam == b.lam)
        out[method] = bool(ok)
    return out


def bench_sim(reps: int, rounds: int) -> dict:
    # "pre": the retained pre-vectorization hot path, end to end — loop MAC,
    # one-rng-per-block fading, sequential Algorithm 2.
    fading_legacy = dataclasses.replace(get_scenario("fading").fading,
                                        rng_scheme="per_block")
    pre_cfg = get_scenario("fading", reference_mac=True, fading=fading_legacy,
                           solver="auto_reference")
    post_cfg = get_scenario("fading")

    def run_pre():
        rate_opt.clear_candidate_cache()   # pre-PR solvers had no memoization
        return WirelessSimulator(pre_cfg).run(rounds)

    t_pre, t_pre_min, _ = _timeit(run_pre, reps)
    t_post, t_post_min, trace = _timeit(
        lambda: WirelessSimulator(post_cfg).run(rounds), reps)
    first_pass = rounds * int(np.ceil(M_BITS / post_cfg.mac.packet_bits)) \
        * post_cfg.n_nodes
    total_packets = first_pass + trace.summary()["retx_packets"]
    return {
        "scenario": "fading", "rounds": rounds,
        "t_pre_s": t_pre, "t_post_s": t_post,
        "t_pre_min_s": t_pre_min, "t_post_min_s": t_post_min,
        "speedup": t_pre / t_post,
        "speedup_min": t_pre_min / t_post_min,
        "rounds_per_s": rounds / t_post,
        "packets_per_s": total_packets / t_post,
        "packets": total_packets,
    }


def check_mac(rounds: int) -> dict:
    out: dict = {}
    for name in ("static", "fading", "mixed"):
        tf = WirelessSimulator(get_scenario(name, solver="greedy")).run(rounds)
        tr = WirelessSimulator(get_scenario(name, solver="greedy",
                                            reference_mac=True)).run(rounds)
        out[name] = bool(
            tf.total_comm_s == tr.total_comm_s
            and all(a.t_comm_s == b.t_comm_s
                    and a.retx_packets == b.retx_packets
                    and a.outage_links == b.outage_links
                    and a.delivered_frac == b.delivered_frac
                    for a, b in zip(tf.records, tr.records)))
    # Eq. 3 static anchor
    from repro.sim import DEFAULT_MODEL_BITS
    cap = channel.capacity_matrix(
        channel.random_placement(6, 200.0, seed=0),
        channel.ChannelParams(path_loss_exp=5.0))
    sol = rate_opt.solve(cap, DEFAULT_MODEL_BITS, 0.3)
    trace = WirelessSimulator(get_scenario("static", lambda_target=0.3)).run(10)
    rel = abs(trace.total_comm_s - sol.t_com_s * 10) / (sol.t_com_s * 10)
    out["eq3_anchor_rel_err"] = rel
    out["eq3_anchor"] = bool(rel < 1e-9)
    return out


def check_access(quick: bool) -> dict:
    """Batched (p, R) sweep vs pinned sequential reference — bit-identical
    over random placements and density targets (the RA-plane analogue of
    ``check_solvers``)."""
    from repro.core import access_opt

    ok = True
    seeds = range(2) if quick else range(5)
    for seed in seeds:
        n = 4 + seed % 3
        pos = channel.random_placement(n, 200.0, seed=seed)
        cap = channel.capacity_matrix(
            pos, channel.ChannelParams(path_loss_exp=3.5 + 0.5 * seed))
        for lam_t in (0.3, 0.7, -1.0):
            a = access_opt.solve_access(cap, M_BITS, lam_t)
            b = access_opt.solve_access_reference(cap, M_BITS, lam_t)
            ok &= (np.array_equal(a.p, b.p)
                   and np.array_equal(a.rates_bps, b.rates_bps)
                   and a.t_round_s == b.t_round_s and a.lam == b.lam
                   and a.feasible == b.feasible)
    return {"solve_access": bool(ok)}


def bench_mac_compare(quick: bool) -> dict:
    """TDM vs random access on the same placement: train the paper's CNN
    through both MAC planes (one batched scan/vmap call) and report the
    accuracy-vs-simulated-time traces and communication times."""
    import time as _time

    from repro.sim import train_cnn_on_traces

    n_train = 300 if quick else 1200
    cfgs = [get_scenario("static", eval_every_rounds=2),
            get_scenario("ra_static", eval_every_rounds=2),
            get_scenario("ra_capture", eval_every_rounds=2)]
    t0 = _time.perf_counter()
    traces, out = train_cnn_on_traces(cfgs, epochs=1, n_train=n_train,
                                      n_test=150)
    dt = _time.perf_counter() - t0
    result: dict = {"t_wall_s": dt, "rounds": traces.n_rounds, "planes": {}}
    for k, cfg in enumerate(cfgs):
        s = traces.traces[k].trace.summary()
        result["planes"][cfg.name] = {
            "mac_kind": cfg.mac_kind,
            "comm_s": s["total_comm_s"],
            "outage_rate": s["outage_rate"],
            "final_acc": float(out["acc"][k, -1]),
            "curve": [[float(t), float(a)] for t, a in out["curves"][k]],
        }
    return result


def bench_compression_compare(quick: bool) -> dict:
    """fp32 vs bf16 vs int8+EF payloads on the dense fading world: wire
    bits, simulated communication time, and the quantized train-on-trace
    accuracy curves (one ``train_cnn_on_traces`` call per mode — the scan
    executable bakes the quantization mode in)."""
    import time as _time

    from repro.sim import train_cnn_on_traces

    n_train = 300 if quick else 1200
    cfgs = {
        "fp32": get_scenario("fading", eval_every_rounds=2),
        "bf16": get_scenario("compressed_bf16", eval_every_rounds=2),
        "int8_ef": get_scenario("compressed_int8", eval_every_rounds=2),
    }
    t0 = _time.perf_counter()
    result: dict = {"modes": {}}
    base_comm = None
    for label, cfg in cfgs.items():
        traces, out = train_cnn_on_traces([cfg], epochs=1, n_train=n_train,
                                          n_test=150)
        s = traces.traces[0].trace.summary()
        if base_comm is None:
            base_comm = s["total_comm_s"]
        result["modes"][label] = {
            "scenario": cfg.name,
            "payload_mode": cfg.payload.mode,
            "wire_bits": cfg.wire_bits(),
            "wire_ratio": cfg.model_bits / cfg.wire_bits(),
            "comm_s": s["total_comm_s"],
            "airtime_speedup": base_comm / s["total_comm_s"],
            "outage_rate": s["outage_rate"],
            "final_acc": float(out["acc"][0, -1]),
            "curve": [[float(t), float(a)] for t, a in out["curves"][0]],
        }
    result["t_wall_s"] = _time.perf_counter() - t0
    return result


def check_compression(quick: bool) -> dict:
    """Joint rate x payload planners vs their pinned sequential references
    — identical picked mode, wire bits, rates, times — plus the Eq. 3
    wire-bit anchor: the static scenario under an int8 payload reproduces
    ``tdm_time_s(payload_bits, rates) * rounds`` to 1e-9 relative."""
    from repro.core import access_opt, rate_opt
    from repro.sim import QuantConfig

    ok_joint = True
    ok_access = True
    seeds = range(2) if quick else range(5)
    for seed in seeds:
        n = 4 + seed % 3
        pos = channel.random_placement(n, 200.0, seed=seed)
        cap = channel.capacity_matrix(
            pos, channel.ChannelParams(path_loss_exp=3.5 + 0.5 * seed))
        for lam_t in (0.3, 0.7, -1.0):
            a = rate_opt.solve_joint(cap, M_BITS, lam_t)
            b = rate_opt.solve_joint_reference(cap, M_BITS, lam_t)
            ok_joint &= (a.mode == b.mode and a.wire_bits == b.wire_bits
                         and np.array_equal(a.rates_bps, b.rates_bps)
                         and a.t_com_s == b.t_com_s and a.lam == b.lam)
            c = access_opt.solve_access_joint(cap, M_BITS, lam_t)
            d = access_opt.solve_access_joint_reference(cap, M_BITS, lam_t)
            ok_access &= (c.mode == d.mode and c.wire_bits == d.wire_bits
                          and np.array_equal(c.p, d.p)
                          and np.array_equal(c.rates_bps, d.rates_bps)
                          and c.t_round_s == d.t_round_s and c.lam == d.lam)

    cfg = get_scenario("static", lambda_target=0.3,
                       payload=QuantConfig(mode="int8"))
    cap = channel.capacity_matrix(
        channel.random_placement(6, 200.0, seed=0),
        channel.ChannelParams(path_loss_exp=5.0))
    sol = rate_opt.solve(cap, cfg.wire_bits(), 0.3)
    trace = WirelessSimulator(cfg).run(10)
    rel = abs(trace.total_comm_s - sol.t_com_s * 10) / (sol.t_com_s * 10)
    return {
        "solve_joint": bool(ok_joint),
        "solve_access_joint": bool(ok_access),
        "eq3_wire_anchor_rel_err": rel,
        "eq3_wire_anchor": bool(rel < 1e-9),
    }


def bench_policy_compare(quick: bool) -> dict:
    """TDM vs uniform RA vs BASS on the same fading placement: the CNN
    trained through all three scheduling policies in one batched scan/vmap
    call; the headline metric is time-to-accuracy — the first simulated
    second each policy reaches the best accuracy ALL of them attain."""
    import time as _time

    from repro.sim import train_cnn_on_traces

    n_train = 300 if quick else 1200
    cfgs = [get_scenario("fading", eval_every_rounds=2),
            get_scenario("ra_fading", eval_every_rounds=2),
            get_scenario("bass_fading", eval_every_rounds=2)]
    t0 = _time.perf_counter()
    traces, out = train_cnn_on_traces(cfgs, epochs=1, n_train=n_train,
                                      n_test=150)
    dt = _time.perf_counter() - t0
    target = float(out["acc"][:, -1].min())
    result: dict = {"t_wall_s": dt, "rounds": traces.n_rounds,
                    "target_acc": target, "policies": {}}
    tta: dict = {}
    for k, cfg in enumerate(cfgs):
        s = traces.traces[k].trace.summary()
        kind = cfg.resolved_policy()
        curve = out["curves"][k]
        tta[kind] = next((float(t) for t, a in curve if a >= target),
                         float("inf"))
        result["policies"][kind] = {
            "scenario": cfg.name,
            "comm_s": s["total_comm_s"],
            "outage_rate": s["outage_rate"],
            "final_acc": float(out["acc"][k, -1]),
            "time_to_target_s": tta[kind],
            "curve": [[float(t), float(a)] for t, a in curve],
        }
    result["winner"] = min(tta, key=tta.get)
    result["bass_beats_tdm_and_ra"] = bool(
        tta["bass"] < tta["tdm"] and tta["bass"] < tta["uniform_ra"])
    return result


def bench_fault_compare(quick: bool) -> dict:
    """Graceful degradation under injected faults, head to head on the SAME
    bursty-blackout world (``fault_burst``): the fault-free baseline
    (faults stripped) vs renorm degradation + watchdog vs naive degradation.
    The gate (``checks.fault``): renorm+watchdog holds final accuracy within
    ``renorm_tol`` of fault-free, while naive W-degradation measurably
    degrades — the silent mass-leak failure mode the degrade switch exists
    to expose."""
    import time as _time

    from repro.sim import train_cnn_on_traces

    # 600 (not the 300 the other quick benches use): the renorm-vs-naive
    # accuracy gap needs a model trained past chance to be measurable.
    n_train = 600 if quick else 1200
    cfgs = {
        "fault_free": get_scenario("fault_burst", eval_every_rounds=2,
                                   faults=None),
        "renorm_watchdog": get_scenario("fault_burst", eval_every_rounds=2,
                                        watchdog=True),
        "naive": get_scenario("fault_burst", eval_every_rounds=2,
                              degrade="naive"),
    }
    t0 = _time.perf_counter()
    result: dict = {"modes": {}}
    for label, cfg in cfgs.items():
        # one call per mode: degrade/watchdog change the scan executable,
        # so the modes cannot share a vmapped family
        traces, out = train_cnn_on_traces([cfg], epochs=1, n_train=n_train,
                                          n_test=150)
        s = traces.traces[0].trace.summary()
        rb = out["rollbacks"]
        result["modes"][label] = {
            "scenario": cfg.name,
            "degrade": cfg.degrade,
            "watchdog": cfg.watchdog,
            "comm_s": s["total_comm_s"],
            "outage_rate": s["outage_rate"],
            "blackout_link_rounds": s["blackout_link_rounds"],
            "down_node_rounds": s["down_node_rounds"],
            "plan_fallback_rounds": s["plan_fallback_rounds"],
            "watchdog_rollbacks": (int(rb.sum()) if rb is not None else 0),
            "final_acc": float(out["acc"][0, -1]),
            "curve": [[float(t), float(a)] for t, a in out["curves"][0]],
        }
    result["t_wall_s"] = _time.perf_counter() - t0
    return result


def check_fault(fault_compare: dict, quick: bool) -> dict:
    """Gate on ``bench_fault_compare``: renorm+watchdog within tolerance of
    the fault-free accuracy, naive measurably below renorm. Quick mode
    trains on a sliver of data, so its tolerances are looser."""
    acc_free = fault_compare["modes"]["fault_free"]["final_acc"]
    acc_renorm = fault_compare["modes"]["renorm_watchdog"]["final_acc"]
    acc_naive = fault_compare["modes"]["naive"]["final_acc"]
    renorm_tol = 0.10 if quick else 0.05
    naive_margin = 0.02
    return {
        "acc_fault_free": acc_free,
        "acc_renorm_watchdog": acc_renorm,
        "acc_naive": acc_naive,
        "renorm_tol": renorm_tol,
        "naive_margin": naive_margin,
        "renorm_holds_accuracy": bool(acc_renorm >= acc_free - renorm_tol),
        "naive_degrades": bool(acc_naive <= acc_renorm - naive_margin),
    }


def check_sched(quick: bool) -> dict:
    """Batched (rates x fraction) accuracy-per-second sweep vs its pinned
    sequential reference — bit-identical over random placements, fraction
    grids, and duty cycles (the scheduling-plane analogue of
    ``check_access``)."""
    from repro.core import sched_opt

    ok = True
    seeds = range(2) if quick else range(5)
    for seed in seeds:
        n = 4 + seed % 3
        pos = channel.random_placement(n, 200.0, seed=seed)
        cap = channel.capacity_matrix(
            pos, channel.ChannelParams(path_loss_exp=3.5 + 0.5 * seed))
        for duty in (1.0, 0.5):
            a = sched_opt.solve_schedule(cap, M_BITS, duty_cycle=duty)
            b = sched_opt.solve_schedule_reference(cap, M_BITS,
                                                   duty_cycle=duty)
            ok &= (np.array_equal(a.rates_bps, b.rates_bps)
                   and a.tx_fraction == b.tx_fraction
                   and a.lam == b.lam and a.score_s == b.score_s
                   and a.t_round_s == b.t_round_s
                   and a.feasible == b.feasible)
    return {"solve_schedule": bool(ok)}


def bench_n_sweep(quick: bool) -> dict:
    """Large-n scaling of the whole wireless plane: at each n, one
    Algorithm 2 replan (above ``ITERATIVE_MIN_N`` that's the certified
    local-candidate sweep — power-iteration screen, exact eig only on the
    winner) and one scan-engine fading trace (``sim.jit_trace``: the round
    loop as a single compiled program). Rayleigh-only fading — the scan
    plane's stateless per-block RNG has no AR(1) shadowing. Reported per
    size: solver time, trace rounds/s, the plan's lambda, and whether the
    winner is ``certified`` (returned lambda == exact ``spectral_lambda``
    of the returned W — the contract ``checks.scale`` gates on)."""
    from repro.core.topology import spectral_lambda
    from repro.sim.jit_trace import precompute_trace_scan

    ns = (16, 64, 256) if quick else (16, 64, 256, 1024)
    rounds = 10 if quick else 30
    out: dict = {"rounds": rounds, "sizes": {}}
    for n in ns:
        cfg = get_scenario("fading", n_nodes=n,
                           **{"fading.shadowing_sigma_db": 0.0})
        t0 = time.perf_counter()
        sim = WirelessSimulator(cfg)           # __init__ runs the replan
        t_solver = time.perf_counter() - t0
        sol = sim.solution
        t0 = time.perf_counter()
        trace = precompute_trace_scan(cfg, rounds, sim=sim).trace
        t_trace = time.perf_counter() - t0
        s = trace.summary()
        out["sizes"][str(n)] = {
            "t_solver_s": t_solver,
            "t_trace_s": t_trace,
            "rounds_per_s": rounds / t_trace,
            "lambda": float(sol.lam),
            "lambda_target": cfg.lambda_target,
            "feasible": bool(sol.feasible),
            "certified": bool(sol.lam == spectral_lambda(sol.w)),
            "outage_rate": s["outage_rate"],
        }
    return out


# Mid-n planner budget (seconds). The default greedy solver at n=64 used to
# cost ~20s — every trial raise paid a full batch of exact eigs, a cliff
# sitting between the cheap small-n solves and the iterative large-n sweeps.
# The screened greedy (``rate_opt.GREEDY_SCREEN_MIN_N``: optimistic exact
# certs + lazy power-iteration pre-screen, bit-identical picks) brings it to
# ~2-4s; the budget is generous so slow CI boxes pass, but a regression back
# to the unscreened cliff fails loudly.
MID_N_SOLVER_BUDGET_S = 12.0


def check_scale(n_sweep: dict) -> dict:
    """Gate: at every n the winning plan's lambda must be the exact eig of
    its W (certify-on-winner) and the plan must clear the density target;
    the n=64 solve must also stay under ``MID_N_SOLVER_BUDGET_S`` (the
    mid-n greedy cliff fixed by the screened ``solve_greedy``)."""
    sizes = n_sweep["sizes"]
    mid = sizes.get("64")
    mid_n_fast = bool(mid is None or mid["t_solver_s"] <= MID_N_SOLVER_BUDGET_S)
    return {
        "certified": {n: v["certified"] for n, v in sizes.items()},
        "feasible": {n: v["feasible"] for n, v in sizes.items()},
        "mid_n_t_solver_s": (None if mid is None else mid["t_solver_s"]),
        "mid_n_budget_s": MID_N_SOLVER_BUDGET_S,
        "mid_n_fast": mid_n_fast,
        "all_certified": bool(all(v["certified"] for v in sizes.values())),
        "all_feasible": bool(all(v["feasible"] for v in sizes.values())),
    }


def bench_sweep(quick: bool) -> dict:
    seeds = range(2) if quick else range(5)
    configs = [get_scenario(name, seed=s, solver="greedy")
               for name in ("static", "fading") for s in seeds]
    n_rounds = 3 if quick else 8
    t0 = time.perf_counter()
    traces = sweep(configs, n_rounds)
    dt = time.perf_counter() - t0
    total_rounds = sum(len(t.records) for t in traces)
    return {
        "configs": len(configs), "rounds_per_config": n_rounds,
        "t_s": dt, "rounds_per_s": total_rounds / dt,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke: fewer reps/rounds, same cross-checks")
    ap.add_argument("--out", default=None,
                    help="output JSON path (default: repo-root BENCH_sim.json)")
    args = ap.parse_args(argv)

    from repro.utils.compile_cache import use_compile_cache

    use_compile_cache()
    from repro.analysis import repo_is_clean

    reps = 1 if args.quick else 9
    rounds = 10 if args.quick else 30
    result = {
        "schema": "bench_sim/v1",
        "quick": bool(args.quick),
        "platform": platform.platform(),
        "numpy": np.__version__,
        "analysis_clean": repo_is_clean(),
        "solver": bench_solver(reps),
        "sim": bench_sim(reps, rounds),
        "sweep": bench_sweep(args.quick),
        "n_sweep": bench_n_sweep(args.quick),
        "mac_compare": bench_mac_compare(args.quick),
        "compression_compare": bench_compression_compare(args.quick),
        "policy_compare": bench_policy_compare(args.quick),
        "fault_compare": bench_fault_compare(args.quick),
        "checks": {
            "solver": check_solvers(args.quick),
            "access": check_access(args.quick),
            "compression": check_compression(args.quick),
            "sched": check_sched(args.quick),
            "mac": check_mac(4 if args.quick else 8),
        },
    }
    result["checks"]["fault"] = check_fault(result["fault_compare"],
                                            args.quick)
    result["checks"]["scale"] = check_scale(result["n_sweep"])
    checks = result["checks"]
    failed = (not result["solver"]["match"]
              or not all(checks["solver"].values())
              or not all(checks["access"].values())
              or not all(v for k, v in checks["compression"].items()
                         if isinstance(v, bool))
              or not all(checks["sched"].values())
              or not result["policy_compare"]["bass_beats_tdm_and_ra"]
              or not all(v for k, v in checks["mac"].items()
                         if isinstance(v, bool))
              or not all(v for k, v in checks["fault"].items()
                         if isinstance(v, bool))
              or not checks["scale"]["all_certified"]
              or not checks["scale"]["all_feasible"]
              or not checks["scale"]["mid_n_fast"])
    result["ok"] = not failed

    text = json.dumps(result, indent=2)
    print(text)
    if args.out:
        Path(args.out).write_text(text + "\n")
    elif not args.quick:
        # only full runs update the tracked perf trajectory; --quick (CI
        # smoke) must not clobber it with reps=1 numbers
        out = Path(__file__).resolve().parent.parent / "BENCH_sim.json"
        out.write_text(text + "\n")
    if failed:
        print("FAIL: batched implementations diverged from pinned references",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
