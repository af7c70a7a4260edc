"""Sharded real-model train-on-trace smoke — runnable as a module.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python -m repro.sim.real_model_smoke --json

Builds the smoke-reduced transformer (``sim.batch.transformer_adapter``),
realizes a fading trace, and runs train-on-trace three ways:

1. the per-round reference loop (``train_on_trace_reference``) — the oracle;
2. the jitted scan with node-parameters laid out over a
   ``launch.mesh.make_fleet_mesh`` (``train.shardings.node_param_specs``),
   mixing by ``core.dpsgd.exchange_mix`` where the node axis divides over
   the fleet, asserting the final parameters actually span >= 2 devices;
3. the full ``train_model_on_traces`` driver on the same mesh.

All three must agree to the parity bound (<=1e-5 on final params and
per-round losses). Exit code 0 + a JSON report on stdout when they do —
CI's multi-device job, ``benchmarks/bench_train.py``'s ``real_model``
section, and the pytest smoke all drive this one entry point, so there is
exactly one definition of "the sharded path works".
"""
from __future__ import annotations

import argparse
import json
import sys


def run(arch: str = "stablelm-3b", scenario: str = "fading", rounds: int = 4,
        fleet: int = 2, model: int = 2, batch: int = 2, seq_len: int = 16,
        eta: float = 0.05, tol: float = 1e-5,
        n_nodes: int | None = None) -> dict:
    """Run the smoke; returns the report dict (key ``ok``). ``n_nodes``
    overrides the scenario's fleet size (the node axis shards only when it
    divides ``fleet``)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..checkpoint.ckpt import compact_nodes
    from ..core import dpsgd
    from ..core.dpsgd import DPSGDConfig
    from ..launch.mesh import make_fleet_mesh
    from ..train.shardings import node_param_specs
    from .batch import (_exchange_mesh, _mix_kind, train_model_on_traces,
                        train_on_trace, train_on_trace_reference,
                        transformer_adapter)
    from .scenario import get_scenario
    from .trace import precompute_traces

    adapter = transformer_adapter(arch, batch=batch, seq_len=seq_len)
    cfg = get_scenario(scenario, model_bits=adapter.model_bits,
                       model_shapes=adapter.param_shapes,
                       eval_every_rounds=rounds,
                       **({} if n_nodes is None else {"n_nodes": n_nodes}))
    tb = precompute_traces([cfg], rounds)
    tr = tb.traces[0]
    batches = adapter.batch_fn(cfg, tr)
    params0 = dpsgd.replicate(adapter.init_params(cfg.seed), cfg.n_nodes)
    config = DPSGDConfig(eta=eta)

    # 1. per-round reference (unsharded, host loop)
    ref_final, ref_losses = train_on_trace_reference(
        adapter.loss_fn, params0, tr.w_eff, tr.live, batches, config,
        payload=cfg.payload, active_seq=tr.active)

    # 2. sharded scan: node axis over 'fleet', tensors over 'model'
    mesh = make_fleet_mesh(fleet, model)
    specs = node_param_specs(params0, mesh)
    p_leaves, tdef = jax.tree.flatten(params0)
    s_leaves = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    p0_sharded = jax.tree.unflatten(tdef, [
        jax.device_put(x, NamedSharding(mesh, s))
        for x, s in zip(p_leaves, s_leaves)])
    b_sharded = jax.tree.map(
        lambda b: jax.device_put(
            jnp.asarray(b),
            NamedSharding(mesh, P(None, "fleet",
                                  *([None] * (np.ndim(b) - 2))))
            if b.shape[1] % fleet == 0
            else NamedSharding(mesh, P())),
        batches)
    mix_mesh = _exchange_mesh(mesh, cfg.n_nodes, cfg.payload)
    final, losses = train_on_trace(
        adapter.loss_fn, p0_sharded, jnp.asarray(tr.w_eff),
        jnp.asarray(tr.live), b_sharded, config, unroll=1,
        payload=cfg.payload, active_seq=jnp.asarray(tr.active),
        mesh=mix_mesh)
    device_span = {d.id for leaf in jax.tree.leaves(final)
                   for d in leaf.sharding.device_set}
    # leaves split across devices (replicated leaves span every device too)
    sharded_leaves = sum(not leaf.sharding.is_fully_replicated
                         for leaf in jax.tree.leaves(final))
    param_diff = max(float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                           - b.astype(jnp.float32))))
                     for a, b in zip(jax.tree.leaves(final),
                                     jax.tree.leaves(ref_final)))
    loss_diff = float(np.max(np.abs(np.asarray(losses) - ref_losses)))

    # 3. the full driver on the same mesh vs the reference's masked means
    _, out = train_model_on_traces(
        adapter, [cfg], rounds, eta=eta, trace_batch=tb, unroll=1, mesh=mesh)
    ref_mean = (np.where(tr.live, ref_losses, 0.0).sum(-1)
                / tr.live.sum(-1))
    driver_loss_diff = float(np.max(np.abs(out["losses"][0] - ref_mean)))
    driver_param_diff = max(
        float(jnp.max(jnp.abs(a.astype(jnp.float32)
                              - b.astype(jnp.float32))))
        for a, b in zip(jax.tree.leaves(out["final_params"][0]),
                        jax.tree.leaves(compact_nodes(ref_final,
                                                      tr.live[-1]))))

    report = {
        "arch": adapter.name,
        "scenario": scenario,
        "rounds": rounds,
        "n_nodes": cfg.n_nodes,
        "mesh": {"fleet": fleet, "model": model},
        "mix": _mix_kind(mix_mesh, cfg.n_nodes, cfg.payload),
        "devices_visible": jax.device_count(),
        "devices_spanned": len(device_span),
        "sharded_leaves": sharded_leaves,
        "model_bits": adapter.model_bits,
        "wire_bits": cfg.wire_bits(),
        "parity": {
            "sharded_vs_reference_params": param_diff,
            "sharded_vs_reference_losses": loss_diff,
            "driver_vs_reference_losses": driver_loss_diff,
            "driver_vs_reference_params": driver_param_diff,
            "tol": tol,
        },
        "final_loss": float(out["losses"][0][-1]),
        "eval_metric": (float(out["acc"][0][-1])
                        if out["acc"] is not None else None),
    }
    report["ok"] = bool(
        len(device_span) >= 2 and sharded_leaves > 0
        and param_diff <= tol and loss_diff <= tol
        and driver_loss_diff <= tol and driver_param_diff <= tol)
    return report


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--arch", default="stablelm-3b")
    ap.add_argument("--scenario", default="fading")
    ap.add_argument("--rounds", type=int, default=4)
    ap.add_argument("--fleet", type=int, default=2)
    ap.add_argument("--model", type=int, default=2)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--seq-len", type=int, default=16)
    ap.add_argument("--json", action="store_true",
                    help="emit the full report as JSON on stdout")
    args = ap.parse_args(argv)
    report = run(arch=args.arch, scenario=args.scenario, rounds=args.rounds,
                 fleet=args.fleet, model=args.model, batch=args.batch,
                 seq_len=args.seq_len)
    if args.json:
        print(json.dumps(report))
    else:
        status = "OK" if report["ok"] else "FAIL"
        print(f"[real_model_smoke] {status}: {report['arch']} on "
              f"{report['scenario']}, {report['devices_spanned']} devices, "
              f"parity {report['parity']}")
    return 0 if report["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
