"""Multi-device integration tests.

These spawn subprocesses with XLA_FLAGS=--xla_force_host_platform_device_count=8
because the main pytest process must keep seeing ONE device (per the repo
policy: only the dry-run and explicit dist tests fake a device count).
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(_ROOT, "src")
    env["JAX_PLATFORMS"] = "cpu"   # faked host devices; never the chip
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env, timeout=900)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


def test_shard_map_gossip_matches_dense_w():
    """core.gossip ppermute mixing on a real 8-device mesh == plan_w @ X."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from jax.experimental.shard_map import shard_map
        from repro.core.gossip import ring_plan, plan_w, gossip_mix_array
        mesh = jax.make_mesh((8,), ("data",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        plan = ring_plan(("data",), (8,), 2)
        x = jax.random.normal(jax.random.key(0), (8, 16))
        fn = shard_map(lambda v: gossip_mix_array(v[0], plan)[None],
                       mesh=mesh, in_specs=P("data"), out_specs=P("data"))
        got = np.asarray(jax.jit(fn)(x))
        want = plan_w(plan) @ np.asarray(x)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
        print("OK")
    """)
    assert "OK" in out


def test_mode_b_trainstep_on_mesh_contains_collective_permute():
    """The Mode B train step on a (4 data x 2 model) mesh lowers the gossip
    to collective-permute (not all-gather) and runs to a finite loss."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import RunConfig, get_config, reduce_for_smoke
        from repro.core.gossip import ring_plan
        from repro.models import build
        from repro.optim.schedule import constant_lr
        from repro.train import shardings as shr
        from repro.train.step import init_train_state, make_train_step
        mesh = jax.make_mesh((4, 2), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        cfg = reduce_for_smoke(get_config("nemotron-4-15b"))
        api = build(cfg)
        run = RunConfig(mode="dpsgd", optimizer="sgd", remat="none")
        plan = ring_plan(("data",), (4,), 1)
        step = make_train_step(api, run, plan, constant_lr(0.01),
                               node_axes=("data",))
        state = init_train_state(api, run, jax.random.key(0), n_nodes=4)
        pspecs = shr.param_specs(state["params"], 2, kv_dim=cfg.kv_dim)
        pspecs = jax.tree.map(lambda s: P("data", *tuple(s)[1:]), pspecs,
                              is_leaf=lambda x: isinstance(x, P))
        sspecs = {"params": pspecs, "opt": state["opt"] and {} or {}, "step": P()}
        state = jax.device_put(state, {
            "params": jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                                   is_leaf=lambda x: isinstance(x, P)),
            "opt": {}, "step": NamedSharding(mesh, P())})
        tokens = jax.random.randint(jax.random.key(1), (4, 2, 32), 0,
                                    cfg.vocab_size, jnp.int32)
        batch = {"tokens": jax.device_put(
            tokens, NamedSharding(mesh, P("data", None, None)))}
        with mesh:
            jstep = jax.jit(step)
            lowered = jstep.lower(state, batch)
            compiled = lowered.compile()
            txt = compiled.as_text()
            ncp = txt.count("collective-permute")
            state2, m = jstep(state, batch)
        assert ncp > 0, "no collective-permute in Mode B HLO"
        assert np.isfinite(float(m["loss"]))
        print("OK ncp=", ncp)
    """)
    assert "OK" in out


def test_dryrun_cell_on_8_devices():
    """run_cell logic on a small host mesh via the launch driver (smoke of the
    512-device path without the big compile)."""
    out = _run("""
        import jax, numpy as np
        from repro.core.density_controller import choose_plan
        ch = choose_plan(("pod", "data"), (2, 4), 0.95, 1e8)
        assert ch.feasible
        print("OK", ch.plan.name)
    """, devices=8)
    assert "OK" in out


def test_allreduce_mode_matches_single_node_sgd():
    """Mode A on 4-way data parallel == single-process SGD on the full batch."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import RunConfig, get_config, reduce_for_smoke
        from repro.models import build
        from repro.optim.schedule import constant_lr
        from repro.train.step import init_train_state, make_train_step
        cfg = reduce_for_smoke(get_config("stablelm-3b"))
        api = build(cfg)
        run = RunConfig(mode="allreduce", optimizer="sgd", remat="none")
        step = make_train_step(api, run, None, constant_lr(0.05))
        state = init_train_state(api, run, jax.random.key(0))
        tokens = jax.random.randint(jax.random.key(1), (8, 32), 0,
                                    cfg.vocab_size, jnp.int32)
        # sharded run
        mesh = jax.make_mesh((4, 2), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        b_sh = jax.device_put(tokens, NamedSharding(mesh, P("data", None)))
        with mesh:
            s1, m1 = jax.jit(step)(state, {"tokens": b_sh})
        # single-device run
        s2, m2 = jax.jit(step)(state, {"tokens": tokens})
        np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                                   rtol=1e-5)
        for a, b in zip(jax.tree.leaves(s1["params"]),
                        jax.tree.leaves(s2["params"])):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-5)
        print("OK")
    """)
    assert "OK" in out
