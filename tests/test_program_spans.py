"""The program's host spans (``repro.*``) and the D-PSGD step's name scopes
(``dpsgd.*``): recorded by the profiler with their arguments, nested as
documented, present in the compiled step's op metadata, and without effect
on any output."""
import glob
import json
import os
import subprocess
import sys
import textwrap
from functools import partial

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.core import dpsgd  # noqa: E402
from repro.core.compression import QuantConfig  # noqa: E402
from repro.sim import WirelessSimulator, get_scenario  # noqa: E402
from repro.sim.batch import ModelAdapter, train_model_on_traces  # noqa: E402
from repro.sim.jit_trace import precompute_trace_scan  # noqa: E402
from repro.sim.trace import precompute_trace, stack_traces  # noqa: E402
from repro.utils.spans import PREFIX  # noqa: E402

# each span and the span it lies in
PARENT = {
    "repro.plan.capacity": "repro.plan",
    "repro.plan.solve": "repro.plan",
    "repro.plan.screen": "repro.plan.solve",
    "repro.plan.certify": "repro.plan.solve",
    "repro.plan.links": "repro.plan",
    "repro.scan.prepare": "repro.scan",
    "repro.scan.run": "repro.scan",
    "repro.scan.records": "repro.scan",
    "repro.train.prep": "repro.train",
    "repro.train.run": "repro.train",
    "repro.train.post": "repro.train",
}
ARGS = {
    "repro.plan": {"seed", "n"},
    "repro.plan.solve": {"method"},
    "repro.plan.screen": {"candidates", "n"},
    "repro.scan": {"seed", "n", "rounds", "packets"},
    "repro.train": {"traces", "rounds", "nodes", "mix", "experts_held"},
}
FADING = {"fading.shadowing_sigma_db": 0.0}


def _linear_loss(p, b):
    return jnp.mean((b["x"] @ p["w"] - b["y"]) ** 2)


def _linear_batches(cfg, tr):
    x = np.random.default_rng(cfg.seed).normal(
        size=(tr.w_eff.shape[0], tr.n_nodes, 3, 4)).astype(np.float32)
    return {"x": x, "y": 0.5 * x[..., :2]}


LINEAR = ModelAdapter(
    name="linear", loss_fn=_linear_loss, batch_fn=_linear_batches,
    init_params=lambda seed: {"w": jnp.full((4, 2), 0.1 * (seed % 5 + 1))})


def _work():
    """A plan at n = 128 (the certified sweeps), a 4-round scan trace at
    n = 16 and a 2-node, 2-round training call."""
    big = WirelessSimulator(get_scenario("fading", n_nodes=128, seed=3,
                                         **FADING))
    tr = precompute_trace_scan(get_scenario("fading", n_nodes=16, seed=5,
                                            **FADING), 4)
    cfg = get_scenario("static", n_nodes=2, seed=7)
    traces = stack_traces([precompute_trace(cfg, 2)])
    _, out = train_model_on_traces(LINEAR, [cfg], 2, trace_batch=traces,
                                   unroll=1)
    jax.block_until_ready(out["final_params"])
    return big.solution, tr, out


@pytest.fixture(scope="module")
def profiled(tmp_path_factory):
    """The work run once without the profiler and once under it, and the
    trace of the second run."""
    out = str(tmp_path_factory.mktemp("profile"))
    plain = _work()
    jax.profiler.start_trace(out)
    try:
        traced = _work()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(f"{out}/plugins/profile/*/*.xplane.pb")
    data = jax.profiler.ProfileData.from_file(path)
    host = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
             dict(ev.stats), line.name)
            for plane in data.planes if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events]
    return plain, traced, host


def _spans(host, name):
    return [h for h in host if h[0] == name]


def test_every_span_is_recorded_inside_its_parent(profiled):
    _, _, host = profiled
    names = {h[0] for h in host if h[0].startswith(PREFIX)}
    assert names == set(PARENT) | set(PARENT.values())
    for child, parent in PARENT.items():
        for _, a, b, _, _ in _spans(host, child):
            assert any(pa <= a and b <= pb
                       for _, pa, pb, _, _ in _spans(host, parent)), child


def test_spans_carry_their_arguments(profiled):
    _, _, host = profiled
    for name, keys in ARGS.items():
        for *_, args, _ in _spans(host, name):
            assert set(args) == keys, name
    plans = {(a["seed"], a["n"]) for *_, a, _ in _spans(host, "repro.plan")}
    assert plans == {(3, 128), (5, 16), (7, 2)}
    methods = {a["method"] for *_, a, _ in _spans(host, "repro.plan.solve")}
    assert methods == {"k_nearest", "common_rate", "greedy", "bruteforce"}
    screens = [a for *_, a, _ in _spans(host, "repro.plan.screen")]
    assert len(screens) == 2 and all(a["n"] == 128 and a["candidates"] > 0
                                     for a in screens)
    (*_, scan, _), = _spans(host, "repro.scan")
    cfg = get_scenario("fading", n_nodes=16)
    assert scan == {"seed": 5, "n": 16, "rounds": 4,
                    "packets": -(-int(cfg.model_bits) // cfg.mac.packet_bits)}
    (*_, train, _), = _spans(host, "repro.train")
    assert train == {"traces": 1, "rounds": 2, "nodes": 2, "mix": "combine",
                     "experts_held": 0}


def test_each_certification_is_one_span_inside_a_certified_sweep(profiled):
    _, _, host = profiled
    certs = _spans(host, "repro.plan.certify")
    # one sweep per solver at n = 128, each certifying at least its winner
    assert len(certs) >= 2
    for _, a, b, _, _ in certs:
        assert any(pa <= a and b <= pb and args["n"] == 128
                   for _, pa, pb, args, _ in _spans(host, "repro.plan"))


def test_the_channel_scan_compiles_under_a_stable_name(profiled):
    _, _, host = profiled
    modules = {args.get("hlo_module") for *_, args, _ in host}
    assert "jit_channel_round_scan" in modules


def test_outputs_are_bit_identical_with_the_profiler_on(profiled):
    (plan0, tr0, out0), (plan1, tr1, out1), _ = profiled
    assert np.array_equal(plan0.rates_bps, plan1.rates_bps)
    for field in ("w_eff", "t_start_s", "t_comm_s"):
        assert np.array_equal(getattr(tr0, field), getattr(tr1, field))
    assert np.array_equal(out0["losses"], out1["losses"])
    for a, b in zip(jax.tree.leaves(out0["final_params"]),
                    jax.tree.leaves(out1["final_params"])):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def _step_inputs(n: int = 2):
    params = {"w": jnp.zeros((n, 4, 2))}
    batch = {"x": jnp.ones((n, 3, 4)), "y": jnp.ones((n, 3, 2))}
    return params, batch, jnp.full((n, n), 1.0 / n), jnp.ones(n, bool)


@pytest.mark.parametrize("step,scopes", [
    ("dpsgd_step", ("dpsgd.grad", "dpsgd.mix", "dpsgd.update")),
    ("dpsgd_masked_step", ("dpsgd.grad", "dpsgd.mix", "dpsgd.update")),
    ("dpsgd_masked_compressed_step",
     ("dpsgd.grad", "dpsgd.mix", "dpsgd.quantize", "dpsgd.update")),
])
def test_step_scopes_reach_the_compiled_program(step, scopes):
    params, batch, w, live = _step_inputs()
    if step == "dpsgd_step":
        lowered = dpsgd.dpsgd_step.lower(_linear_loss, params, batch, w)
    elif step == "dpsgd_masked_step":
        lowered = jax.jit(partial(dpsgd.dpsgd_masked_step, _linear_loss)
                          ).lower(params, batch, w, live)
    else:
        fn = partial(dpsgd.dpsgd_masked_compressed_step, _linear_loss,
                     quant=QuantConfig(mode="int8"))
        lowered = jax.jit(fn).lower(params, batch, w, live,
                                    dpsgd.zero_residuals(params))
    text = lowered.compile().as_text()
    for scope in scopes:
        assert f"/{scope}/" in text, scope


def test_train_span_says_exchange_on_a_fleet_mesh(tmp_path):
    """Over a mesh whose fleet axis shards the node axis the ``repro.train``
    span carries ``mix="exchange"``; over the same mesh with a node count
    that does not divide the fleet, the one-device mix: ``mix="combine"``
    up to ``COMBINE_MAX_NODES`` nodes, ``mix="dense"`` past it. Four host
    devices, in a subprocess: this process keeps seeing one."""
    code = textwrap.dedent(f"""
        import glob, json
        import jax
        from repro.launch.mesh import make_fleet_mesh
        from repro.sim import get_scenario
        from repro.sim.batch import train_model_on_traces
        from repro.sim.trace import precompute_trace, stack_traces
        from test_program_spans import LINEAR

        mesh = make_fleet_mesh(4, 1)
        jax.profiler.start_trace({str(tmp_path)!r})
        for n in (4, 6, 10):
            cfg = get_scenario("static", n_nodes=n, seed=7)
            traces = stack_traces([precompute_trace(cfg, 2)])
            _, out = train_model_on_traces(LINEAR, [cfg], 2,
                                           trace_batch=traces, unroll=1,
                                           mesh=mesh)
            jax.block_until_ready(out["final_params"])
        jax.profiler.stop_trace()
        path, = glob.glob({str(tmp_path)!r} + "/plugins/profile/*/*.xplane.pb")
        data = jax.profiler.ProfileData.from_file(path)
        print(json.dumps(sorted(
            (dict(ev.stats)["nodes"], dict(ev.stats)["mix"])
            for plane in data.planes for line in plane.lines
            for ev in line.events if ev.name == "repro.train")))
    """)
    here = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(here), "src"), here])
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=600)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    spans = json.loads(out.stdout.strip().splitlines()[-1])
    assert spans == [[4, "exchange"], [6, "combine"], [10, "dense"]]
