"""The reduction of the program's own spans and scopes
(``program_trace.ProgramReduction``): its readings on traces built here,
the same answers as ``trace_reduce.Reduction`` from every method and every
metric reader, and the ``<bench span>/<program span>`` labels of idle
gaps."""
import json
import types

import numpy as np
import pytest

import benchlib
import program_trace as pt
import trace_reduce as tr

NS = 1e-9


def _device(events):
    """events: (start_ns, dur_ns, hlo text, scope or None) -> (DeviceOps,
    scope ids) with the scope names of ``SCOPES``."""
    names = {}
    ids = [names.setdefault(e[2], len(names)) for e in events]
    ops = tr.DeviceOps.from_names([e[0] for e in events],
                                  [e[1] for e in events], ids, names)
    return ops, [SCOPES.index(e[3]) if e[3] else -1 for e in events]


SCOPES = ["dpsgd.grad", "dpsgd.mix", "dpsgd.update"]
GATHER = "%all-gather.1 = f32[8] all-gather(f32[2] %p)"
FUSION = "%fusion.1 = f32[8] fusion(f32[8] %p)"


def _reductions(events_by_chip, spans, program_spans):
    """(Reduction, ProgramReduction) of the same trace."""
    ops, sids = {}, {}
    for chip, events in events_by_chip.items():
        ops[chip], sids[chip] = _device(events)
    return (tr.Reduction(ops, spans),
            pt.ProgramReduction(ops, spans, program_spans, sids, SCOPES))


@pytest.fixture
def train_trace():
    """One 2-round training call on two chips: preparation 0-10, the
    compiled call 10-92 (grad, mix's all-gather, update, inside a loop),
    post-processing 92-100."""
    chip0 = [(10, 80, "%while.1 = (f32[2]) while(...)", "dpsgd.mix"),
             (2, 4, FUSION, None),
             (12, 28, "%fusion.2 = f32[8] fusion(f32[8] %g)", "dpsgd.grad"),
             (40, 20, GATHER, "dpsgd.mix"),
             (60, 10, "%fusion.3 = f32[8] fusion(f32[8] %u)", "dpsgd.update"),
             (94, 2, "%copy.1 = f32[8] copy(f32[8] %c)", None)]
    chip1 = [(12, 18, "%convolution.1 = f32[4] convolution(...)", "dpsgd.grad"),
             (40, 10, GATHER, "dpsgd.mix"),
             (60, 4, "%fusion.3 = f32[8] fusion(f32[8] %u)", "dpsgd.update")]
    spans = [("window", 0, 100 * NS), ("unit", 0, 100 * NS),
             ("train", 1 * NS, 99 * NS)]
    program = [
        ("repro.train", 1 * NS, 99 * NS, {"traces": 1, "rounds": 2, "nodes": 2}),
        ("repro.train.prep", 1 * NS, 10 * NS, {}),
        ("repro.train.run", 10 * NS, 92 * NS, {}),
        ("repro.train.post", 92 * NS, 99 * NS, {})]
    return _reductions({0: chip0, 1: chip1}, spans, program)


@pytest.fixture
def chan_trace():
    """Two traces on one chip: plan 0-30 and 50-80 (pre-screen and three
    certifications between them), scan 30-50 and 80-100."""
    chip = [(32, 7, FUSION, None), (82, 7, FUSION, None)]
    spans = [("window", 0, 100 * NS), ("unit", 0, 50 * NS),
             ("unit", 50 * NS, 100 * NS), ("plan", 0, 30 * NS),
             ("scan", 30 * NS, 50 * NS), ("plan", 50 * NS, 80 * NS),
             ("scan", 80 * NS, 100 * NS)]
    program = [
        ("repro.plan", 1 * NS, 29 * NS, {"seed": 1, "n": 256}),
        ("repro.plan.screen", 5 * NS, 15 * NS, {"candidates": 72, "n": 256}),
        ("repro.plan.certify", 16 * NS, 18 * NS, {}),
        ("repro.plan.certify", 18 * NS, 20 * NS, {}),
        ("repro.plan", 51 * NS, 79 * NS, {"seed": 2, "n": 256}),
        ("repro.plan.screen", 55 * NS, 60 * NS, {"candidates": 72, "n": 256}),
        ("repro.plan.certify", 61 * NS, 64 * NS, {}),
        ("repro.scan", 31 * NS, 49 * NS, {"seed": 1}),
        ("repro.scan.records", 40 * NS, 48 * NS, {}),
        ("repro.scan", 81 * NS, 99 * NS, {"seed": 2}),
        ("repro.scan.records", 90 * NS, 96 * NS, {})]
    return _reductions({0: chip}, spans, program)


def test_scope_is_the_innermost_dpsgd_component():
    assert pt.scope_of("jit(f)/while/body/dpsgd.mix/dot_general") == "dpsgd.mix"
    assert pt.scope_of("jit(f)/dpsgd.grad/transpose(jvp(dpsgd.update))/mul") \
        == "dpsgd.update"
    assert pt.scope_of("jit(f)/while/body/add") is None


def test_device_time_under_each_scope(train_trace):
    _, red = train_trace
    # the loop holds everything and is left out; chips 28 + 18, 20 + 10,
    # 10 + 4 ns
    assert red.busy_in_scope("dpsgd.grad", "train") == pytest.approx(23 * NS)
    assert red.busy_in_scope("dpsgd.mix", "train") == pytest.approx(15 * NS)
    assert red.busy_in_scope("dpsgd.update", "train") == pytest.approx(7 * NS)
    assert red.busy_in_scope("dpsgd.quantize", "train") == 0.0
    assert red.busy_in_scope("dpsgd.grad", "plan") == 0.0
    assert red.collective_in_scope("dpsgd.mix", "train") == pytest.approx(
        red.collective_in("train"))
    assert red.collective_in_scope("dpsgd.grad", "train") == 0.0


def test_training_readings(train_trace):
    _, red = train_trace
    got = red.readings()
    # per round of the call's 2, in ms
    assert got["step_grad_ms"] == pytest.approx(1e3 * 23 * NS / 2)
    assert got["step_mix_ms"] == pytest.approx(1e3 * 15 * NS / 2)
    assert got["step_update_ms"] == pytest.approx(1e3 * 7 * NS / 2)
    # prep 1-10 holds chip 0's 4 ns op, post 92-99 its 2 ns copy
    assert got["train_prep_ms"] == pytest.approx(1e3 * (9 - 2) * NS)
    assert got["train_post_ms"] == pytest.approx(1e3 * (7 - 1) * NS)
    assert got["collective_in_mix_pct"] == pytest.approx(100.0)
    assert got["planner_certs"] is None and got["scan_records_ms"] is None


def test_planner_and_scan_readings(chan_trace):
    _, red = chan_trace
    got = red.readings()
    assert got["planner_screen_ms"] == pytest.approx(1e3 * (10 + 5) * NS / 2)
    assert got["planner_certify_ms"] == pytest.approx(1e3 * (2 + 2 + 3) * NS / 2)
    assert got["planner_certs"] == 1.5
    assert got["scan_records_ms"] == pytest.approx(1e3 * (8 + 6) * NS / 2)
    assert got["step_mix_ms"] is None and got["train_prep_ms"] is None
    assert red.spans_per_unit()["repro.plan.certify"] == 1.5
    assert red.program_spans_named("repro.plan")[1][2] == {"seed": 2, "n": 256}


def test_gaps_are_labelled_with_the_program_span_they_fell_in(chan_trace):
    base, red = chan_trace
    assert [g[0] for g in base.idle_gaps()] == ["plan", "plan", "scan"]
    assert red.idle_gaps() == [
        ["plan/repro.plan", pytest.approx(43 * NS)],
        ["plan/repro.plan.certify", pytest.approx(32 * NS)],
        ["scan/repro.scan.records", pytest.approx(11 * NS)]]


@pytest.mark.parametrize("fixture", ["train_trace", "chan_trace"])
def test_every_method_of_the_reduction_reads_as_before(fixture, request):
    base, red = request.getfixturevalue(fixture)
    for name in ("window_s", "busy_s", "collective_s",
                 "collective_exposed_s", "top_ops", "per_device"):
        assert getattr(red, name)() == getattr(base, name)(), name
    for span in ("train", "plan", "scan", "unit"):
        assert red.busy_in(span) == base.busy_in(span)
        assert red.collective_in(span) == base.collective_in(span)
        assert red.spans_named(span) == base.spans_named(span)
    # the gaps are the same; a program span only lengthens their label
    for (lb, tb), (lp, tp) in zip(base.idle_gaps(), red.idle_gaps()):
        assert tp == tb and (lp == lb or lp.startswith(lb + "/repro."))
    bare = pt.ProgramReduction(red.ops, red.spans)
    assert bare.breakdown() == base.breakdown()
    assert all(v is None for v in bare.readings().values())


@pytest.mark.parametrize("fixture", ["train_trace", "chan_trace"])
def test_every_metric_reader_reads_as_before(fixture, request):
    base, red = request.getfixturevalue(fixture)
    lm = benchlib.SPEC["configs"][1]
    config = json.loads((benchlib.ROOT / lm["file"]).read_text())

    def ctx(trace):
        return types.SimpleNamespace(
            trace=trace, info={"rounds_per_call": 2,
                               "tokens_per_node_round": 256},
            device={"memory_peak_bytes": 2 ** 30},
            peaks={"bf16_flops": 1.97e14}, window_s=1.0, work=64.0,
            units=2, devices=[0, 1], config=config,
            traffic={"seq_len": 256})
    readers = sorted((benchlib.BENCH / "metrics").glob("*.py"))
    assert readers
    for path in readers:
        read = benchlib.harness.load_module(path).read
        assert read(ctx(red)) == read(ctx(base)), path.stem


def test_a_recorded_cpu_trace_gives_the_program_spans(tmp_path, capsys):
    import jax

    from repro.utils.spans import span

    x = jax.numpy.ones((32, 32))
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench.window"), \
                jax.profiler.TraceAnnotation("bench.unit"), \
                jax.profiler.TraceAnnotation("bench.plan"):
            with span("plan", seed=11, n=4), span("plan.certify"):
                (x @ x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    red = pt.ProgramReduction.from_dir(str(tmp_path), device_ids=[0])
    assert [s[0] for s in red.program_spans] == ["repro.plan",
                                                  "repro.plan.certify"]
    (a, b, args), = red.program_spans_named("repro.plan")
    assert args == {"seed": 11, "n": 4} and a < b
    assert red.readings()["planner_certs"] == 1.0
    assert red.ops == {} and red.scope_names == []
    assert pt.main([str(tmp_path)]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["spans_per_unit"] == {"repro.plan": 1.0,
                                     "repro.plan.certify": 1.0}
    assert np.isfinite(out["readings"]["planner_certify_ms"])


# -- a TPU-shaped trace written here -----------------------------------------

def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _pb(field: int, value) -> bytes:
    """One protobuf field: an int as a varint, text or bytes as a
    length-delimited field."""
    if isinstance(value, int):
        return _varint(field << 3) + _varint(value)
    data = value.encode() if isinstance(value, str) else value
    return _varint(field << 3 | 2) + _varint(len(data)) + data


def _entry(key: int, value: bytes) -> bytes:
    return _pb(1, key) + _pb(2, value)


def _plane(name, line, events, metas, stat_names) -> bytes:
    """An XPlane with one line of (metadata id, start ns, duration ns,
    stats) events, its event metadata and its stat names."""
    body = b"".join(_pb(4, _pb(1, m) + _pb(2, a * 1000) + _pb(3, d * 1000)
                        + b"".join(_pb(4, s) for s in stats))
                    for m, a, d, stats in events)
    out = _pb(2, name) + _pb(3, _pb(2, line) + _pb(3, 0) + body)
    out += b"".join(_pb(4, _entry(k, _pb(1, k) + meta))
                    for k, meta in metas.items())
    return out + b"".join(_pb(5, _entry(k, _pb(1, k) + _pb(2, n)))
                          for k, n in stat_names.items())


def test_a_tpu_shaped_trace_gives_each_op_its_scope(tmp_path):
    # stat names: 7 tf_op, 8 an interned path, 9 source, 10 rounds; the
    # async start has no tf_op and takes its done's scope
    start = ("%async-collective-start = (f32[2], f32[8]) fusion(%bitcast.1),"
             " kind=kCustom, calls=%fused_computation.3")
    device = _plane("/device:TPU:0", "XLA Ops", [
        (1, 10, 30, []), (2, 40, 20, []), (3, 70, 5, []), (4, 76, 4, []),
        (5, 80, 6, [])], {
        1: _pb(2, "%fusion.1 = f32[8] fusion(f32[8] %p)") + _pb(4, "fusion.1")
        + _pb(5, _pb(1, 9) + _pb(5, "src/repro/core/dpsgd.py:92"))
        + _pb(5, _pb(1, 7) + _pb(7, 8)),
        2: _pb(2, "%all-gather.1 = f32[8] all-gather(f32[2] %x)")
        + _pb(5, _pb(1, 7) + _pb(5, "jit(f)/while/body/dpsgd.mix/all_gather:")),
        3: _pb(2, "%copy.1 = f32[8] copy(f32[8] %y)")
        + _pb(5, _pb(1, 9) + _pb(5, "src/repro/core/dpsgd.py:99")),
        4: _pb(2, start) + _pb(4, "async-collective-start"),
        5: _pb(2, "%async-collective-done = f32[8] fusion(%get-tuple-element"
                  ".2), kind=kCustom, calls=%fused_computation.4")
        + _pb(5, _pb(1, 7) + _pb(5, "jit(f)/while/body/dpsgd.mix/dot:"))},
        {7: "tf_op", 8: "jit(f)/dpsgd.grad/vmap(transpose(jvp()))/dot:",
         9: "source"})
    host = _plane("/host:CPU", "python", [
        (1, 0, 100, []), (2, 0, 100, []), (3, 5, 90, []),
        (4, 6, 88, [_pb(1, 10) + _pb(4, 2)])], {
        1: _pb(2, "bench.window"), 2: _pb(2, "bench.unit"),
        3: _pb(2, "bench.train"), 4: _pb(2, "repro.train")}, {10: "rounds"})
    trace = tmp_path / "t.xplane.pb"
    trace.write_bytes(_pb(1, device) + _pb(1, host))
    scopes = pt.op_scopes(str(trace), ["/device:TPU:0"])["/device:TPU:0"]
    assert scopes["fusion.1"] == scopes["%fusion.1 = f32[8] fusion(f32[8] %p)"] \
        == "dpsgd.grad"
    assert start not in scopes
    assert "%copy.1 = f32[8] copy(f32[8] %y)" not in scopes  # no tf_op stat
    red = pt.ProgramReduction.from_xplane(str(trace), [0])
    assert red.program_spans_named("repro.train")[0][2] == {"rounds": 2}
    assert red.busy_in_scope("dpsgd.grad", "train") == pytest.approx(30 * NS)
    assert red.busy_in_scope("dpsgd.mix", "train") == pytest.approx(30 * NS)
    got = red.readings()
    assert got["step_mix_ms"] == pytest.approx(1e3 * 30 * NS / 2)
    assert got["step_update_ms"] is None
    assert got["collective_in_mix_pct"] == pytest.approx(100.0)
    base = tr.Reduction.from_xplane(str(trace), [0])
    assert red.top_ops() == base.top_ops()
    gaps, base_gaps = red.idle_gaps(), base.idle_gaps()
    assert [g[1] for g in gaps] == [g[1] for g in base_gaps]
    assert [g[0] for g in base_gaps] == ["train"] * 4
    assert [g[0] for g in gaps] == ["train/repro.train", "train",
                                    "train/repro.train", "train/repro.train"]


def test_an_async_start_takes_the_scope_of_its_done():
    ops, sids = _device([
        (0, 4, "%async-collective-start = (f32[2]) fusion(%b), kind=kCustom",
         None),
        (4, 6, "%async-collective-done = f32[8] fusion(%g), kind=kCustom",
         "dpsgd.mix"),
        (10, 2, "%async-collective-start.1 = (f32[2]) fusion(%c)", "dpsgd.grad"),
        (12, 3, "%async-collective-done.1 = f32[8] fusion(%h)", "dpsgd.grad"),
        (20, 2, "%async-collective-start.2 = (f32[2]) fusion(%d)", None),
        (30, 1, "%copy-start = (f32[2]) copy-start(%e)", None)])
    got = pt.pair_async(ops, sids)
    assert [SCOPES[i] if i >= 0 else None for i in got] == [
        "dpsgd.mix", "dpsgd.mix", "dpsgd.grad", "dpsgd.grad", None, None]
    red = pt.ProgramReduction(
        {0: ops}, [("window", 0, 40 * NS), ("train", 0, 40 * NS)],
        scope_ids={0: sids}, scope_names=SCOPES)
    assert red.busy_in_scope("dpsgd.mix", "train") == pytest.approx(10 * NS)
