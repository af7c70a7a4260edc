"""The reduction from a profiler trace to device busy time, busy time inside
host spans, collectives and idle gaps, on traces built here and on one
recorded on the CPU."""
import numpy as np
import pytest

import benchlib  # noqa: F401  (puts bench/ on the path)
import trace_reduce as tr

NS = 1e-9


def _device(events):
    """events: (start_ns, dur_ns, hlo text)."""
    names = {}
    ids = [names.setdefault(n, len(names)) for _, _, n in events]
    return tr.DeviceOps.from_names([e[0] for e in events],
                                   [e[1] for e in events], ids, names)


@pytest.fixture
def two_chips():
    # chip 0: a fusion 0-40, an all-gather 30-60 (10 of it overlapped), a
    # while loop 0-100 that holds them, a fusion 80-90; idle 60-80, 90-100
    chip0 = _device([
        (0, 100, "%while.3 = (f32[2]) while(...)"),
        (0, 40, "%fusion.12 = f32[8]{0} fusion(f32[8] %p), kind=kLoop"),
        (30, 30, "%all-gather.2 = f32[32]{0} all-gather(f32[8] %x)"),
        (80, 10, "%fusion.7 = f32[8]{0} fusion(f32[8] %q)"),
    ])
    # chip 1: a convolution 10-50 and a fusion 95-100
    chip1 = _device([(10, 40, "%convolution.1 = f32[4] convolution(...)"),
                     (95, 5, "%fusion.3 = f32[4] fusion(...)")])
    spans = [("window", 0, 100 * NS), ("unit", 0, 100 * NS),
             ("plan", 55 * NS, 85 * NS), ("scan", 0, 50 * NS)]
    return tr.Reduction({0: chip0, 1: chip1}, spans)


def test_op_base_reads_tpu_hlo_text():
    assert tr.op_base("%all-gather-start.3 = (f32[2]) all-gather-start(x)") == "all-gather-start"
    assert tr.op_base("%fusion.3948 = u32[256]{0} fusion(u32[] %b)") == "fusion"
    assert tr.op_base("jit_run(9094603458158834231)") == "jit_run(9094603458158834231)"
    assert tr.op_base("%collective-permute-done.1 = f32[] x").startswith(tr.COLLECTIVES)
    assert not tr.op_base("%fusion.1 = f32[] x").startswith(tr.COLLECTIVES)


def test_busy_is_the_union_averaged_over_chips(two_chips):
    # chip 0 busy 0-100 (the while loop covers it all), chip 1 busy 45 ns
    assert two_chips.busy_s() == pytest.approx((100 + 45) / 2 * NS)
    assert two_chips.window_s() == pytest.approx(100 * NS)


def test_busy_inside_a_kind_of_span(two_chips):
    # scan 0-50: chip 0 busy 50, chip 1 busy 40
    assert two_chips.busy_in("scan") == pytest.approx(45 * NS)
    assert two_chips.busy_in("nothing") == 0.0


def test_collective_time_and_its_exposed_part(two_chips):
    # the all-gather lasts 30 ns on chip 0, 20 of them with no compute op;
    # chip 1 runs none
    assert two_chips.collective_s() == pytest.approx(30 / 2 * NS)
    # the scan span (0-50) holds 20 ns of it
    assert two_chips.collective_in("scan") == pytest.approx(20 / 2 * NS)
    assert two_chips.collective_exposed_s() == pytest.approx(20 / 2 * NS)


def test_op_table_leaves_out_containers(two_chips):
    table = dict(two_chips.top_ops())
    assert "while" not in table
    assert table["fusion"] == pytest.approx(55 / 2 * NS)
    assert table["convolution"] == pytest.approx(40 / 2 * NS)
    assert table["all-gather"] == pytest.approx(30 / 2 * NS)


def test_idle_gaps_are_labelled_with_the_innermost_span():
    chip = _device([(0, 60, "%fusion.1 = f32[] f"), (80, 10, "%fusion.2 = f32[] f")])
    red = tr.Reduction({0: chip}, [("window", 0, 100 * NS),
                                   ("plan", 55 * NS, 85 * NS)])
    gaps = red.idle_gaps()
    assert [g[0] for g in gaps] == ["plan", "window"]
    assert gaps[0][1] == pytest.approx(20 * NS)
    assert gaps[1][1] == pytest.approx(10 * NS)
    assert red.breakdown()["device_ops"] == [["fusion", pytest.approx(70 * NS)]]


def test_a_recorded_cpu_trace_gives_the_host_spans(tmp_path):
    import jax
    import jax.numpy as jnp

    x = jnp.ones((64, 64))
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            with jax.profiler.TraceAnnotation("bench.plan"):
                (x @ x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    red = tr.Reduction.from_dir(str(tmp_path), device_ids=[0])
    names = [n for n, _, _ in red.spans]
    assert names == ["window", "plan"]
    a, b = red.window()
    (pa, pb), = red.spans_named("plan")
    assert a <= pa <= pb <= b and red.window_s() > 0
    # the CPU has no TPU device plane: nothing is read as device time
    assert red.ops == {} and red.busy_s() == 0.0
    assert np.isfinite(red.window_s())

