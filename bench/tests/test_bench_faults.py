"""``correct`` on whole runs driven on the CPU at a size a test run holds:
true for the program as it is, false with the timed path broken underneath
in each way the cell can be, and false for the control (the reference one
precision below the configuration's, in the program's place)."""
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from benchlib import BENCH, cpu_run, harness, small_root

CHAN = {"overrides": {"n_nodes": 16}, "rounds": 3, "placements": 2}
LM_TRAFFIC = {"rounds": 3, "seq_len": 128}
LM_CONFIG = {"hidden_size": 256, "intermediate_size": 768,
             "num_attention_heads": 4, "num_key_value_heads": 4,
             "vocab_size": 4096}


@pytest.fixture
def cache(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    jax.clear_caches()
    yield
    jax.clear_caches()


@pytest.fixture
def chan_root(tmp_path, cache):
    return small_root(tmp_path / "root", "chan-fading-n256", CHAN)


@pytest.fixture
def lm_root(tmp_path, cache):
    return small_root(tmp_path / "root", "lm-static-4chip", LM_TRAFFIC, LM_CONFIG)


def _patch_scan(monkeypatch, alter):
    """Wrap the jitted round program so that its output is altered where it
    is produced."""
    from repro.sim import jit_trace

    real = jit_trace._round_scan

    def broken(*key):
        fn = real(*key)

        def run(rates, sizes, recv, chan):
            return alter(list(fn(rates, sizes, recv, chan)), np.asarray(recv))
        return run
    monkeypatch.setattr(jit_trace, "_round_scan", broken)


def test_chan_sound_run_is_correct(chan_root):
    res = cpu_run(chan_root, "chan-fading-n256")
    assert res["correct"] and res["attempted"] >= 1, res["checks"]
    assert res["metrics"]["sim_rounds_per_s"]["value"] > 0


def test_chan_flipped_link_is_caught(chan_root, monkeypatch):
    def flip(out, recv):
        i, j = np.argwhere(recv)[0]
        out[2] = out[2].at[1, i, j].set(~out[2][1, i, j])
        return tuple(out)
    _patch_scan(monkeypatch, flip)
    res = cpu_run(chan_root, "chan-fading-n256")
    assert not res["correct"]
    assert res["checks"]["round_link_mismatch"]["value"] == 1


def test_chan_altered_airtime_is_caught(chan_root, monkeypatch):
    def stretch(out, recv):
        out[1] = out[1].at[1].multiply(1 + 1e-4)
        return tuple(out)
    _patch_scan(monkeypatch, stretch)
    res = cpu_run(chan_root, "chan-fading-n256")
    assert not res["correct"]
    assert not res["checks"]["t_comm_rel_err"]["ok"]


def test_chan_window_plans_each_placement_once(monkeypatch):
    """The window takes the pool's fleets one by one, none twice until the
    pool is spent; every seed draws from the same pool in its own order."""
    module = harness.load_module(BENCH / "drivers" / "chan_scan.py")
    monkeypatch.setattr(module.Driver, "_trace",
                        lambda self, s: SimpleNamespace(placement_seed=s))

    def window(seed, units):
        ctx = SimpleNamespace(traffic={**harness.load_cell("chan-fading-n256").traffic,
                                       **CHAN}, config={"message_bits": 698880},
                              seed=seed)
        d = module.Driver(ctx)
        for _ in range(units):
            d.unit()
        return [a.placement_seed for a in d.answers], d.pool

    seen, pool = window(4_000_000_777, CHAN["placements"] + 1)
    assert sorted(seen[:-1]) == sorted(pool) and seen[-1] in pool
    other, _ = window(4_000_000_778, CHAN["placements"])
    assert sorted(other) == sorted(pool)


def test_chan_control_fails(chan_root):
    import control

    line, = control.readings("chan-fading-n256", [4_000_000_321], {4_000_000_321},
                             devices_for=lambda n: jax.devices()[:n],
                             root=chan_root, log=lambda *a, **k: None)
    driver = harness.load_module(BENCH / "drivers" / "chan_scan.py")
    assert line["program"]["t_comm_rel_err"] <= driver.T_COMM_REL_LIMIT
    ctl = line["control"]
    assert (ctl["t_comm_rel_err"] > driver.T_COMM_REL_LIMIT
            or ctl["round_link_mismatch"] > driver.LINKS_LIMIT)


def test_lm_sound_run_is_correct(lm_root):
    res = cpu_run(lm_root, "lm-static-4chip")
    assert res["correct"], res["checks"]
    assert res["metrics"]["train_node_rounds_per_s"]["value"] > 0


def _lm_fault(monkeypatch, fault):
    from repro.core import dpsgd
    from repro.sim import batch

    if fault == "state_unchanged":
        def step(loss_fn, params, batches, w, live, config):
            return params, jax.vmap(loss_fn)(params, batches)
        monkeypatch.setattr(batch, "dpsgd_masked_step", step)
    elif fault == "half_batch":
        real = dpsgd._node_grads

        def half(loss_fn, params, batches):
            tokens = batches["tokens"]
            return real(loss_fn, params,
                        {"tokens": tokens[..., : tokens.shape[-1] // 2]})
        monkeypatch.setattr(dpsgd, "_node_grads", half)
    elif fault == "no_exchange":
        monkeypatch.setattr(dpsgd, "mix", lambda params, w: params)


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch", "no_exchange"])
def test_lm_broken_step_is_caught(lm_root, monkeypatch, fault):
    _lm_fault(monkeypatch, fault)
    res = cpu_run(lm_root, "lm-static-4chip")
    assert not res["correct"], (fault, res["checks"])


def test_lm_wrong_mixing_matrix_is_caught(lm_root, monkeypatch):
    """A W that the event loop gets wrong trains the program, not the
    reference, which builds its own."""
    from repro.sim.trace import WirelessSimulator

    real = WirelessSimulator.precompute

    def uniform(self, n_rounds):
        tr = real(self, n_rounds)
        tr.w_eff = np.full_like(tr.w_eff, 1.0 / tr.n_nodes)
        return tr
    monkeypatch.setattr(WirelessSimulator, "precompute", uniform)
    res = cpu_run(lm_root, "lm-static-4chip")
    assert not res["correct"]
    assert res["checks"]["w_max_abs_err"]["value"] == pytest.approx(0.25)


def test_lm_window_call_is_compared(lm_root, monkeypatch):
    """The window's own calls are compared, not the set-up's first: calls
    after the first that train differently are caught."""
    from repro.sim import batch

    real, calls = batch.train_model_on_traces, []

    def stale(*args, **kw):
        calls.append(1)
        if len(calls) > 1:
            kw["eta"] = 0.5 * kw["eta"]
        return real(*args, **kw)
    monkeypatch.setattr(batch, "train_model_on_traces", stale)
    res = cpu_run(lm_root, "lm-static-4chip")
    assert len(calls) > 1 and not res["correct"], res["checks"]


def test_lm_control_fails(lm_root):
    import control

    line, = control.readings("lm-static-4chip", [4_000_000_654], {4_000_000_654},
                             devices_for=lambda n: jax.devices()[:n],
                             root=lm_root, log=lambda *a, **k: None)
    driver = harness.load_module(BENCH / "drivers" / "dpsgd_lm.py")
    ctl = line["control"]
    assert (ctl["loss_rel_gap"] > driver.LOSS_REL_LIMIT
            or ctl["change_norm_gap"] > driver.CHANGE_GAP_LIMIT), ctl
