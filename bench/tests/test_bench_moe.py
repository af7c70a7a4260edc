"""The DeepSeek-V2 cell on a CPU-sized root: it loads, its work counts
agree with the program's parameter tree, its trace readers read the
grouped matmul's kernels, and ``correct`` holds for the program as it is
and fails with the timed path broken underneath in each way the cell can
be."""
import json

import jax
import numpy as np
import pytest
from types import SimpleNamespace

from benchlib import BENCH, cpu_run, harness, small_root
from test_bench_faults import _lm_fault
import flops_moe
import trace_reduce

CELL = "dsv2lite-static-2node"
# 128 wide, three layers (one dense), 4 of 16 experts held from expert 4;
# 256 tokens a node, enough that bfloat16's route flips average out below
# the loss limit as at the cell's size (PERF.md section 6)
TRAFFIC = {"rounds": 2, "seq_len": 256}
CONFIG = {"hidden_size": 128, "num_attention_heads": 4, "num_key_value_heads": 4,
          "kv_lora_rank": 64, "qk_nope_head_dim": 32, "qk_rope_head_dim": 16,
          "v_head_dim": 32, "intermediate_size": 256, "moe_intermediate_size": 128,
          "vocab_slice": 1024, "router_width": 16, "n_routed_experts": 4,
          "expert_offset": 4, "num_hidden_layers": 3}


def _file():
    return json.loads((BENCH / "configs" / "deepseek-v2-lite-l5e8.json").read_text())


@pytest.fixture
def moe_root(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    jax.clear_caches()
    yield small_root(tmp_path / "root", CELL, TRAFFIC, CONFIG)
    jax.clear_caches()


def test_cell_loads_with_its_driver_and_readers():
    cell = harness.load_cell(CELL)
    assert cell.chips == 1 and cell.driver_path.name == "dpsgd_moe.py"
    assert cell.traffic["rate_metric"] == "train_node_rounds_per_s"
    assert {m["name"] for m in cell.per_layer} == {
        "moe_gmm_ms", "moe_gmm_roofline_pct", "train_mfu_pct.moe",
        "train_host_ms", "train_device_ms", "device_idle_pct.train",
        "peak_hbm_gib"}


def test_configuration_keeps_the_published_widths():
    """Every number of the published config is in the file under its own
    key; only the depth, the experts held and the vocabulary slice are cut,
    and each cut names the published value."""
    f = _file()
    published = {"hidden_size": 2048, "intermediate_size": 10944,
                 "moe_intermediate_size": 1408, "kv_lora_rank": 512,
                 "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
                 "v_head_dim": 128, "num_attention_heads": 16,
                 "num_experts_per_tok": 6, "n_shared_experts": 2,
                 "vocab_size": 102400, "router_width": 64,
                 "first_k_dense_replace": 1, "rms_norm_eps": 1e-6}
    assert {k: f[k] for k in published} == published
    assert f["reduced"] == {"num_hidden_layers": [27, 5],
                            "n_routed_experts": [64, 8],
                            "vocab_slice": [102400, 12800]}
    assert f["rope_scaling"]["factor"] == 40 and f["norm_topk_prob"] is False
    assert f["tie_word_embeddings"] is False


def test_parameter_count_matches_eval_shape():
    from repro.models.api import build

    driver = harness.load_module(BENCH / "drivers" / "dpsgd_moe.py")
    mcfg = driver.model_config(_file())
    assert mcfg.moe.held == 8 and mcfg.moe.n_experts == 64
    assert mcfg.vocab_size == 12800 and not mcfg.tie_embeddings
    shapes = jax.eval_shape(build(mcfg).init, jax.random.key(0))
    counted = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(shapes))
    assert counted == _file()["params_per_node"] == 535_060_992


def test_work_counts():
    f = _file()
    assert flops_moe.held_pairs_per_token(f) == 0.75
    # MLA 13.76 M a layer; the dense layer's 67.2 M; per MoE layer the
    # router, two shared experts and 0.75 expected held pairs; the head slice
    mla = 2048 * 16 * 192 + 2048 * 576 + 512 * 2048 * 2 + 2048 * 2048
    moe = 2048 * 64 + 3 * 2048 * 1408 * 2 + 0.75 * 3 * 2048 * 1408
    assert flops_moe.chip_matmul_params_per_token(f) == pytest.approx(
        5 * mla + 3 * 2048 * 10944 + 4 * moe + 2048 * 12800)
    flops, bytes_ = flops_moe.gmm_flops_bytes(f, 1024)
    assert flops == 4 * 9 * 2 * 768 * 2048 * 1408
    assert bytes_ == 4 * 9 * 2 * (768 * 2048 + 8 * 2048 * 1408 + 768 * 1408)


def _trace(kinds, events, spans):
    """A reduction of one chip whose operations are ``events`` of
    (kind, start, end), with host ``spans``."""
    ids = [kinds.index(k) for k, _, _ in events]
    ops = trace_reduce.DeviceOps([a for _, a, _ in events],
                                 [b for _, _, b in events], ids, kinds)
    return trace_reduce.Reduction({0: ops}, spans)


def test_gmm_readers_count_the_kernels_inside_train_spans():
    kinds = ["moe_gmm", "moe_gmm_dlhs", "moe_gmm_drhs", "fusion", "while"]
    events = [("moe_gmm", 1.0, 1.1), ("moe_gmm_dlhs", 1.2, 1.4),
              ("moe_gmm_drhs", 1.5, 1.6), ("fusion", 1.6, 1.9),
              ("while", 1.0, 1.9), ("moe_gmm", 5.0, 5.5)]
    spans = [("window", 0.0, 6.0), ("train", 0.5, 2.0), ("train", 2.0, 3.0)]
    ctx = SimpleNamespace(trace=_trace(kinds, events, spans),
                          info={"rounds_per_call": 2, "nodes": 2,
                                "tokens_per_node_round": 1024},
                          config=_file(), devices=[0],
                          peaks={"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9})
    ms = harness.load_module(BENCH / "metrics" / "moe_gmm_ms.py").read(ctx)
    assert ms == pytest.approx(1e3 * 0.4 / (2 * 2))
    pct = harness.load_module(BENCH / "metrics" / "moe_gmm_roofline_pct.py").read(ctx)
    flops, bytes_ = flops_moe.gmm_flops_bytes(_file(), 1024)
    least = 2 * max(flops / 197e12, bytes_ / 819e9)
    assert pct == pytest.approx(100 * least / (ms * 1e-3))
    ctx.trace = _trace(kinds[3:], [("fusion", 1.0, 1.5)], spans)
    assert harness.load_module(BENCH / "metrics" / "moe_gmm_ms.py").read(ctx) is None


def test_sound_run_is_correct_and_reads_its_routes(moe_root):
    res = cpu_run(moe_root, CELL)
    assert res["correct"], res["checks"]
    assert set(res["checks"]) == {"w_max_abs_err", "loss_rel_gap",
                                  "change_norm_gap", "route_agree"}
    agree = res["checks"]["route_agree"]
    assert 0.9 <= agree["value"] <= 1.0 and agree["limit"] == 0.0
    assert res["metrics"]["train_node_rounds_per_s"]["value"] > 0


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch", "no_exchange"])
def test_broken_step_is_caught(moe_root, monkeypatch, fault):
    _lm_fault(monkeypatch, fault)
    res = cpu_run(moe_root, CELL)
    assert not res["correct"], (fault, res["checks"])


def test_float8_control_fails(moe_root):
    import control

    line, = control.readings(CELL, [4_000_000_655], {4_000_000_655},
                             devices_for=lambda n: jax.devices()[:n],
                             root=moe_root, log=lambda *a, **k: None)
    driver = harness.load_module(BENCH / "drivers" / "dpsgd_lm.py")
    assert line["program"]["loss_rel_gap"] <= driver.LOSS_REL_LIMIT
    ctl = line["control"]
    assert (ctl["loss_rel_gap"] > driver.LOSS_REL_LIMIT
            or ctl["change_norm_gap"] > driver.CHANGE_GAP_LIMIT), ctl
    for f, nums in line["faults"].items():
        assert (nums["loss_rel_gap"] > driver.LOSS_REL_LIMIT
                or nums["change_norm_gap"] > driver.CHANGE_GAP_LIMIT), (f, nums)
