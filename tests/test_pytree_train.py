"""Pytree-general training plane: per-leaf compression, shape contracts,
node-sharded parameter specs, and real-model train-on-trace parity.

The multi-device sharded smoke runs in a subprocess (same policy as
tests/test_dist.py: the main pytest process must keep seeing ONE device).
"""
import os
import json
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.checkpoint.ckpt import compact_nodes, expand_nodes
from repro.core import dpsgd
from repro.core.compression import (_BLOCK, QuantConfig, payload_bits,
                                    payload_bits_tree)
from repro.core.dpsgd import (DPSGDConfig, dpsgd_masked_compressed_step,
                              embed_w, node_axis_size, replicate,
                              zero_residuals)
from repro.core.topology import paper_w, ring_adjacency

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


def _tree(n, sizes, seed=0):
    """A masked-layout pytree: every leaf (n, *shape), deterministic fill."""
    rng = np.random.default_rng(seed)
    return {f"leaf{i}": jnp.asarray(rng.standard_normal((n, *s)),
                                    jnp.float32)
            for i, s in enumerate(sizes)}


def _mix_both(tree, w, live, mode, granularity):
    quant = QuantConfig(mode=mode, granularity=granularity)
    return dpsgd._mix_compressed(tree, zero_residuals(tree),
                                 jnp.asarray(w), jnp.asarray(live), quant)


# ---------------------------------------------------------------------------
# per-leaf vs concat-flat mixing
# ---------------------------------------------------------------------------

def test_leaf_vs_message_bf16_bit_identical_on_ragged_leaves():
    """bf16 rounding is elementwise, so the wire format cannot matter —
    even for leaves whose flat sizes are nothing like the int8 blocks."""
    n = 6
    tree = _tree(n, [(3,), (5, 7), (2, 2, 2)])
    w = jnp.asarray(paper_w(ring_adjacency(n)))
    live = jnp.ones(n, bool)
    got_l, res_l = _mix_both(tree, w, live, "bf16", "leaf")
    got_m, res_m = _mix_both(tree, w, live, "bf16", "message")
    for k in tree:
        np.testing.assert_array_equal(np.asarray(got_l[k]),
                                      np.asarray(got_m[k]))
        np.testing.assert_array_equal(np.asarray(res_l[k]),
                                      np.asarray(res_m[k]))


def test_leaf_vs_message_int8_matches_on_block_aligned_leaves():
    """When every leaf is a whole number of quantization blocks, the leaf
    and message block grids coincide, so int8 agrees across formats."""
    n = 4
    tree = _tree(n, [(_BLOCK,), (2, _BLOCK)])
    w = jnp.asarray(paper_w(ring_adjacency(n)))
    live = jnp.ones(n, bool)
    got_l, _ = _mix_both(tree, w, live, "int8", "leaf")
    got_m, _ = _mix_both(tree, w, live, "int8", "message")
    for k in tree:
        np.testing.assert_allclose(np.asarray(got_l[k]),
                                   np.asarray(got_m[k]), atol=1e-6)


def test_mode_none_is_exact_mix_any_granularity():
    n = 5
    tree = _tree(n, [(4,), (3, 3)])
    w = jnp.asarray(paper_w(ring_adjacency(n)))
    live = jnp.ones(n, bool)
    want = dpsgd.mix(tree, w)
    for gran in ("message", "leaf"):
        got, res = _mix_both(tree, w, live, "none", gran)
        for k in tree:
            np.testing.assert_array_equal(np.asarray(got[k]),
                                          np.asarray(want[k]))
            assert not np.asarray(res[k]).any()


# ---------------------------------------------------------------------------
# error-feedback residuals as a pytree under churn
# ---------------------------------------------------------------------------

def test_leaf_residuals_zeroed_for_dead_nodes_and_shaped_like_params():
    n = 6
    tree = _tree(n, [(7,), (3, 5)])          # ragged: leaf-specific blocks
    live = np.ones(n, bool)
    live[[1, 4]] = False
    ids = np.flatnonzero(live)
    w = jnp.asarray(embed_w(paper_w(ring_adjacency(ids.size)), ids, n))
    live_j = jnp.asarray(live)
    for gran in ("message", "leaf"):
        quant = QuantConfig(mode="int8", granularity=gran)
        mixed, res = dpsgd._mix_compressed(tree, zero_residuals(tree), w,
                                           live_j, quant)
        for k in tree:
            assert res[k].shape == tree[k].shape
            assert res[k].dtype == jnp.float32
            # dead nodes carry no stale quantization error...
            assert not np.asarray(res[k])[~live].any()
            # ...and their parameters come back verbatim (identity row)
            np.testing.assert_array_equal(np.asarray(mixed[k])[~live],
                                          np.asarray(tree[k])[~live])
        # live rows accumulated real error (int8 is lossy)
        assert any(np.asarray(res[k])[live].any() for k in tree)


def test_leaf_ef_converges_to_message_mean_under_churn():
    """Multi-round EF roundtrip: the per-leaf format preserves the masked
    live-mean (mixing is doubly-stochastic over live rows) just like the
    message format, round after round, while nodes churn."""
    n = 6
    tree = _tree(n, [(9,), (2, 3)])
    live0 = np.array([True, True, True, True, False, True])
    live1 = np.array([True, False, True, True, False, True])
    results = {}
    for gran in ("message", "leaf"):
        quant = QuantConfig(mode="int8", granularity=gran)
        params, res = tree, zero_residuals(tree)
        for live in (live0, live1):
            ids = np.flatnonzero(live)
            w = jnp.asarray(embed_w(paper_w(np.ones((ids.size, ids.size))),
                                    ids, n))
            params, res = dpsgd._mix_compressed(params, res, w,
                                                jnp.asarray(live), quant)
        results[gran] = params
    for k in tree:
        a = np.asarray(results["leaf"][k])
        b = np.asarray(results["message"][k])
        # both formats track the same mean trajectory; quantization noise
        # differs only through the block partitioning
        np.testing.assert_allclose(a, b, atol=5e-2)
        # round 0 averages the live0 cohort; round 1 re-averages a subset of
        # rows that already hold that mean, so it is a fixed point
        exact = np.asarray(tree[k])[live0].mean(axis=0)
        np.testing.assert_allclose(a[live1], np.broadcast_to(
            exact, a[live1].shape), atol=5e-2)


# ---------------------------------------------------------------------------
# shape contracts fail loudly
# ---------------------------------------------------------------------------

def test_node_axis_size_rejects_ragged_node_axes():
    good = {"a": jnp.zeros((4, 3)), "b": jnp.zeros((4, 2, 2))}
    assert node_axis_size(good) == 4
    bad = {"a": jnp.zeros((4, 3)), "b": jnp.zeros((5, 2))}
    with pytest.raises(ValueError, match="node axis"):
        node_axis_size(bad)
    with pytest.raises(ValueError, match="scalar"):
        node_axis_size({"a": jnp.float32(0.0)})
    assert node_axis_size({"a": jnp.float32(0.0)}, allow_scalar=True) == 0


def test_mix_compressed_rejects_mismatched_w_and_live():
    tree = _tree(4, [(3,)])
    quant = QuantConfig(mode="bf16")
    w5 = jnp.asarray(paper_w(ring_adjacency(5)))
    with pytest.raises(ValueError, match="disagree with the node axis"):
        dpsgd._mix_compressed(tree, zero_residuals(tree), w5,
                              jnp.ones(4, bool), quant)
    w4 = jnp.asarray(paper_w(ring_adjacency(4)))
    with pytest.raises(ValueError, match="disagree with the node axis"):
        dpsgd._mix_compressed(tree, zero_residuals(tree), w4,
                              jnp.ones(5, bool), quant)


def test_ckpt_compact_expand_pytree_general_and_validating():
    params = {"emb": jnp.arange(12.0).reshape(4, 3),
              "head": {"w": jnp.arange(16.0).reshape(4, 2, 2)}}
    live = np.array([True, False, True, True])
    compact = compact_nodes(params, live)
    assert compact["emb"].shape == (3, 3)
    assert compact["head"]["w"].shape == (3, 2, 2)
    back = expand_nodes(compact, np.flatnonzero(live), 4)
    np.testing.assert_array_equal(np.asarray(back["emb"])[live],
                                  np.asarray(params["emb"])[live])
    # dead rows get the survivor-mean warm start (reshape_nodes contract)
    np.testing.assert_allclose(
        np.asarray(back["emb"])[~live],
        np.asarray(compact["emb"]).mean(axis=0, keepdims=True), rtol=1e-6)
    with pytest.raises(ValueError):
        compact_nodes(params, np.ones(5, bool))          # width mismatch
    with pytest.raises(ValueError):
        expand_nodes(compact, np.array([0, 2, 9]), 4)    # id out of range


def test_driver_batches_rejects_wrong_shard_width():
    from repro.sim.batch import _driver_batches
    from repro.sim.scenario import get_scenario
    from repro.sim.trace import precompute_trace
    cfg = get_scenario("static")
    tr = precompute_trace(cfg, 2)
    bad_x = np.zeros((cfg.n_nodes + 1, 4, 5, 5, 1), np.float32)
    bad_y = np.zeros((cfg.n_nodes + 1, 4), np.int32)
    with pytest.raises(ValueError, match="data shards cover"):
        _driver_batches(cfg, tr, bad_x, bad_y, batch=2)


def test_model_batch_tokens_matches_reference_bit_for_bit():
    from repro.sim.trace import model_batch_tokens, model_batch_tokens_reference
    for seed, round_, n_live, batch, seq_len in [
            (0, 0, 3, 2, 8), (7, 5, 1, 4, 12), (3, 11, 6, 2, 17)]:
        fast = model_batch_tokens(seed, round_, n_live, batch, seq_len, 256)
        ref = model_batch_tokens_reference(
            seed, round_, n_live, batch, seq_len, 256)
        assert fast.dtype == np.int32 and fast.shape == (n_live, batch, seq_len)
        np.testing.assert_array_equal(fast, ref)


# ---------------------------------------------------------------------------
# wire accounting for pytree models
# ---------------------------------------------------------------------------

def test_payload_bits_tree_message_equals_flat_total():
    shapes = ((3, 5), (100,), (2, 2, 2))
    total = sum(int(np.prod(s)) for s in shapes)
    for mode in ("none", "bf16", "int8"):
        cfg = QuantConfig(mode=mode)  # granularity="message"
        assert payload_bits_tree(shapes, cfg) == payload_bits(total, cfg)


def test_payload_bits_tree_leaf_charges_per_leaf_tail_blocks():
    shapes = ((1,), (1,))
    cfg = QuantConfig(mode="int8", granularity="leaf")
    # two one-element leaves = two padded blocks on the wire, not one
    assert payload_bits_tree(shapes, cfg) == 2 * payload_bits(1, cfg)
    assert payload_bits_tree(shapes, cfg) > payload_bits(2, cfg)
    # bf16/none are elementwise: granularity cannot change the bill
    for mode in ("none", "bf16"):
        leaf = QuantConfig(mode=mode, granularity="leaf")
        assert payload_bits_tree(shapes, leaf) == payload_bits(2, leaf)


def test_quantconfig_and_scenario_validate_granularity():
    from repro.sim.scenario import get_scenario
    with pytest.raises(ValueError, match="granularity"):
        QuantConfig(mode="int8", granularity="tensor")
    with pytest.raises(ValueError, match="model_shapes"):
        get_scenario("static", payload=QuantConfig(mode="int8",
                                                   granularity="leaf"))
    with pytest.raises(ValueError, match="model_shapes sums to"):
        get_scenario("static", model_bits=32.0, model_shapes=((2, 2),))
    cfg = get_scenario("static", model_bits=32.0 * 4,
                       model_shapes=((2, 2),),
                       payload=QuantConfig(mode="int8", granularity="leaf"))
    assert cfg.wire_bits() == payload_bits_tree(((2, 2),), cfg.payload)


# ---------------------------------------------------------------------------
# node-sharded parameter specs (AbstractMesh: no devices touched)
# ---------------------------------------------------------------------------

def test_node_param_specs_shards_node_axis_over_fleet():
    from jax.sharding import AbstractMesh, PartitionSpec as P
    from repro.train.shardings import node_param_specs
    mesh = AbstractMesh((2, 2), ("fleet", "model"))
    params = {"tok_emb": jnp.zeros((8, 16, 4)),      # divisible: shards
              "odd": jnp.zeros((7, 4))}              # 7 % 2: replicated
    specs = node_param_specs(params, mesh)
    assert specs["tok_emb"][0] == "fleet"
    assert specs["odd"][0] is None
    with pytest.raises(ValueError, match="scalar"):
        node_param_specs({"s": jnp.float32(0.0)}, mesh)
    # no-fleet mesh (model only): node axis always replicated
    solo = AbstractMesh((2,), ("model",))
    specs = node_param_specs(params, solo)
    assert specs["tok_emb"][0] is None


# ---------------------------------------------------------------------------
# real-model train-on-trace parity
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_transformer():
    from repro.sim.batch import transformer_adapter
    return transformer_adapter("stablelm-3b", batch=2, seq_len=8)


def test_transformer_scan_matches_reference(tiny_transformer):
    """Single-device parity: the jitted scan over a static trace must match
    the per-round reference loop to 1e-5 (the ISSUE's parity contract)."""
    from repro.sim.batch import (train_model_on_traces,
                                 train_on_trace_reference)
    from repro.sim.scenario import get_scenario
    from repro.sim.trace import precompute_traces
    adapter = tiny_transformer
    rounds = 3
    cfg = get_scenario("static", model_bits=adapter.model_bits,
                       model_shapes=adapter.param_shapes,
                       eval_every_rounds=rounds)
    tb = precompute_traces([cfg], rounds)
    tr = tb.traces[0]
    params0 = replicate(adapter.init_params(cfg.seed), cfg.n_nodes)
    ref_final, ref_losses = train_on_trace_reference(
        adapter.loss_fn, params0, tr.w_eff, tr.live,
        adapter.batch_fn(cfg, tr), DPSGDConfig(eta=0.05),
        payload=cfg.payload, active_seq=tr.active)
    _, out = train_model_on_traces(adapter, [cfg], rounds, eta=0.05,
                                   trace_batch=tb)
    ref_mean = np.where(tr.live, ref_losses, 0.0).sum(-1) / tr.live.sum(-1)
    np.testing.assert_allclose(out["losses"][0], ref_mean, atol=1e-5)
    final = out["final_params"][0]
    want = compact_nodes(ref_final, tr.live[-1])
    for a, b in zip(jax.tree.leaves(final), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=1e-5)
    assert np.isfinite(out["losses"]).all()


def test_transformer_leaf_compressed_trains_finite(tiny_transformer):
    """Per-leaf int8 over a fading trace: the sharding-safe wire format
    trains end to end with finite losses and exact leaf accounting."""
    from repro.sim.batch import train_model_on_traces
    from repro.sim.scenario import get_scenario
    adapter = tiny_transformer
    cfg = get_scenario("fading", model_bits=adapter.model_bits,
                       model_shapes=adapter.param_shapes,
                       payload=QuantConfig(mode="int8", granularity="leaf"),
                       eval_every_rounds=3)
    assert cfg.wire_bits() == payload_bits_tree(adapter.param_shapes,
                                                cfg.payload)
    _, out = train_model_on_traces(adapter, [cfg], 3, eta=0.05)
    assert np.isfinite(out["losses"]).all()


def test_cnn_path_matches_reference_loop_to_ulp():
    """The CNN rides the generic pytree plane now; its losses must match
    the per-round reference of the same update sequence to float32
    round-off. Not bit for bit: the scan and the per-round step are
    separate XLA programs, and fusion order moves the last ulp (1.2e-7 at
    loss ~2.4 under jax 0.9, and differently again on the TPU)."""
    from repro.data import SyntheticFashion, node_splits
    from repro.models import cnn
    from repro.sim.batch import (_cnn_loss, _driver_batches,
                                 train_cnn_on_traces,
                                 train_on_trace_reference)
    from repro.sim.scenario import get_scenario
    from repro.sim.trace import precompute_traces
    batch, n_train = 25, 300
    cfg = get_scenario("static", eval_every_rounds=2)
    ds = SyntheticFashion(n_train=n_train, n_test=60, seed=0)
    shards = node_splits(ds.train_x, ds.train_y, cfg.n_nodes, seed=0)
    shard_x = np.stack([x for x, _ in shards])
    shard_y = np.stack([y for _, y in shards])
    rounds = max(shard_x.shape[1] // batch, 1)  # one epoch, like the driver
    tb = precompute_traces([cfg], rounds)
    tr = tb.traces[0]
    imgs, labs = _driver_batches(cfg, tr, shard_x, shard_y, batch)
    params0 = replicate(cnn.cnn_init(jax.random.key(cfg.seed)), cfg.n_nodes)
    ref_final, ref_losses = train_on_trace_reference(
        _cnn_loss, params0, tr.w_eff, tr.live,
        {"images": imgs, "labels": labs},
        DPSGDConfig(eta=0.05), payload=cfg.payload, active_seq=tr.active)
    _, out = train_cnn_on_traces([cfg], epochs=1, batch=batch,
                                 n_train=n_train, n_test=60, trace_batch=tb)
    ref_mean = np.where(tr.live, ref_losses, 0.0).sum(-1) / tr.live.sum(-1)
    np.testing.assert_allclose(np.asarray(out["losses"][0]),
                               ref_mean.astype(out["losses"].dtype),
                               rtol=1e-6, atol=0)


def test_screened_greedy_prefix_identical_to_unscreened():
    """The screened solve_greedy (mid-n cliff fix) must make exactly the
    unscreened picks — checked on a truncated run so the exact branch stays
    affordable at a screened-range n."""
    from repro.core import rate_opt
    from repro.core.channel import (ChannelParams, capacity_matrix,
                                    random_placement)
    n = rate_opt.GREEDY_SCREEN_MIN_N + 8
    cap = capacity_matrix(random_placement(n, seed=5), ChannelParams())
    a = rate_opt.solve_greedy(cap, 4e6, 0.5, max_iters=12)
    b = rate_opt.solve_greedy(cap, 4e6, 0.5, max_iters=12, screen=False)
    assert a.t_com_s == b.t_com_s and a.lam == b.lam
    np.testing.assert_array_equal(a.rates_bps, b.rates_bps)


def test_sharded_transformer_smoke_subprocess():
    """The acceptance path end to end: 8 host devices, fleet x model mesh,
    node-params spanning >= 2 devices, parity <= 1e-5 vs the per-round
    reference — one entry point shared with CI and the train bench."""
    out = _run("""
        import json
        from repro.sim.real_model_smoke import run
        report = run(rounds=2, fleet=2, model=2, batch=2, seq_len=8)
        print(json.dumps(report))
    """)
    report = json.loads(out.strip().splitlines()[-1])
    assert report["ok"], report
    assert report["mix"] == "exchange"
    assert report["devices_spanned"] >= 2
    assert report["parity"]["sharded_vs_reference_params"] <= 1e-5
    assert report["parity"]["driver_vs_reference_params"] <= 1e-5
