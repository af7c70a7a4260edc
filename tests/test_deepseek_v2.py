"""DeepSeek-V2 on the training path against its plain reference
(``bench/refs/deepseek_v2.py``), on seeded random weights at a smoke size:
MLA with its latent norm and YaRN, the expert layer (float32 router, top-k
without renormalizing, drop-free grouped dispatch over the held experts),
the share of each chip adding up to the uncut layer, the whole model's loss
and gradients, and one D-PSGD round through ``train_model_on_traces``."""
import dataclasses
import math
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import MoEConfig, reduce_for_smoke
from repro.models import mla, moe, transformer
from repro.models.layers import yarn

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "bench"))
from refs import deepseek_v2 as ref  # noqa: E402

EXPERTS, TOP_K, HELD = 8, 3, 4


def _cfg(held: int = 0, offset: int = 0, norm_topk: bool = False, layers: int = 3):
    base = reduce_for_smoke(get_config("deepseek-v2-lite-16b"))
    return dataclasses.replace(
        base, n_layers=layers, vocab_size=96,
        moe=MoEConfig(n_experts=EXPERTS, top_k=TOP_K, d_ff_expert=32,
                      n_shared=1, norm_topk=norm_topk, experts_held=held,
                      expert_offset=offset))


def _ref_cfg(cfg) -> dict:
    rs = cfg.rope_scaling
    return {"hidden_size": cfg.d_model, "num_attention_heads": cfg.n_heads,
            "kv_lora_rank": cfg.mla.kv_lora_rank,
            "qk_nope_head_dim": cfg.mla.qk_nope_dim,
            "qk_rope_head_dim": cfg.mla.qk_rope_dim,
            "v_head_dim": cfg.mla.v_head_dim,
            "num_experts_per_tok": cfg.moe.top_k,
            "norm_topk_prob": cfg.moe.norm_topk, "rms_norm_eps": 1e-6,
            "rope_theta": cfg.rope_theta, "num_hidden_layers": cfg.n_layers,
            "first_k_dense_replace": cfg.first_k_dense,
            "expert_offset": cfg.moe.expert_offset,
            "n_routed_experts": cfg.moe.held,
            "rope_scaling": (("beta_fast", rs.beta_fast), ("beta_slow", rs.beta_slow),
                             ("factor", rs.factor), ("mscale", rs.mscale),
                             ("mscale_all_dim", rs.mscale_all_dim),
                             ("original_max_position_embeddings",
                              rs.original_max_position), ("type", "yarn"))}


def _params(cfg, seed=0):
    """Seeded random weights with norm scales away from 1, so that a norm
    left out shows."""
    params = transformer.init_params(cfg, jax.random.key(seed))
    leaves, treedef = jax.tree_util.tree_flatten_with_path(params)
    out = []
    for k, (path, leaf) in enumerate(leaves):
        if jax.tree_util.keystr(path).endswith("['scale']"):
            leaf = 1.0 + 0.5 * jax.random.normal(jax.random.key(1000 + k), leaf.shape)
        out.append(leaf)
    return jax.tree.unflatten(treedef, out)


def _tokens(cfg, seed=1, b=2, s=24):
    return jax.random.randint(jax.random.key(seed), (b, s), 0, cfg.vocab_size,
                              jnp.int32)


def _hidden(cfg, seed=2, b=2, s=24):
    return jax.random.normal(jax.random.key(seed), (b, s, cfg.d_model), jnp.float32)


def test_yarn_matches_the_published_correction_range():
    """At DeepSeek-V2-Lite's 64 rope channels: frequencies 0-10 kept, 23 on
    interpolated by 40, a linear ramp between, and mscale^2 = 1.5896."""
    cfg = get_config("deepseek-v2-lite-16b")
    inv, factor = yarn(cfg.rope_scaling, 64, cfg.rope_theta)
    extra = 1e4 ** (-np.arange(32) * 2 / 64)
    np.testing.assert_allclose(inv[:11], extra[:11], rtol=1e-6)
    np.testing.assert_allclose(inv[23:], extra[23:] / 40, rtol=1e-6)
    mid = 1 - (np.arange(11, 23) - 10) / 13
    np.testing.assert_allclose(inv[11:23], extra[11:23] * (mid + (1 - mid) / 40),
                               rtol=1e-6)
    assert factor == pytest.approx((0.1 * 0.707 * math.log(40) + 1) ** 2)
    assert factor == pytest.approx(1.5896, abs=1e-4)
    r_inv, r_factor = ref.yarn_inv_freq(_ref_cfg(cfg))
    np.testing.assert_allclose(inv, r_inv, rtol=1e-6)
    assert factor == pytest.approx(r_factor)


@pytest.mark.parametrize("latent_scale", [1.0, 3.0])
def test_mla_forward_matches_reference(latent_scale):
    """MLA with its latent RMSNorm and YaRN against the reference's full
    attention matrix; scaling ``c_kv`` changes nothing, since the latent is
    normed before its up-projections."""
    cfg = _cfg()
    attn = _params(cfg)["prologue"][0]["attn"]
    attn = {**attn, "wkv_a": {"w": attn["wkv_a"]["w"].at[:, :cfg.mla.kv_lora_rank]
                              .multiply(latent_scale)}}
    h = _hidden(cfg)
    got, _ = jax.jit(lambda a, x: mla.mla_apply(
        a, x, cfg, m=cfg.mla, positions=jnp.arange(x.shape[1])))(attn, h)
    want = jax.jit(lambda a, x: ref._mla(a, x, _ref_cfg(cfg), ref.matmul_f32))(attn, h)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("norm_topk", [False, True])
@pytest.mark.parametrize("held, offset", [(0, 0), (HELD, 0), (HELD, HELD)])
def test_moe_layer_matches_reference(norm_topk, held, offset):
    """The program's share of the expert layer, with the gates kept or
    renormalized, against the reference's dense loop over held experts."""
    cfg = _cfg(held, offset, norm_topk)
    p = _params(cfg)["unit"][0]["moe"]
    p = jax.tree.map(lambda a: a[0], p)
    h = _hidden(cfg)
    got = moe.moe_apply(p, h, cfg, cfg.moe)
    want, _ = ref._moe(p, h, _ref_cfg(cfg), ref.matmul_f32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-5)


def test_moe_drops_no_token_when_all_pick_one_expert():
    """Every token's first choice is expert 0 (a router column far above
    the rest): all T pairs reach it, where a capacity would have dropped
    most."""
    cfg = _cfg(HELD, 0)
    p = jax.tree.map(lambda a: a[0], _params(cfg)["unit"][0]["moe"])
    h = _hidden(cfg) + 5.0
    w = p["router"]["w"].at[:, 0].set(10.0)
    p = {**p, "router": {"w": w}}
    _, experts = moe.moe_route(w, h.reshape(-1, cfg.d_model), cfg.moe)
    assert bool(jnp.all(experts[:, 0] == 0))
    got = moe.moe_apply(p, h, cfg, cfg.moe)
    want, _ = ref._moe(p, h, _ref_cfg(cfg), ref.matmul_f32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-5)


def test_gates_are_not_renormalized():
    cfg = _cfg()
    w = 0.05 * jax.random.normal(jax.random.key(3), (cfg.d_model, EXPERTS))
    x = jax.random.normal(jax.random.key(4), (16, cfg.d_model))
    gates, experts = moe.moe_route(w, x, cfg.moe)
    probs = jax.nn.softmax(x @ w, -1)
    np.testing.assert_allclose(np.asarray(gates),
                               np.asarray(jnp.take_along_axis(probs, experts, -1)),
                               rtol=1e-6)
    assert float(gates.sum(-1).max()) < 1.0


@pytest.mark.parametrize("shares", [2, 4, 8])
def test_disjoint_expert_shares_add_up_to_the_uncut_layer(shares):
    """Each of ``shares`` chips holds ``EXPERTS / shares`` experts and
    computes its routed part plus the shared experts; the routed parts,
    with the shared experts counted once, add up to the uncut reference."""
    uncut_cfg = _cfg()
    p = jax.tree.map(lambda a: a[0], _params(uncut_cfg)["unit"][0]["moe"])
    h = _hidden(uncut_cfg)
    want, _ = ref._moe(p, h, _ref_cfg(uncut_cfg), ref.matmul_f32)
    per = EXPERTS // shares
    shared = ref._swiglu(p["shared"], h.reshape(-1, h.shape[-1]), ref.matmul_f32)
    total = -(shares - 1) * shared.reshape(h.shape)
    for s in range(shares):
        cfg = _cfg(per, s * per)
        part = {**p, **{k: p[k][s * per:(s + 1) * per]
                        for k in ("ew_gate", "ew_up", "ew_down")}}
        total = total + moe.moe_apply(part, h, cfg, cfg.moe)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), rtol=5e-4, atol=5e-5)


@pytest.mark.parametrize("held, offset", [(0, 0), (HELD, HELD)])
def test_model_loss_and_gradients_match_reference(held, offset):
    cfg = _cfg(held, offset)
    params = _params(cfg)
    tokens = _tokens(cfg)
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: transformer.lm_loss(cfg, p, {"tokens": tokens})))(params)
    r_loss, r_grads = jax.jit(jax.value_and_grad(
        lambda p: ref.loss(p, tokens, _ref_cfg(cfg))))(params)
    assert float(loss) == pytest.approx(float(r_loss), rel=1e-5)
    for (path, g), r in zip(jax.tree_util.tree_flatten_with_path(grads)[0],
                            jax.tree.leaves(r_grads)):
        scale = float(jnp.abs(r).max()) + 1e-12
        err = float(jnp.abs(g - r).max()) / scale
        assert err < 2e-3, (jax.tree_util.keystr(path), err)


def test_untied_head_and_latent_norm_are_parameters():
    cfg = get_config("deepseek-v2-lite-16b")
    shapes = jax.eval_shape(lambda k: transformer.init_params(
        dataclasses.replace(cfg, n_layers=2), k), jax.random.key(0))
    assert shapes["lm_head"]["w"].shape == (2048, 102400)
    assert shapes["prologue"][0]["attn"]["kv_norm"]["scale"].shape == (512,)
    assert shapes["unit"][0]["moe"]["router"]["w"].shape == (1, 2048, 64)


@pytest.mark.parametrize("arch", ["deepseek-v2-lite-16b", "phi3.5-moe-42b-a6.6b"])
def test_expert_tensors_replicate_over_the_model_axis(arch):
    """A chip's expert share is ``MoEConfig.experts_held``, never a sharding:
    GSPMD cannot split the grouped matmul's custom call."""
    from jax.sharding import PartitionSpec
    from repro.train.shardings import param_specs

    cfg = reduce_for_smoke(get_config(arch))
    shapes = jax.eval_shape(lambda k: transformer.init_params(cfg, k),
                            jax.random.key(0))
    specs = jax.tree_util.tree_flatten_with_path(
        param_specs(shapes, tp=2),
        is_leaf=lambda s: isinstance(s, PartitionSpec))[0]
    experts = [s for p, s in specs if "'ew_" in jax.tree_util.keystr(p)]
    assert len(experts) >= 3
    assert all(all(a is None for a in s) for s in experts)
    assert any("model" in tuple(s) for _, s in specs)


def test_one_dpsgd_round_matches_reference():
    """Two nodes, two rounds of the static world through
    ``train_model_on_traces`` against the reference's Eq. 5 with the same
    weights, tokens and W."""
    from repro.sim.batch import train_model_on_traces, transformer_adapter
    from repro.sim.scenario import get_scenario
    from repro.sim.trace import precompute_traces

    cfg = _cfg(HELD, HELD)
    rounds, eta = 2, 0.05
    base = transformer_adapter(cfg, batch=1, seq_len=24)
    x0 = _params(cfg)
    tokens = np.asarray(jax.random.randint(jax.random.key(9), (rounds, 2, 1, 24),
                                           0, cfg.vocab_size, jnp.int32))
    adapter = dataclasses.replace(base, init_params=lambda _s: x0,
                                  batch_fn=lambda _c, _t: {"tokens": tokens},
                                  eval_fn=None)
    scen = get_scenario("static", n_nodes=2, model_bits=adapter.model_bits,
                        model_shapes=adapter.param_shapes,
                        eval_every_rounds=rounds)
    tb = precompute_traces([scen], rounds)
    _, out = train_model_on_traces(adapter, [scen], rounds, eta=eta,
                                   trace_batch=tb, unroll=1)
    w = np.asarray(tb.traces[0].w_eff, np.float32)
    assert not np.allclose(w[0], np.eye(2))
    losses, change = ref.dpsgd([x0, x0], tokens, w, eta, _ref_cfg(cfg))
    np.testing.assert_allclose(out["losses"][0], losses, rtol=1e-5)
    final = out["final_params"][0]
    got = np.array([[float(jnp.linalg.norm(a[i] - b)) for a, b in
                     zip(jax.tree.leaves(final), jax.tree.leaves(x0))]
                    for i in range(2)])
    np.testing.assert_allclose(got, change, rtol=1e-3, atol=1e-7)
