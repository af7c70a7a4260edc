"""Per-architecture smoke tests: reduced same-family config, one forward /
train-loss and one prefill+decode on CPU; asserts shapes and finiteness."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import ARCHS, cell_is_runnable, get_config, reduce_for_smoke
from repro.configs.base import SHAPES, ShapeConfig
from repro.models import build

SMOKE_SHAPE = ShapeConfig("smoke", 64, 2, "train")


@pytest.fixture(scope="module")
def smoke_apis():
    return {}


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_arch_smoke(arch, smoke_apis):
    cfg = reduce_for_smoke(get_config(arch))
    api = build(cfg)
    key = jax.random.key(0)
    params = api.init(key)
    n_params = sum(l.size for l in jax.tree.leaves(params))
    assert n_params > 1000

    batch = api.make_inputs(SMOKE_SHAPE, key, batch_override=2)
    loss = api.loss(params, batch)
    assert loss.shape == ()
    assert bool(jnp.isfinite(loss)), f"{arch}: non-finite loss"
    # loss of a random init on ~uniform tokens should be ~log(vocab)
    assert 2.0 < float(loss) < 12.0

    logits, cache = api.prefill(params, batch, max_len=96)
    assert logits.shape == (2, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits)))
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    start = batch["tokens"].shape[1]
    logits2, cache = api.decode_step(params, tok, cache, jnp.asarray(start))
    assert logits2.shape == (2, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits2))), f"{arch}: non-finite decode"
    smoke_apis[arch] = (cfg, api)


def test_exactly_ten_archs_registered():
    assert len(ARCHS) == 10


def test_full_configs_match_assignment():
    """Pin the assigned architecture hyperparameters (typo guard)."""
    expect = {
        "seamless-m4t-large-v2": (24, 1024, 16, 16, 8192, 256206),
        "gemma3-12b": (48, 3840, 16, 8, 15360, 262144),
        "nemotron-4-15b": (32, 6144, 48, 8, 24576, 256000),
        "qwen2.5-14b": (48, 5120, 40, 8, 13824, 152064),
        "stablelm-3b": (32, 2560, 32, 32, 6912, 50304),
        "recurrentgemma-2b": (26, 2560, 10, 1, 7680, 256000),
        "phi3.5-moe-42b-a6.6b": (32, 4096, 32, 8, 6400, 32064),
        "deepseek-v2-lite-16b": (27, 2048, 16, 16, 1408, 102400),
        "qwen2-vl-2b": (28, 1536, 12, 2, 8960, 151936),
        "rwkv6-7b": (32, 4096, 64, 64, 14336, 65536),
    }
    for name, (L, d, h, kv, ff, v) in expect.items():
        c = get_config(name)
        assert (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads, c.d_ff,
                c.vocab_size) == (L, d, h, kv, ff, v), name


def test_cell_skip_logic():
    # long_500k runs only for the sub-quadratic archs
    runnable = {a for a in ARCHS
                if cell_is_runnable(get_config(a), SHAPES["long_500k"])[0]}
    assert runnable == {"recurrentgemma-2b", "rwkv6-7b"}
    for a in ARCHS:  # every other shape runs everywhere
        for s in ("train_4k", "prefill_32k", "decode_32k"):
            assert cell_is_runnable(get_config(a), SHAPES[s])[0]


def test_moe_configs():
    ds = get_config("deepseek-v2-lite-16b")
    assert ds.moe.n_experts == 64 and ds.moe.top_k == 6 and ds.moe.n_shared == 2
    assert ds.first_k_dense == 1 and ds.mla.kv_lora_rank == 512
    # the published config.json: untied head, gates not renormalized,
    # YaRN rope scaling; every expert held unless a deployment cuts it
    assert not ds.tie_embeddings and not ds.moe.norm_topk
    assert ds.moe.held == 64 and ds.moe.expert_offset == 0
    rs = ds.rope_scaling
    assert (rs.factor, rs.original_max_position, rs.beta_fast, rs.beta_slow,
            rs.mscale, rs.mscale_all_dim) == (40, 4096, 32, 1, 0.707, 0.707)
    phi = get_config("phi3.5-moe-42b-a6.6b")
    assert phi.moe.n_experts == 16 and phi.moe.top_k == 2
    assert phi.moe.norm_topk and phi.rope_scaling is None


def test_pattern_structures():
    g = get_config("gemma3-12b")
    assert g.pattern.count("local") == 5 and g.pattern.count("global") == 1
    assert g.pattern_repeats == 8 and g.pattern_remainder == 0
    rg = get_config("recurrentgemma-2b")
    assert rg.pattern == ("rglru", "rglru", "local")
    assert rg.pattern_repeats == 8 and rg.pattern_remainder == 2
    assert not g.supports_long_context
    assert rg.supports_long_context


def test_paper_cnn_param_count():
    from repro.models import cnn
    params = cnn.cnn_init(jax.random.key(0))
    n = sum(l.size for l in jax.tree.leaves(params))
    assert n == cnn.PARAM_COUNT == 21840
    assert cnn.MODEL_BITS == 698880
    imgs = jnp.zeros((4, 1, 28, 28))
    logp = cnn.cnn_apply(params, imgs)
    assert logp.shape == (4, 10)
    assert bool(jnp.allclose(jnp.exp(logp).sum(-1), 1.0, atol=1e-5))
