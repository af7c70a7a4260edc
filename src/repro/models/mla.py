"""Multi-head Latent Attention (DeepSeek-V2) — compressed-KV attention.

KV path:  x -> c_kv (kv_lora_rank) + k_rope (shared across heads)
          c_kv <- RMSNorm(c_kv) (the published ``kv_a_layernorm``)
          k_i = [W_uk_i c_kv, k_rope],  v_i = W_uv_i c_kv
Q path (V2-Lite has no Q-LoRA): x -> q_i = [q_nope_i, q_rope_i]

The cache stores only (c_kv, k_rope) per token — (512+64) values instead of
2·H·D — which is the paper-relevant property for the decode_32k cell: the
memory roofline term of MLA decode is ~10x smaller than GQA at equal heads.

Decode uses the low-rank identity: score_i = q_nope_i^T W_uk_i c_kv
 = (W_uk_i^T q_nope_i)^T c_kv, so the per-step FLOPs stay O(H·(nope·r) + L·r)
without expanding the cache to full K/V.

With ``cfg.rope_scaling`` (YaRN) the rope channels take YaRN's frequencies
and the softmax scale ``(qk_nope + qk_rope)**-0.5`` is multiplied by
mscale^2 (``layers.yarn``). The rope channels are rotated in the
rotate-half layout; DeepSeek's published code pairs them interleaved,
which is the same function up to a fixed permutation of the rope columns
of ``wq`` and ``wkv_a``.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ..configs.base import MLAConfig, ModelConfig
from .attention import chunked_attention
from .layers import dense, dense_init, norm, norm_init, rope, yarn

__all__ = ["mla_init", "init_mla_cache", "mla_apply"]

_NEG = -1e30


def mla_init(key, cfg: ModelConfig, m: MLAConfig) -> dict:
    ks = jax.random.split(key, 6)
    h = cfg.n_heads
    return {
        "wq": dense_init(ks[0], cfg.d_model, h * (m.qk_nope_dim + m.qk_rope_dim),
                         dtype=cfg.param_dtype),
        "wkv_a": dense_init(ks[1], cfg.d_model, m.kv_lora_rank + m.qk_rope_dim,
                            dtype=cfg.param_dtype),
        "w_uk": dense_init(ks[2], m.kv_lora_rank, h * m.qk_nope_dim, dtype=cfg.param_dtype),
        "w_uv": dense_init(ks[3], m.kv_lora_rank, h * m.v_head_dim, dtype=cfg.param_dtype),
        "wo": dense_init(ks[4], h * m.v_head_dim, cfg.d_model, dtype=cfg.param_dtype),
        "kv_norm": norm_init(m.kv_lora_rank, "rmsnorm", cfg.param_dtype),
    }


def init_mla_cache(cfg: ModelConfig, m: MLAConfig, batch: int, max_len: int, dtype) -> dict:
    return {
        "c_kv": jnp.zeros((batch, max_len, m.kv_lora_rank), dtype),
        "k_rope": jnp.zeros((batch, max_len, m.qk_rope_dim), dtype),
    }


def _rope_and_scale(cfg: ModelConfig, m: MLAConfig):
    """The rope channels' inverse frequencies (None: ``rope_theta``'s) and
    the softmax scale."""
    scale = (m.qk_nope_dim + m.qk_rope_dim) ** -0.5
    if cfg.rope_scaling is None:
        return None, scale
    inv_freq, factor = yarn(cfg.rope_scaling, m.qk_rope_dim, cfg.rope_theta)
    return jnp.asarray(inv_freq), scale * factor


def mla_apply(p: dict, x: jax.Array, cfg: ModelConfig, *, m: MLAConfig,
              positions: jax.Array,
              cache: Optional[dict] = None,
              cache_index: Optional[jax.Array] = None,
              k_chunk: int = 1024) -> tuple[jax.Array, Optional[dict]]:
    with jax.named_scope("mla.attn"):
        return _mla(p, x, cfg, m, positions, cache, cache_index, k_chunk)


def _mla(p, x, cfg: ModelConfig, m: MLAConfig, positions, cache, cache_index,
         k_chunk):
    dt = jnp.dtype(cfg.dtype)
    b, s, _ = x.shape
    h = cfg.n_heads
    inv_freq, scale = _rope_and_scale(cfg, m)
    q = dense(p["wq"], x, dt).reshape(b, s, h, m.qk_nope_dim + m.qk_rope_dim)
    q_nope, q_rope = q[..., : m.qk_nope_dim], q[..., m.qk_nope_dim:]
    q_rope = rope(q_rope, positions, cfg.rope_theta, inv_freq=inv_freq)

    kv = dense(p["wkv_a"], x, dt)
    c_kv, k_rope = kv[..., : m.kv_lora_rank], kv[..., m.kv_lora_rank:]
    c_kv = norm(p["kv_norm"], c_kv, "rmsnorm")
    k_rope = rope(k_rope[:, :, None, :], positions, cfg.rope_theta,
                  inv_freq=inv_freq)[:, :, 0, :]

    if cache_index is None:
        # ----- train / prefill: expand to full heads, reuse chunked attention
        k_nope = (c_kv @ p["w_uk"]["w"].astype(dt)).reshape(b, s, h, m.qk_nope_dim)
        v = (c_kv @ p["w_uv"]["w"].astype(dt)).reshape(b, s, h, m.v_head_dim)
        k_full = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope[:, :, None, :], (b, s, h, m.qk_rope_dim))],
            axis=-1)
        q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
        # chunked_attention supports Dv != Dqk natively — no V padding
        # (padding V to 192 cost +50% AV flops; EXPERIMENTS.md §Perf cell C)
        out = chunked_attention(q_full, k_full, v, causal=True,
                                q_positions=positions, k_positions=positions,
                                k_chunk=k_chunk, scale=scale)
        new_cache = None
        if cache is not None:
            new_cache = {
                "c_kv": jax.lax.dynamic_update_slice_in_dim(
                    cache["c_kv"], c_kv.astype(cache["c_kv"].dtype), 0, axis=1),
                "k_rope": jax.lax.dynamic_update_slice_in_dim(
                    cache["k_rope"], k_rope.astype(cache["k_rope"].dtype), 0, axis=1),
            }
        y = dense(p["wo"], out.astype(dt).reshape(b, s, h * m.v_head_dim), dt)
        return y, new_cache

    # ----- decode: low-rank attention directly against the compressed cache
    ckv_c = jax.lax.dynamic_update_slice_in_dim(
        cache["c_kv"], c_kv.astype(cache["c_kv"].dtype), cache_index, axis=1)
    kr_c = jax.lax.dynamic_update_slice_in_dim(
        cache["k_rope"], k_rope.astype(cache["k_rope"].dtype), cache_index, axis=1)
    length = ckv_c.shape[1]

    # absorb W_uk into q: q_lat (b, h, r) = q_nope @ W_uk (per head)
    w_uk = p["w_uk"]["w"].astype(dt).reshape(m.kv_lora_rank, h, m.qk_nope_dim)
    q_lat = jnp.einsum("bhd,rhd->bhr", q_nope[:, 0].astype(jnp.float32),
                       w_uk.astype(jnp.float32))
    scores = jnp.einsum("bhr,blr->bhl", q_lat, ckv_c.astype(jnp.float32))
    scores += jnp.einsum("bhd,bld->bhl", q_rope[:, 0].astype(jnp.float32),
                         kr_c.astype(jnp.float32))
    scores *= scale
    valid = jnp.arange(length) <= cache_index
    scores = jnp.where(valid[None, None, :], scores, _NEG)
    pr = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bhl,blr->bhr", pr, ckv_c.astype(jnp.float32))   # latent context
    w_uv = p["w_uv"]["w"].astype(dt).reshape(m.kv_lora_rank, h, m.v_head_dim)
    out = jnp.einsum("bhr,rhd->bhd", ctx, w_uv.astype(jnp.float32))
    y = dense(p["wo"], out.reshape(b, 1, h * m.v_head_dim).astype(dt), dt)
    return y, {"c_kv": ckv_c, "k_rope": kr_c}
