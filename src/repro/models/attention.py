"""Attention token mixers: GQA global/local (sliding window) + cross-attn.

Train/prefill uses **chunked online-softmax attention** (a flash-style
formulation in pure JAX): a ``lax.scan`` over KV blocks carries the running
(max, denominator, accumulator), so the S x T score matrix is never
materialized — memory stays O(S x block). The Pallas kernel
(``kernels/flash_attention.py``) is the TPU-target version of the same
computation with block skipping; this module is the lowering used on CPU and
in the dry-run (see DESIGN.md §5).

Local (sliding-window) attention uses exact two-block banding: with block
size c = window, query block i attends to key blocks {i-1, i} only — O(S*2w)
FLOPs instead of O(S^2).

Decode: single-token attention against a cache. Global layers keep a full
(B, L, Hkv, D) cache; local layers keep a ring buffer of ``window`` slots with
explicit position tags; cross-attention caches encoder K/V once at prefill.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from .layers import dense, dense_init, rope

__all__ = ["attn_init", "init_attn_cache", "attn_apply", "chunked_attention",
           "local_block_attention"]

_NEG = -1e30


def attn_init(key, cfg: ModelConfig, cross: bool = False) -> dict:
    ks = jax.random.split(key, 4)
    kv_src = cfg.d_model
    return {
        "wq": dense_init(ks[0], cfg.d_model, cfg.q_dim, bias=cfg.qkv_bias, dtype=cfg.param_dtype),
        "wk": dense_init(ks[1], kv_src, cfg.kv_dim, bias=cfg.qkv_bias, dtype=cfg.param_dtype),
        "wv": dense_init(ks[2], kv_src, cfg.kv_dim, bias=cfg.qkv_bias, dtype=cfg.param_dtype),
        "wo": dense_init(ks[3], cfg.q_dim, cfg.d_model, dtype=cfg.param_dtype),
    }


def init_attn_cache(cfg: ModelConfig, kind: str, batch: int, max_len: int,
                    dtype) -> dict:
    """Cache pytree for one attention layer. ``kind``: global|local|cross."""
    length = min(cfg.window, max_len) if kind == "local" and cfg.window else max_len
    cache = {
        "k": jnp.zeros((batch, length, cfg.n_kv_heads, cfg.head_dim), dtype),
        "v": jnp.zeros((batch, length, cfg.n_kv_heads, cfg.head_dim), dtype),
    }
    if kind == "local":
        cache["pos"] = jnp.full((length,), -1, jnp.int32)
    return cache


# ---------------------------------------------------------------------------
# Chunked global attention (train / prefill)
# ---------------------------------------------------------------------------

def chunked_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                      causal: bool = True,
                      q_positions: Optional[jax.Array] = None,
                      k_positions: Optional[jax.Array] = None,
                      k_chunk: int = 1024,
                      scale: Optional[float] = None) -> jax.Array:
    """(B,S,Hq,Dqk) x (B,T,Hkv,Dqk), (B,T,Hkv,Dv) -> (B,S,Hq,Dv); online
    softmax over KV blocks. Dv may differ from Dqk (MLA). ``scale``
    multiplies the scores; None is ``Dqk**-0.5``."""
    b, s, hq, d = q.shape
    t, hkv = k.shape[1], k.shape[2]
    dv = v.shape[-1]
    g = hq // hkv
    qg = q.reshape(b, s, hkv, g, d).astype(jnp.float32) * (
        d**-0.5 if scale is None else scale)
    if q_positions is None:
        q_positions = jnp.arange(s)
    if k_positions is None:
        k_positions = jnp.arange(t)

    k_chunk = min(k_chunk, t)
    pad = (-t) % k_chunk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k_positions = jnp.pad(k_positions, (0, pad), constant_values=-1)
    nblk = k.shape[1] // k_chunk
    kb = jnp.moveaxis(k.reshape(b, nblk, k_chunk, hkv, d), 1, 0)
    vb = jnp.moveaxis(v.reshape(b, nblk, k_chunk, hkv, dv), 1, 0)
    pb = k_positions.reshape(nblk, k_chunk)

    acc0 = jnp.zeros((b, s, hkv, g, dv), jnp.float32)
    m0 = jnp.full((b, s, hkv, g), _NEG, jnp.float32)
    l0 = jnp.zeros((b, s, hkv, g), jnp.float32)

    def body(carry, blk):
        acc, m, l = carry
        kblk, vblk, pos = blk
        scores = jnp.einsum("bshgd,bchd->bshgc", qg, kblk.astype(jnp.float32))
        valid = pos[None, None, :] >= 0
        if causal:
            valid = valid & (pos[None, None, :] <= q_positions[None, :, None])
        scores = jnp.where(valid[:, :, None, None, :], scores, _NEG)
        m_new = jnp.maximum(m, scores.max(axis=-1))
        p = jnp.exp(scores - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bshgc,bchd->bshgd", p, vblk.astype(jnp.float32))
        return (acc_new, m_new, l_new), None

    (acc, _, l), _ = jax.lax.scan(body, (acc0, m0, l0), (kb, vb, pb))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(b, s, hq, dv)


def local_block_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                          window: int) -> jax.Array:
    """Exact causal sliding-window attention via two-block banding.

    Block size = window; query block i sees key blocks {i-1, i} with the band
    mask ``0 <= qpos - kpos < window``. Inputs (B,S,H*,D) with S % window == 0
    handled by padding."""
    b, s, hq, d = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    c = min(window, s)
    pad = (-s) % c
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    sp = q.shape[1]
    n = sp // c
    qb = q.reshape(b, n, c, hkv, g, d).astype(jnp.float32) * d**-0.5
    kb = k.reshape(b, n, c, hkv, d)
    vb = v.reshape(b, n, c, hkv, d)
    # previous block (block -1 is zeros, masked out via kpos < 0)
    kprev = jnp.concatenate([jnp.zeros_like(kb[:, :1]), kb[:, :-1]], axis=1)
    vprev = jnp.concatenate([jnp.zeros_like(vb[:, :1]), vb[:, :-1]], axis=1)
    k2 = jnp.concatenate([kprev, kb], axis=2)  # (b, n, 2c, hkv, d)
    v2 = jnp.concatenate([vprev, vb], axis=2)
    scores = jnp.einsum("bnqhgd,bnkhd->bnqhgk", qb, k2.astype(jnp.float32))
    tq = jnp.arange(c)[:, None]          # in-block query offset
    tk = jnp.arange(2 * c)[None, :] - c  # key offset relative to block start
    delta = tq - tk                      # qpos - kpos (block-invariant)
    band = (delta >= 0) & (delta < window)
    kpos_ok = (jnp.arange(2 * c)[None, :] - c + jnp.arange(n)[:, None] * c) >= 0
    mask = band[None, :, :] & kpos_ok[:, None, :]         # (n, c, 2c)
    scores = jnp.where(mask[None, :, :, None, None, :], scores, _NEG)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bnqhgk,bnkhd->bnqhgd", p, v2.astype(jnp.float32))
    out = out.reshape(b, sp, hq, d)[:, :s]
    return out


# ---------------------------------------------------------------------------
# Full layer application
# ---------------------------------------------------------------------------

def attn_apply(p: dict, x: jax.Array, cfg: ModelConfig, *, kind: str,
               positions: jax.Array,
               cache: Optional[dict] = None,
               cache_index: Optional[jax.Array] = None,
               kv_src: Optional[jax.Array] = None,
               causal_override: Optional[bool] = None,
               k_chunk: int = 1024) -> tuple[jax.Array, Optional[dict]]:
    """One attention mixer. Modes:

    * train/prefill: ``cache is None`` or prefill fills the cache; x is (B,S,D)
    * decode: ``cache_index`` given, x is (B,1,D)
    * cross: ``kind == 'cross'`` with ``kv_src`` (B,T,D) encoder output (or
      cached K/V when decoding)
    """
    dt = jnp.dtype(cfg.dtype)
    b, s, _ = x.shape
    q = dense(p["wq"], x, dt).reshape(b, s, cfg.n_heads, cfg.head_dim)

    if kind == "cross":
        if kv_src is not None:
            t = kv_src.shape[1]
            k = dense(p["wk"], kv_src, dt).reshape(b, t, cfg.n_kv_heads, cfg.head_dim)
            v = dense(p["wv"], kv_src, dt).reshape(b, t, cfg.n_kv_heads, cfg.head_dim)
            if cache is not None:
                cache = {"k": k.astype(dt), "v": v.astype(dt)}
        else:
            k, v = cache["k"], cache["v"]
        out = chunked_attention(q, k, v, causal=False,
                                q_positions=jnp.zeros((s,), jnp.int32),
                                k_positions=jnp.zeros((k.shape[1],), jnp.int32),
                                k_chunk=k_chunk)
        y = dense(p["wo"], out.astype(dt).reshape(b, s, cfg.q_dim), dt)
        return y, cache

    k = dense(p["wk"], x, dt).reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
    v = dense(p["wv"], x, dt).reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
    q = rope(q, positions, cfg.rope_theta, cfg.rope_fraction)
    k = rope(k, positions, cfg.rope_theta, cfg.rope_fraction)

    if cache_index is None:
        # ----- train / prefill -----
        causal = True if causal_override is None else causal_override
        if kind == "local" and cfg.window:
            out = local_block_attention(q, k, v, window=cfg.window)
        else:
            out = chunked_attention(q, k, v, causal=causal,
                                    q_positions=positions,
                                    k_positions=positions, k_chunk=k_chunk)
        new_cache = None
        if cache is not None:  # prefill: write keys into the cache
            length = cache["k"].shape[1]
            new_cache = dict(cache)
            if "pos" in cache and s >= length:
                # local ring buffer: decode addresses slot = pos % length, so
                # place the trailing window rolled to its ring positions.
                shift = s % length
                kw = jnp.roll(k[:, -length:], shift, axis=1)
                vw = jnp.roll(v[:, -length:], shift, axis=1)
                pos_w = jnp.roll(positions[-length:], shift)
                new_cache["k"] = kw.astype(cache["k"].dtype)
                new_cache["v"] = vw.astype(cache["v"].dtype)
                new_cache["pos"] = pos_w.astype(jnp.int32)
            else:
                # global cache (length >= s) or short prompt into a ring
                new_cache["k"] = jax.lax.dynamic_update_slice_in_dim(
                    cache["k"], k.astype(cache["k"].dtype), 0, axis=1)
                new_cache["v"] = jax.lax.dynamic_update_slice_in_dim(
                    cache["v"], v.astype(cache["v"].dtype), 0, axis=1)
                if "pos" in cache:
                    pos_w = jnp.pad(positions, (0, length - s), constant_values=-1)
                    new_cache["pos"] = pos_w.astype(jnp.int32)
        return dense(p["wo"], out.astype(dt).reshape(b, s, cfg.q_dim), dt), new_cache

    # ----- decode (s == 1) -----
    length = cache["k"].shape[1]
    if "pos" in cache:  # local ring buffer
        slot = cache_index % length
        kc = jax.lax.dynamic_update_slice_in_dim(cache["k"], k.astype(cache["k"].dtype), slot, axis=1)
        vc = jax.lax.dynamic_update_slice_in_dim(cache["v"], v.astype(cache["v"].dtype), slot, axis=1)
        posc = jax.lax.dynamic_update_slice_in_dim(
            cache["pos"], cache_index[None].astype(jnp.int32), slot, axis=0)
        valid = (posc >= 0) & (posc <= cache_index) & (posc > cache_index - cfg.window)
        new_cache = {"k": kc, "v": vc, "pos": posc}
    else:
        kc = jax.lax.dynamic_update_slice_in_dim(cache["k"], k.astype(cache["k"].dtype), cache_index, axis=1)
        vc = jax.lax.dynamic_update_slice_in_dim(cache["v"], v.astype(cache["v"].dtype), cache_index, axis=1)
        valid = jnp.arange(length) <= cache_index
        new_cache = {"k": kc, "v": vc}

    g = cfg.n_heads // cfg.n_kv_heads
    qg = q.reshape(b, cfg.n_kv_heads, g, cfg.head_dim).astype(jnp.float32)
    scores = jnp.einsum("bhgd,blhd->bhgl", qg, kc.astype(jnp.float32)) * cfg.head_dim**-0.5
    scores = jnp.where(valid[None, None, None, :], scores, _NEG)
    pr = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgl,blhd->bhgd", pr, vc.astype(jnp.float32))
    out = out.reshape(b, 1, cfg.q_dim).astype(dt)
    return dense(p["wo"], out, dt), new_cache
