"""Shared model primitives: inits, norms, MLPs, RoPE, embeddings.

Conventions:
* params are nested dicts of jax arrays (pure pytrees);
* weights are stored in ``cfg.param_dtype`` and cast to ``cfg.dtype`` at use;
* every matmul keeps the contraction in the weight's trailing/leading dims so
  the sharding rules in ``train/shardings.py`` (keyed on leaf names) apply.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["dense_init", "dense", "norm_init", "norm", "mlp_init", "mlp",
           "embed_init", "rope", "yarn", "cross_entropy"]


def _dtype(name: str):
    return jnp.dtype(name)


def dense_init(key, d_in: int, d_out: int, *, bias: bool = False,
               dtype: str = "float32", scale: Optional[float] = None) -> dict:
    scale = scale if scale is not None else d_in**-0.5
    p = {"w": (jax.random.normal(key, (d_in, d_out), jnp.float32) * scale).astype(_dtype(dtype))}
    if bias:
        p["b"] = jnp.zeros((d_out,), _dtype(dtype))
    return p


def dense(p: dict, x: jax.Array, compute_dtype) -> jax.Array:
    y = x @ p["w"].astype(compute_dtype)
    if "b" in p:
        y = y + p["b"].astype(compute_dtype)
    return y


def norm_init(dim: int, kind: str, dtype: str = "float32") -> dict:
    p = {"scale": jnp.ones((dim,), _dtype(dtype))}
    if kind == "layernorm":
        p["bias"] = jnp.zeros((dim,), _dtype(dtype))
    return p


def norm(p: dict, x: jax.Array, kind: str) -> jax.Array:
    """RMSNorm / LayerNorm with fp32 statistics (standard practice)."""
    x32 = x.astype(jnp.float32)
    if kind == "rmsnorm":
        y = x32 * jax.lax.rsqrt(jnp.mean(x32**2, axis=-1, keepdims=True) + 1e-6)
    elif kind == "layernorm":
        mu = jnp.mean(x32, axis=-1, keepdims=True)
        var = jnp.mean((x32 - mu) ** 2, axis=-1, keepdims=True)
        y = (x32 - mu) * jax.lax.rsqrt(var + 1e-5)
    else:
        raise ValueError(kind)
    y = y * p["scale"].astype(jnp.float32)
    if "bias" in p:
        y = y + p["bias"].astype(jnp.float32)
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# MLPs: swiglu | geglu | gelu | relu2 (nemotron squared-ReLU)
# ---------------------------------------------------------------------------

def mlp_init(key, d_model: int, d_ff: int, kind: str, dtype: str = "float32") -> dict:
    ks = jax.random.split(key, 3)
    p = {"w_up": dense_init(ks[0], d_model, d_ff, dtype=dtype),
         "w_down": dense_init(ks[1], d_ff, d_model, dtype=dtype)}
    if kind in ("swiglu", "geglu"):
        p["w_gate"] = dense_init(ks[2], d_model, d_ff, dtype=dtype)
    return p


def mlp(p: dict, x: jax.Array, kind: str, compute_dtype) -> jax.Array:
    up = dense(p["w_up"], x, compute_dtype)
    if kind == "swiglu":
        h = jax.nn.silu(dense(p["w_gate"], x, compute_dtype)) * up
    elif kind == "geglu":
        h = jax.nn.gelu(dense(p["w_gate"], x, compute_dtype)) * up
    elif kind == "gelu":
        h = jax.nn.gelu(up)
    elif kind == "relu2":
        h = jnp.square(jax.nn.relu(up))
    else:
        raise ValueError(kind)
    return dense(p["w_down"], h, compute_dtype)


# ---------------------------------------------------------------------------
# Embedding / RoPE / loss
# ---------------------------------------------------------------------------

def embed_init(key, vocab: int, d_model: int, dtype: str = "float32") -> dict:
    w = jax.random.normal(key, (vocab, d_model), jnp.float32) * d_model**-0.5
    return {"embedding": w.astype(_dtype(dtype))}


def rope(x: jax.Array, positions: jax.Array, theta: float,
         fraction: float = 1.0, inv_freq: Optional[jax.Array] = None) -> jax.Array:
    """Rotary embedding on the trailing head_dim; ``positions`` broadcasts
    against x's leading dims (..., S, H, D). ``fraction`` < 1 rotates only the
    first ``fraction * D`` channels (stablelm-style partial rotary).
    ``inv_freq`` (D_rot / 2,) replaces the ``theta`` frequencies (YaRN)."""
    d = x.shape[-1]
    d_rot = int(d * fraction)
    d_rot -= d_rot % 2
    if d_rot == 0:
        return x
    xr, xp = x[..., :d_rot], x[..., d_rot:]
    half = d_rot // 2
    if inv_freq is None:
        freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    else:
        freqs = inv_freq
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., S, half)
    ang = ang[..., None, :]  # broadcast over heads (..., S, 1, half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = xr[..., :half].astype(jnp.float32), xr[..., half:].astype(jnp.float32)
    rotated = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return jnp.concatenate([rotated.astype(x.dtype), xp], axis=-1)


def yarn(rs, dim: int, theta: float) -> tuple[np.ndarray, float]:
    """YaRN (arXiv:2309.00071) as DeepSeek-V2 applies it to ``dim`` rope
    channels: the inverse frequencies, interpolated by ``rs.factor`` below
    the correction range and kept above it, and the factor mscale^2 on the
    softmax scale. Where ``mscale`` equals ``mscale_all_dim``, as published,
    the rotation itself is not rescaled."""
    if rs.mscale != rs.mscale_all_dim:
        raise ValueError("YaRN with mscale != mscale_all_dim is not supported")
    half = dim // 2
    extra = theta ** (-np.arange(half, dtype=np.float64) * 2 / dim)
    inter = extra / rs.factor

    def corr(rotations):
        return dim * math.log(rs.original_max_position
                              / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(corr(rs.beta_fast)), 0)
    high = min(math.ceil(corr(rs.beta_slow)), dim - 1)
    ramp = np.clip((np.arange(half) - low) / max(high - low, 1e-3), 0.0, 1.0)
    keep = 1.0 - ramp
    inv_freq = inter * (1.0 - keep) + extra * keep
    mscale = 0.1 * rs.mscale_all_dim * math.log(rs.factor) + 1.0 if rs.factor > 1 else 1.0
    return inv_freq.astype(np.float32), mscale ** 2


def cross_entropy(logits: jax.Array, labels: jax.Array,
                  mask: Optional[jax.Array] = None) -> jax.Array:
    """Mean next-token cross entropy; logits upcast to fp32 (..., S, V)."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if mask is not None:
        return (nll * mask).sum() / jnp.maximum(mask.sum(), 1)
    return nll.mean()
