"""Mixture-of-Experts MLP: one chip's share of a token-choice expert layer.

Routing: the router ``x·W_r`` in float32 over all ``n_experts`` experts,
a softmax, greedy top-k. The gates are renormalized to sum to 1 where
``norm_topk`` is set, and kept as they are otherwise (DeepSeek-V2 publishes
``norm_topk_prob: false``).

Expert parallelism: the layer holds experts ``expert_offset ..
expert_offset + held - 1`` and computes ``Σ_{e chosen, e held} g_e ·
FFN_e(x)``, plus the shared experts, which every chip computes alike. What
the experts held on other chips add is left out here: their chips compute
it, and on one chip the layer runs without that exchange. A configuration
that holds every expert (the default) computes the whole layer.

Dispatch is drop-free: the (token, choice) pairs are sorted by expert, and
the held experts' SwiGLU runs as grouped matmuls over them
(``kernels/grouped_matmul.py``), sized by the pairs routed to the held
experts and not by a capacity.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig, MoEConfig
from ..kernels.grouped_matmul import grouped_matmul
from .layers import dense_init, mlp, mlp_init

__all__ = ["moe_init", "moe_apply", "moe_route"]


def moe_init(key, cfg: ModelConfig, mcfg: MoEConfig) -> dict:
    ks = jax.random.split(key, 5)
    e, d, f = mcfg.held, cfg.d_model, mcfg.d_ff_expert
    scale = d**-0.5
    p = {
        "router": dense_init(ks[0], d, mcfg.n_experts, dtype=cfg.param_dtype),
        "ew_gate": (jax.random.normal(ks[1], (e, d, f), jnp.float32) * scale).astype(cfg.param_dtype),
        "ew_up": (jax.random.normal(ks[2], (e, d, f), jnp.float32) * scale).astype(cfg.param_dtype),
        "ew_down": (jax.random.normal(ks[3], (e, f, d), jnp.float32) * f**-0.5).astype(cfg.param_dtype),
    }
    if mcfg.n_shared:
        p["shared"] = mlp_init(ks[4], d, f * mcfg.n_shared, "swiglu", dtype=cfg.param_dtype)
    return p


def moe_route(router_w: jax.Array, x: jax.Array, mcfg: MoEConfig
              ) -> tuple[jax.Array, jax.Array]:
    """(T, d) tokens -> (T, k) gates and chosen experts: float32 logits
    over all experts, softmax, greedy top-k."""
    logits = jnp.matmul(x.astype(jnp.float32), router_w.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    gates, experts = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), mcfg.top_k)
    if mcfg.norm_topk:
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    return gates, experts


def moe_apply(p: dict, x: jax.Array, cfg: ModelConfig, mcfg: MoEConfig) -> jax.Array:
    """x: (B, S, d) -> (B, S, d): the held experts' part of the routed
    output plus the shared experts."""
    dt = jnp.dtype(cfg.dtype)
    b, s, d = x.shape
    k = mcfg.top_k
    xt = x.reshape(b * s, d)
    with jax.named_scope("moe.route"):
        gates, experts = moe_route(p["router"]["w"], xt, mcfg)
        flat = experts.reshape(-1)
        order = jnp.argsort(flat)                   # pairs grouped by expert
        sizes = jnp.bincount(flat, length=mcfg.n_experts)
        xs = xt.astype(dt)[order // k]
    with jax.named_scope("moe.gmm"):
        def grouped(a, w):
            return grouped_matmul(a, p[w].astype(dt), sizes, mcfg.expert_offset)
        h = jax.nn.silu(grouped(xs, "ew_gate")) * grouped(xs, "ew_up")
        ys = grouped(h, "ew_down")
    with jax.named_scope("moe.combine"):
        back = jnp.zeros_like(order).at[order].set(jnp.arange(order.size))
        y = jnp.einsum("tk,tkd->td", gates, ys[back].reshape(b * s, k, d))
        y = y.astype(dt)
        if "shared" in p:
            y = y + mlp(p["shared"], xt.astype(dt), "swiglu", dt)
    return y.reshape(b, s, d)
