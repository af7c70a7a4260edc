"""Plain reference of the LM training plane on a DeepSeek-V2 decoder, cut to
one chip's share of an expert-parallel deployment, and D-PSGD.

The decoder follows the published DeepSeek-V2 description
(arXiv:2405.04434; the DeepSeek-V2-Lite config.json): pre-norm residual
blocks with RMSNorm; multi-head latent attention without q-LoRA, the
compressed KV latent RMS-normed before its up-projections, YaRN rope
frequencies on the rope channels and the softmax scale times mscale^2, the
whole causal attention matrix; the first ``first_k_dense_replace`` layers a
dense SwiGLU, the rest routed experts plus shared experts; final RMSNorm,
untied head. The router is ``x·W_r`` in float32 over all of its experts, a
softmax and greedy top-k, the gates renormalized only where
``norm_topk_prob``. The chip's share: of the routed experts only those held
here (``expert_offset`` onwards, ``n_routed_experts`` of them) contribute,
each computed densely for every token and weighted by its gate where the
token chose it (0 elsewhere); no dispatch, sort or kernel. The vocabulary is
the slice the weights hold; the loss is the mean next-token cross entropy
over it.

Departures, which the program makes and the configuration file records:
the embedding is scaled by ``sqrt(hidden_size)``; the rope channels are
rotated in the rotate-half layout (DeepSeek pairs them interleaved: the
same function up to a fixed permutation of the rope columns of the
projections); no sequence-level balance loss; no token dropping.

Everything runs in float32 at ``highest`` matmul precision, layer by
layer. ``mm`` is the matmul of the dense layers, the experts and the head,
so that the control can round their operands below the configuration's
precision. D-PSGD is ``refs/stablelm.py``'s (arXiv:2002.10758 Eq. 5, the
paper's order). It imports nothing of the program; the weights and tokens
are the benchmark's.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from refs.stablelm import _mix_leaf, matmul_f32, matmul_rounded  # noqa: F401

HIGHEST = jax.lax.Precision.HIGHEST


def _rmsnorm(x, p, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * p["scale"]


def yarn_inv_freq(cfg: dict) -> tuple[np.ndarray, float]:
    """DeepSeek-V2's YaRN: inverse frequencies of the rope channels and the
    factor on the softmax scale."""
    rs = dict(cfg["rope_scaling"])
    dim, base = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    factor, orig = float(rs["factor"]), rs["original_max_position_embeddings"]

    def correction_dim(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(correction_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    inter = 1.0 / (factor * base ** (np.arange(0, dim, 2) / dim))
    mask = 1.0 - ramp
    inv_freq = inter * (1.0 - mask) + extra * mask

    def get_mscale(m):
        return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0

    return inv_freq, get_mscale(rs["mscale_all_dim"]) ** 2


def _rope(x, inv_freq):
    """Rotate-half rotary embedding of every channel of x (B, S, H, D)."""
    s, half = x.shape[1], x.shape[-1] // 2
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * jnp.asarray(inv_freq, jnp.float32)[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _mla(a, h, cfg, mm):
    b, s, _ = h.shape
    heads = cfg["num_attention_heads"]
    nope, rope, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    r = cfg["kv_lora_rank"]
    inv_freq, mscale2 = yarn_inv_freq(cfg)
    q = mm(h, a["wq"]["w"]).reshape(b, s, heads, nope + rope)
    kv = mm(h, a["wkv_a"]["w"])
    c = _rmsnorm(kv[..., :r], a["kv_norm"], cfg["rms_norm_eps"])
    k_pe = _rope(kv[..., r:][:, :, None, :], inv_freq)
    k = jnp.concatenate([mm(c, a["w_uk"]["w"]).reshape(b, s, heads, nope),
                         jnp.broadcast_to(k_pe, (b, s, heads, rope))], -1)
    v = mm(c, a["w_uv"]["w"]).reshape(b, s, heads, dv)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], inv_freq)], -1)
    sc = jnp.einsum("bshd,bthd->bhst", q, k, precision=HIGHEST) * (
        (nope + rope) ** -0.5 * mscale2)
    sc = jnp.where(jnp.tril(jnp.ones((s, s), bool))[None, None], sc, -jnp.inf)
    o = jnp.einsum("bhst,bthd->bshd", jax.nn.softmax(sc, -1), v, precision=HIGHEST)
    return mm(o.reshape(b, s, heads * dv), a["wo"]["w"])


def _swiglu(m, x, mm):
    return mm(jax.nn.silu(mm(x, m["w_gate"]["w"])) * mm(x, m["w_up"]["w"]),
              m["w_down"]["w"])


def _route(router_w, x, cfg):
    """(T, k) gates and chosen experts of (T, d) tokens."""
    logits = jnp.matmul(x, router_w.astype(jnp.float32), precision=HIGHEST)
    gates, experts = jax.lax.top_k(jax.nn.softmax(logits, -1),
                                   cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"]:
        gates = gates / gates.sum(-1, keepdims=True)
    return gates, experts


def _moe(m, h, cfg, mm):
    b, s, d = h.shape
    x = h.reshape(b * s, d)
    gates, experts = _route(m["router"]["w"], x, cfg)
    y = _swiglu(m["shared"], x, mm)
    for j in range(cfg["n_routed_experts"]):
        g = jnp.where(experts == cfg["expert_offset"] + j, gates, 0.0).sum(-1)
        ffn = mm(jax.nn.silu(mm(x, m["ew_gate"][j])) * mm(x, m["ew_up"][j]),
                 m["ew_down"][j])
        y = y + g[:, None] * ffn
    return y.reshape(b, s, d), experts


def _layers(params):
    unit = params["unit"][0] if params["unit"] else None
    out = list(params["prologue"])
    if unit is not None:
        out += [jax.tree.map(lambda a, i=i: a[i], unit)
                for i in range(jax.tree.leaves(unit)[0].shape[0])]
    return out


def _forward(params, tokens, cfg, mm):
    """Final hidden states and each expert layer's chosen experts."""
    d, eps = cfg["hidden_size"], cfg["rms_norm_eps"]
    x = params["embed"]["embedding"][tokens].astype(jnp.float32) * math.sqrt(d)
    routes = []
    for lp in _layers(params):
        x = x + _mla(lp["attn"], _rmsnorm(x, lp["norm1"], eps), cfg, mm)
        h = _rmsnorm(x, lp["norm2"], eps)
        if "moe" in lp:
            y, experts = _moe(lp["moe"], h, cfg, mm)
            routes.append(experts)
        else:
            y = _swiglu(lp["mlp"], h, mm)
        x = x + y
    return _rmsnorm(x, params["final_norm"], eps), routes


def loss(params, tokens, cfg: dict, mm=matmul_f32):
    """Mean next-token cross entropy of one node on (B, S) tokens."""
    x, _ = _forward(params, tokens, cfg, mm)
    logits = mm(x, params["lm_head"]["w"])
    logz = jax.nn.logsumexp(logits[:, :-1], -1)
    gold = jnp.take_along_axis(logits[:, :-1], tokens[:, 1:, None], -1)[..., 0]
    return (logz - gold).mean()


@partial(jax.jit, static_argnums=(2,))
def _routes(params, tokens, cfg_items):
    return jnp.stack(_forward(params, tokens, dict(cfg_items), matmul_f32)[1])


def routes(params, tokens, cfg: dict):
    """(expert layers, B·S, k) experts chosen for (B, S) tokens."""
    return _routes(params, tokens, tuple(sorted(cfg.items())))


@partial(jax.jit, static_argnums=(2, 3))
def _value_and_grad(params, tokens, cfg_items, mm):
    return jax.value_and_grad(loss)(params, tokens, dict(cfg_items), mm)


@jax.jit
def _norm(a, b):
    return jnp.linalg.norm(a - b)


def dpsgd(x0_per_node: list, tokens, w_seq, eta: float, cfg: dict,
          mm=matmul_f32):
    """Run ``len(w_seq)`` rounds. ``x0_per_node[i]`` is node i's start,
    placed on its device; ``tokens`` is (rounds, n, B, S) on the host and
    ``w_seq`` (rounds, n, n). Returns each round's mean loss over the nodes
    and the (n, leaves) norms of each node's change from its start. The mix
    runs leaf by leaf and frees each leaf's old values and gradients as it
    goes, so that two nodes' replicas, gradients and starts fit one chip."""
    n = len(x0_per_node)
    devs = [next(iter(jax.tree.leaves(x)[0].devices())) for x in x0_per_node]
    items = tuple(sorted(cfg.items()))
    treedef = jax.tree.structure(x0_per_node[0])
    xs = list(x0_per_node)
    losses = []
    for r in range(len(w_seq)):
        out = [_value_and_grad(xs[i], jax.device_put(tokens[r, i], devs[i]),
                               items, mm) for i in range(n)]
        losses.append(sum(float(v) for v, _ in out) / n)
        flat = [jax.tree.leaves(x) for x in xs]
        grads = [jax.tree.leaves(g) for _, g in out]
        del out, xs
        new = [[] for _ in range(n)]
        for k in range(len(flat[0])):
            for i in range(n):
                here = [jax.device_put(flat[j][k], devs[i]) for j in range(n)]
                w_row = jax.device_put(jnp.asarray(w_seq[r][i], jnp.float32), devs[i])
                new[i].append(_mix_leaf(w_row, here, grads[i][k], jnp.float32(eta)))
            del here
            for i in range(n):
                flat[i][k] = grads[i][k] = None
        xs = [jax.tree.unflatten(treedef, leaves) for leaves in new]
        del new
    change = np.array([[float(_norm(a, b)) for a, b in
                        zip(jax.tree.leaves(x), jax.tree.leaves(x0))]
                       for x, x0 in zip(xs, x0_per_node)])
    return losses, change
