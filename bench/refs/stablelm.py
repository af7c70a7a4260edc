"""Plain reference of the LM training plane: a StableLM decoder and D-PSGD.

The decoder follows the published StableLM description (pre-norm residual
blocks with LayerNorm, full multi-head attention with partial rotary
embeddings of the rotate-half form on the first quarter of each head,
SwiGLU MLP, final LayerNorm, untied head) with one departure that the
program makes and the configuration file records: the embedding is scaled by
``sqrt(hidden_size)``. Everything runs in float32 at ``highest`` matmul
precision, layer by layer, with the whole attention matrix; the loss is the
mean next-token cross entropy.

D-PSGD (arXiv:2002.10758 Eq. 5, the paper's order): each round every node
takes its gradient at its own parameters, then ``x_i <- sum_j W_ij x_j -
eta g_i``. Node ``i`` lives on device ``i``; the mix runs leaf by leaf so
that no device holds more than one leaf of the other nodes at a time.

``mm`` is the matmul used for the dense layers and the head, so that the
control can round their operands below the configuration's precision. It
imports nothing of the program; the weights and tokens are the benchmark's.
"""
from __future__ import annotations

import math
from functools import lru_cache, partial

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def matmul_f32(x, w):
    return jnp.matmul(x.astype(jnp.float32), w.astype(jnp.float32),
                      precision=HIGHEST)


def _rounded(x, dtype):
    """``x`` rounded to ``dtype`` in the forward pass; the cotangent passes
    through unrounded, so the backward matmuls see rounded operands but no
    gradient underflows in the narrow type."""
    x = x.astype(jnp.float32)
    return x + jax.lax.stop_gradient(x.astype(dtype).astype(jnp.float32) - x)


@lru_cache(maxsize=None)
def matmul_rounded(dtype):
    """Matmul on operands rounded to ``dtype``, accumulated in float32 (one
    function per type, so that its compiled gradient is reused)."""
    def mm(x, w):
        return matmul_f32(_rounded(x, dtype), _rounded(w, dtype))
    return mm


def _layernorm(x, p, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _rope(x, rot: int, theta: float):
    """Rotate-half rotary embedding on the first ``rot`` channels of each
    head; x is (B, S, H, D)."""
    s = x.shape[1]
    half = rot // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2, rest = x[..., :half], x[..., half:rot], x[..., rot:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], -1)


def loss(params, tokens, cfg: dict, mm=matmul_f32):
    """Mean next-token cross entropy of one node on (B, S) tokens."""
    d = cfg["hidden_size"]
    heads = cfg["num_attention_heads"]
    hd = d // heads
    rot = int(hd * cfg["partial_rotary_factor"])
    rot -= rot % 2
    eps = cfg["layer_norm_eps"]
    b, s = tokens.shape
    x = params["embed"]["embedding"][tokens].astype(jnp.float32) * math.sqrt(d)
    causal = jnp.tril(jnp.ones((s, s), bool))
    for layer in range(cfg["num_hidden_layers"]):
        lp = jax.tree.map(lambda a: a[layer], params["unit"][0])
        h = _layernorm(x, lp["norm1"], eps)
        a = lp["attn"]
        q = mm(h, a["wq"]["w"]).reshape(b, s, heads, hd)
        k = mm(h, a["wk"]["w"]).reshape(b, s, heads, hd)
        v = mm(h, a["wv"]["w"]).reshape(b, s, heads, hd)
        q, k = _rope(q, rot, cfg["rope_theta"]), _rope(k, rot, cfg["rope_theta"])
        sc = jnp.einsum("bshd,bthd->bhst", q, k, precision=HIGHEST) / math.sqrt(hd)
        sc = jnp.where(causal[None, None], sc, -jnp.inf)
        o = jnp.einsum("bhst,bthd->bshd", jax.nn.softmax(sc, -1), v,
                       precision=HIGHEST)
        x = x + mm(o.reshape(b, s, d), a["wo"]["w"])
        h = _layernorm(x, lp["norm2"], eps)
        m = lp["mlp"]
        x = x + mm(jax.nn.silu(mm(h, m["w_gate"]["w"])) * mm(h, m["w_up"]["w"]),
                   m["w_down"]["w"])
    x = _layernorm(x, params["final_norm"], eps)
    logits = mm(x, params["lm_head"]["w"])
    logz = jax.nn.logsumexp(logits[:, :-1], -1)
    gold = jnp.take_along_axis(logits[:, :-1], tokens[:, 1:, None], -1)[..., 0]
    return (logz - gold).mean()


@partial(jax.jit, static_argnums=(2, 3))
def _value_and_grad(params, tokens, cfg_items, mm):
    return jax.value_and_grad(loss)(params, tokens, dict(cfg_items), mm)


@jax.jit
def _mix_leaf(w_row, leaves, g, eta):
    out = w_row[0] * leaves[0]
    for j in range(1, len(leaves)):
        out = out + w_row[j] * leaves[j]
    return out - eta * g


def dpsgd(x0_per_node: list, tokens, w_seq, eta: float, cfg: dict,
          mm=matmul_f32):
    """Run ``len(w_seq)`` rounds. ``x0_per_node[i]`` is node i's start,
    placed on its device; ``tokens`` is (rounds, n, B, S) on the host and
    ``w_seq`` (rounds, n, n). Returns each round's mean loss over the nodes
    and the final parameters per node."""
    n = len(x0_per_node)
    devs = [next(iter(jax.tree.leaves(x)[0].devices())) for x in x0_per_node]
    items = tuple(sorted(cfg.items()))
    xs = list(x0_per_node)
    losses = []
    for r in range(len(w_seq)):
        out = [_value_and_grad(xs[i], jax.device_put(tokens[r, i], devs[i]),
                               items, mm) for i in range(n)]
        losses.append(sum(float(v) for v, _ in out) / n)
        flat = [jax.tree.leaves(x) for x in xs]
        grads = [jax.tree.leaves(g) for _, g in out]
        treedef = jax.tree.structure(xs[0])
        new = [[] for _ in range(n)]
        for k in range(len(flat[0])):
            for i in range(n):
                here = [jax.device_put(flat[j][k], devs[i]) for j in range(n)]
                w_row = jax.device_put(jnp.asarray(w_seq[r][i], jnp.float32), devs[i])
                new[i].append(_mix_leaf(w_row, here, grads[i][k],
                                        jnp.float32(eta)))
        del flat, grads, out
        xs = [jax.tree.unflatten(treedef, leaves) for leaves in new]
    return losses, xs
