"""Parameter/activation PartitionSpec rules (Megatron-style TP over "model").

Rules are keyed by parameter *names* (the dict keys in the model pytrees) and
specify the spec of the TRAILING dims; any extra leading dims (pattern-unit
stacking, D-PSGD node axis) are padded with None / the node axes by the
callers. GQA with kv_heads < TP keeps KV projections replicated (Megatron GQA
rule); serving caches shard kv-heads when divisible, else head_dim (see
``cache_specs``).
"""
from __future__ import annotations

from typing import Any, Optional, Sequence

import jax
import numpy as np
from jax.sharding import PartitionSpec as P

PyTree = Any

__all__ = ["param_specs", "cache_specs", "batch_specs", "prepend_axes",
           "node_param_specs"]

# trailing-dim rules: name -> tuple over trailing dims ('model' | None)
_W_RULES: dict[str, tuple] = {
    "embedding": ("model", None),
    "lm_head": (None, "model"),
    # attention
    "wq": (None, "model"), "wk": (None, "model"), "wv": (None, "model"),
    "wo": ("model", None),
    # mlp
    "w_up": (None, "model"), "w_gate": (None, "model"), "w_down": ("model", None),
    # moe: the experts a chip holds are ``MoEConfig.experts_held`` (its
    # ``expert_offset`` onwards), never a sharding; the grouped matmul is a
    # custom call that GSPMD cannot split, so the expert tensors replicate
    "router": (None, None),
    "ew_gate": (None, None, None), "ew_up": (None, None, None),
    "ew_down": (None, None, None),
    "shared": None,  # handled by nested w_up/w_gate/w_down
    # mla
    "wkv_a": (None, None), "w_uk": (None, "model"), "w_uv": (None, "model"),
    # rglru
    "w_x": (None, "model"), "conv_w": (None, "model"),
    "w_ai": (None, "model", None), "b_ai": ("model", None), "lam": ("model",),
    "w_out": ("model", None),
    # rwkv
    "w_r": (None, "model"), "w_k": (None, "model"), "w_v": (None, "model"),
    "w_g": (None, "model"), "w_o": ("model", None),
    "w0": ("model",), "u": ("model",), "ln_scale": ("model",),
    "w_lora_a": (None, None), "w_lora_b": (None, "model"),
    "cw_r": (None, "model"), "cw_k": (None, "model"), "cw_v": ("model", None),
}

# GQA KV-replication: these stay replicated when kv_heads < tp
_KV_NAMES = {"wk", "wv"}


def _spec_for_path(path: tuple, leaf: jax.Array, tp: int,
                   kv_dim: Optional[int]) -> P:
    names = [getattr(k, "key", getattr(k, "name", None)) for k in path]
    names = [n for n in names if isinstance(n, str)]
    leaf_name = names[-1] if names else ""
    parent = names[-2] if len(names) >= 2 else ""

    rule: Optional[tuple] = None
    if leaf_name in _W_RULES and _W_RULES[leaf_name] is not None:
        rule = _W_RULES[leaf_name]
        owner = leaf_name
    elif leaf_name == "w" and parent in _W_RULES and _W_RULES[parent] is not None:
        rule = _W_RULES[parent]
        owner = parent
    elif leaf_name == "b" and parent in _W_RULES and _W_RULES[parent] is not None:
        rule = (_W_RULES[parent][-1],)
        owner = parent
    else:
        owner = ""

    if rule is None:
        return P(*([None] * leaf.ndim))

    # GQA: replicate KV projections when kv heads don't divide over TP
    if owner in _KV_NAMES and kv_dim is not None and kv_dim % tp != 0:
        rule = tuple(None for _ in rule)

    # drop 'model' anywhere the dim isn't divisible (e.g. tiny smoke configs)
    dims = leaf.shape[leaf.ndim - len(rule):]
    rule = tuple(("model" if (r == "model" and d % tp == 0) else None)
                 for r, d in zip(rule, dims))
    pad = leaf.ndim - len(rule)
    return P(*([None] * pad + list(rule)))


def param_specs(params: PyTree, tp: int, kv_dim: Optional[int] = None) -> PyTree:
    """PartitionSpec tree matching ``params`` (TP over 'model' only)."""
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: _spec_for_path(path, leaf, tp, kv_dim), params)


def cache_specs(caches: PyTree, tp: int, batch_axes: Sequence[str],
                global_batch: int, n_batch_shards: int) -> PyTree:
    """Serving cache specs. Leaves are (B, L, H, D) K/V, (B, L, R) latent,
    (B, ...) recurrent states, or (L,) position tags. Batch shards over
    ``batch_axes`` when divisible; the widest trailing dim divisible by tp
    takes 'model'."""
    baxes = tuple(batch_axes)

    def spec(path, leaf):
        names = [getattr(k, "key", getattr(k, "name", None)) for k in path]
        names = [n for n in names if isinstance(n, str)]
        leaf_name = names[-1] if names else ""
        if leaf.ndim == 0:
            return P()
        # position tags (L,) replicate
        if leaf_name == "pos":
            return P(*([None] * leaf.ndim))
        dims = list(leaf.shape)
        # which leading dims are stacking (repeats) vs batch? caches built by
        # init_cache may carry a leading repeats dim; detect batch dim as the
        # first dim equal to global_batch.
        out: list = [None] * leaf.ndim
        try:
            b_idx = dims.index(global_batch)
        except ValueError:
            b_idx = -1
        if b_idx >= 0 and global_batch % n_batch_shards == 0 and n_batch_shards > 1:
            out[b_idx] = baxes if len(baxes) > 1 else baxes[0]
        # model-shard one trailing dim (prefer heads over head_dim)
        for cand in range(max(b_idx + 1, leaf.ndim - 2), leaf.ndim):
            if out[cand] is None and dims[cand] % tp == 0 and dims[cand] >= tp:
                out[cand] = "model"
                break
        return P(*out)

    return jax.tree_util.tree_map_with_path(spec, caches)


def batch_specs(batch: PyTree, batch_axes: Sequence[str], global_batch: int,
                n_shards: int) -> PyTree:
    """Input batch specs: shard dim 0 (batch) over batch_axes if divisible."""
    baxes = tuple(batch_axes)
    first = baxes if len(baxes) > 1 else baxes[0]

    def spec(leaf):
        if leaf.ndim == 0:
            return P()
        if global_batch % n_shards == 0 and n_shards > 1:
            return P(*([first] + [None] * (leaf.ndim - 1)))
        return P(*([None] * leaf.ndim))

    return jax.tree.map(spec, batch)


def prepend_axes(specs: PyTree, axes) -> PyTree:
    """Prepend a (node) axis entry to every spec in the tree."""
    def add(s: P) -> P:
        return P(axes, *tuple(s))
    return jax.tree.map(add, specs, is_leaf=lambda x: isinstance(x, P))


def node_param_specs(params: PyTree, mesh,
                     kv_dim: Optional[int] = None) -> PyTree:
    """PartitionSpecs for **node-stacked** parameters: every leaf of
    ``params`` carries the D-PSGD node axis first (``(n_nodes, *shape)``,
    the ``dpsgd.replicate`` layout), and the spec shards that axis over
    every mesh axis except ``'model'`` (the fleet axes) while the trailing
    dims follow the per-path TP rules of ``param_specs``. Node count and
    model size then scale independently: grow the fleet axes for more
    nodes, grow 'model' for a bigger model.

    The node axis only shards when ``n_nodes`` divides the fleet size
    (otherwise it stays replicated, same policy as the TP rules dropping
    'model' on non-divisible dims). Works with an
    ``AbstractMesh`` — nothing here touches devices."""
    axis_names = tuple(mesh.axis_names)
    tp = int(mesh.shape["model"]) if "model" in axis_names else 1
    node_axes = tuple(a for a in axis_names if a != "model")
    fleet = 1
    for a in node_axes:
        fleet *= int(mesh.shape[a])
    node_entry = node_axes if len(node_axes) > 1 else (
        node_axes[0] if node_axes else None)

    def spec(path, leaf):
        if leaf.ndim == 0:
            raise ValueError(
                f"node-stacked leaf at {jax.tree_util.keystr(path)!s} is a "
                "scalar; every leaf must lead with the (n_nodes, ...) axis")
        # _spec_for_path resolves the trailing-dim rule from the path and
        # pads the extra leading (node) dim with None; swap that None for
        # the fleet axes when the node count divides over them.
        base = _spec_for_path(path, leaf, tp, kv_dim)
        entries = list(tuple(base))
        if node_entry is not None and fleet > 1 and leaf.shape[0] % fleet == 0:
            entries[0] = node_entry
        return P(*entries)

    return jax.tree_util.tree_map_with_path(spec, params)
