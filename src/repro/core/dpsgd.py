"""D-PSGD optimizer (paper Algorithm 1 / Eq. 5) — wireless-faithful simulation.

State layout: every parameter leaf carries a leading **node axis** of size n
(``X = (x_1 .. x_n)`` stacked), mirroring Eq. 5:

    X_{k+1} <- W @ X_k  -  eta * stack_i( grad F_i(x_{k,i}; xi_{k,i}) )

One step = (a) per-node minibatch gradients via ``jax.vmap`` over the node
axis, (b) mixing with the averaging matrix W, (c) SGD update. Both mixing
paths share one node-order combine (``_node_combine``): every output row
is the fp32 sum, in node-index order, of ``W[i, j] * x_j``, elementwise on
the leaves as they are laid out, and the update joins the combine's
pass. Where a mesh's fleet axes shard the node axis,
``exchange_mix`` runs it inside ``shard_map`` on every chip's gathered
blocks. Where every node sits on one device (the unsharded steps, the CNN
sweeps, a node axis that does not divide over a mesh's fleet axes),
``mix`` runs it on the local stack up to ``COMBINE_MAX_NODES`` nodes, and
past that multiplies by W as one fp32 matmul over the node axis; the
compressed mixes keep their own matmul.
This runs the *mathematics* of n wireless nodes exactly on one host; the
wall-clock communication cost is modeled separately by ``comm_model.tdm_time_s``
(exactly how the paper itself evaluates runtime: measured compute + Eq. 3).

Also supports:
* ``local_steps`` H >= 1 (Cooperative-SGD generalization; H=1 == paper).
* arbitrary W (paper row-stochastic, Metropolis, fully-connected baseline).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..launch.mesh import fleet_size, replica_axes

__all__ = ["DPSGDConfig", "replicate", "mix", "mix_path",
           "COMBINE_MAX_NODES", "exchange_mix", "dpsgd_step",
           "make_dpsgd_step", "dpsgd_masked_step", "make_dpsgd_masked_step",
           "dpsgd_masked_compressed_step",
           "make_dpsgd_compressed_step", "embed_w", "zero_residuals",
           "node_axis_size"]

PyTree = Any


def node_axis_size(tree: PyTree, what: str = "node state",
                   allow_scalar: bool = False) -> int:
    """The shared leading node-axis length of every leaf — the shape
    contract of the masked-state layout (every parameter/residual/batch
    leaf is ``(n_nodes, ...)``). Raises with the offending leaf path on
    scalar leaves or disagreeing leading dims: a ragged pytree would
    otherwise silently mis-mask (``live`` broadcast against the wrong
    axis) or mis-mix (W applied to a non-node axis) downstream.

    ``allow_scalar=True`` skips 0-d leaves (checkpoint metadata like step
    counters legitimately has no node axis); returns 0 if every leaf was
    scalar."""
    sizes: dict[str, int] = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        if getattr(leaf, "ndim", 0) == 0:
            if allow_scalar:
                continue
            raise ValueError(
                f"{what} leaf {jax.tree_util.keystr(path)!s} is a scalar; "
                "every leaf must carry the leading (n_nodes, ...) node axis")
        sizes[jax.tree_util.keystr(path)] = int(leaf.shape[0])
    uniq = set(sizes.values())
    if len(uniq) > 1:
        raise ValueError(
            f"{what} leaves disagree on the leading node axis: {sizes}")
    return uniq.pop() if uniq else 0


@dataclasses.dataclass(frozen=True)
class DPSGDConfig:
    eta: float = 0.01        # learning rate (paper Fig. 3: 0.01)
    local_steps: int = 1     # H; H=1 is the paper's Algorithm 1
    # Eq. 5 order. True:  X <- W X - eta G(X)   (gradient at pre-mix params,
    # so computation and communication overlap — Lian et al.'s Algorithm 1).
    # False: X <- W (X - eta G(X))  (gradient-first: local update, then mix).
    # Both orders apply W every iteration and share the same fixed points.
    mix_first: bool = True


def replicate(params: PyTree, n: int) -> PyTree:
    """All nodes start from the same x_0 (paper assumption for Eq. 7)."""
    return jax.tree.map(lambda p: jnp.broadcast_to(p[None], (n, *p.shape)), params)


# Mixing matmuls run at full precision: the TPU's default f32 matmul rounds
# its operands to bf16, which would cut every parameter to bf16 each round
# (an identity W row would not return its node's parameters verbatim).
_MIX_PRECISION = jax.lax.Precision.HIGHEST

# The most nodes whose one-device mix is the node-order combine. Up to 8
# the node axis fills at most one float32 (8, 128) tile's sublanes, so a W
# matmul over it leaves the MXU empty and only relays every leaf to
# (n, -1) and back; past it W's contraction starts to feed the MXU, while
# the combine's n^2 multiply-adds would not. Set from that tiling; the
# chip measurement of mix + update at n = 2, 4, 8, 16 that should confirm
# it is still to be made (PERF.md, section 7).
COMBINE_MAX_NODES = 8


def mix_path(n: int) -> str:
    """How ``mix`` combines ``n`` nodes on one device: ``"combine"``
    (``n <= COMBINE_MAX_NODES``) or ``"dense"`` (the W matmul)."""
    return "combine" if n <= COMBINE_MAX_NODES else "dense"


def _node_combine(rows: jax.Array, node: Callable[[int], jax.Array],
                  rank: int, dtype) -> jax.Array:
    """W's rows ``rows`` (b, n) applied to the nodes ``node(0)`` ..
    ``node(n - 1)``, each of rank ``rank``: every output row is the sum over
    the nodes j, in node-index order, of ``rows[:, j] * node(j)``, as
    float32 multiply-adds cast back to ``dtype``. The leaves keep their
    layout and W stays runtime data; an identity row returns its node bit
    for bit, and what follows elementwise (the update) fuses with it."""
    col = (rows.shape[0],) + (1,) * rank
    acc = None
    for j in range(rows.shape[1]):
        term = rows[:, j].reshape(col) * node(j).astype(jnp.float32)[None]
        acc = term if acc is None else acc + term
    return acc.astype(dtype)


def mix(node_params: PyTree, w: jax.Array) -> PyTree:
    """X <- W @ X on the leading node axis of every leaf, under the
    ``dpsgd.mix`` name scope, where every node sits on one device.

    Up to ``COMBINE_MAX_NODES`` nodes this is ``exchange_mix``'s node-order
    combine over the local stack, one output row at a time, the rows
    joined: the leaves keep their layout, and the compiler forms every row
    of a leaf in one pass over its nodes. Past it, a float32 ``HIGHEST``
    matmul of W with every leaf flattened to (n, -1). ``mix_path`` names
    the path; ``_mix_update`` adds the step's update."""
    def _mix(leaf: jax.Array) -> jax.Array:
        n = leaf.shape[0]
        if mix_path(n) == "dense":
            flat = leaf.reshape(n, -1)
            return jnp.matmul(w.astype(flat.dtype), flat,
                              precision=_MIX_PRECISION).reshape(leaf.shape)
        w32 = w.astype(jnp.float32)
        return jnp.concatenate([
            _node_combine(w32[i:i + 1], lambda j: leaf[j], leaf.ndim - 1,
                          leaf.dtype) for i in range(n)])
    with jax.named_scope("dpsgd.mix"):
        return jax.tree.map(_mix, node_params)


def _mix_update(node_params: PyTree, w: jax.Array, grads: PyTree,
                eta: float) -> PyTree:
    """W X - eta G on one device: ``_sgd(mix(X, W), G)``, where ``mix``
    combines, with each node's row updated on its own and the rows joined.
    The compiler then forms each row with its update in one pass and
    writes the joined rows, the next state, whole, as it writes a matmul's
    output: a state formed anew inside each of its consumers instead may
    round differently in each (XLA:CPU contracts multiply-adds per
    fusion)."""
    def _update(m: jax.Array, g: jax.Array) -> jax.Array:
        n = m.shape[0]
        if mix_path(n) == "dense":
            return _sgd(m, g, eta)
        with jax.named_scope("dpsgd.update"):
            return jnp.concatenate([_sgd(m[i:i + 1], g[i:i + 1], eta)
                                    for i in range(n)])
    return jax.tree.map(_update, mix(node_params, w), grads)


def exchange_mix(node_params: PyTree, w: jax.Array, mesh,
                 grads: PyTree = None, eta: float = 0.0) -> PyTree:
    """X <- W @ X (then ``- eta * grads`` when given) with the node axis
    sharded over ``mesh``'s fleet axes (every axis but ``'model'``), inside
    ``jax.shard_map`` over the layout of ``train.shardings.node_param_specs``,
    so a leaf sharded over ``'model'`` is gathered over the fleet axes only
    and stays sharded.

    Chip c holds the b = n / F node rows c*b .. c*b + b - 1. One
    ``all_gather`` per leaf over the fleet axes brings it every chip's
    block, and ``_node_combine`` forms its output rows from them: the sum
    over every node i, in node index order, of ``W[rows_c, i] * x_i``, as
    elementwise fp32 multiply-adds with the weights sliced from the traced
    W, so W stays runtime data, an identity row returns its node's
    parameters bit for bit, and the combine and the update are one
    elementwise pass that XLA fuses, in place of a matmul over the node
    axis and a separate update. The exchange and the combine run under the
    ``dpsgd.mix`` name scope, the update under ``dpsgd.update``. ``mix``
    runs the same combine where all nodes sit on one device, so up to
    ``COMBINE_MAX_NODES`` nodes the two agree bit for bit.

    Why a gather and not ``ppermute`` shifts: every node hears every other
    in a dense W, so each chip receives F - 1 blocks either way, and on a
    TPU v5e 2x2 one ``ppermute`` moves a block over a single link while the
    all-gather drives every link at once (PERF.md, section 6)."""
    # imported here: the train package imports core on its own import
    from ..train.shardings import node_param_specs

    axes = replica_axes(mesh)
    fleet = fleet_size(mesh)
    n = node_axis_size(node_params, "node_params")
    if n % fleet:
        raise ValueError(
            f"{n} nodes do not divide over the {fleet} fleet slots of "
            f"mesh axes {axes}")
    b = n // fleet
    axis = axes if len(axes) > 1 else axes[0]

    def body(x_tree, w, g_tree=None):
        c = jax.lax.axis_index(axis)
        rows = jax.lax.dynamic_slice_in_dim(
            w.astype(jnp.float32), c * b, b, axis=0)          # (b, n)

        def _mix(x: jax.Array) -> jax.Array:
            # (F, b, ...): every chip's block, in node-index order
            xs = jax.lax.all_gather(x, axis, axis=0, tiled=False)
            return _node_combine(rows, lambda i: xs[i // b, i % b],
                                 x.ndim - 1, x.dtype)

        with jax.named_scope("dpsgd.mix"):
            mixed = jax.tree.map(_mix, x_tree)
        return mixed if g_tree is None else _sgd(mixed, g_tree, eta)

    spec = node_param_specs(node_params, mesh)
    args, specs = (node_params, w), (spec, P())
    if grads is not None:
        args, specs = args + (grads,), specs + (spec,)
    return jax.shard_map(body, mesh=mesh, in_specs=specs,
                         out_specs=spec)(*args)


def _node_grads(
    loss_fn: Callable[[PyTree, PyTree], jax.Array],
    node_params: PyTree,
    node_batches: PyTree,
) -> tuple[jax.Array, PyTree]:
    """Per-node loss/grads: vmap over the leading node axis of params+batch."""
    with jax.named_scope("dpsgd.grad"):
        return jax.vmap(jax.value_and_grad(loss_fn))(node_params, node_batches)


def _masked_node_grads(loss_fn, node_params: PyTree, node_batches: PyTree,
                       live: jax.Array) -> tuple[jax.Array, PyTree]:
    """``_node_grads`` with the rows of nodes not in ``live`` set to zero
    (``where``, so NaNs from junk batch rows cannot leak)."""
    losses, grads = _node_grads(loss_fn, node_params, node_batches)

    def _mask(g: jax.Array) -> jax.Array:
        m = live.reshape(live.shape[0], *([1] * (g.ndim - 1)))
        return jnp.where(m, g, jnp.zeros((), dtype=g.dtype))

    with jax.named_scope("dpsgd.grad"):
        return losses, jax.tree.map(_mask, grads)


def _sgd(node_params: PyTree, grads: PyTree, eta: float) -> PyTree:
    """x - eta * g on every leaf, under the ``dpsgd.update`` name scope."""
    with jax.named_scope("dpsgd.update"):
        return jax.tree.map(lambda x, g: x - eta * g.astype(x.dtype),
                            node_params, grads)


@partial(jax.jit, static_argnames=("loss_fn", "config"))
def dpsgd_step(
    loss_fn: Callable[[PyTree, PyTree], jax.Array],
    node_params: PyTree,
    node_batches: PyTree,
    w: jax.Array,
    config: DPSGDConfig = DPSGDConfig(),
) -> tuple[PyTree, jax.Array]:
    """One D-PSGD iteration (Algorithm 1 steps 2-5) for all n nodes.

    Eq. 5:  X_{k+1} = W X_k - eta * G(X_k)   — note the gradient is taken at
    X_k (the *pre-mix* parameters), exactly as in Lian et al./the paper, so
    computation and communication could proceed concurrently on real systems.

    ``node_batches`` leaves have shape (n, local_batch, ...). With
    local_steps > 1 the batch leaves carry (n, H, local_batch, ...) and W is
    applied once per H local SGD steps (Cooperative SGD).
    """
    h = config.local_steps
    if h == 1:
        losses, grads = _node_grads(loss_fn, node_params, node_batches)
        if config.mix_first:
            new_params = _mix_update(node_params, w, grads, config.eta)
        else:
            # gradient-first order: X <- W (X - eta G). The previous
            # implementation skipped W entirely here, silently degenerating
            # to plain per-node SGD.
            new_params = mix(_sgd(node_params, grads, config.eta), w)
        return new_params, losses

    def local_step(params, batch):
        losses, grads = _node_grads(loss_fn, params, batch)
        return _sgd(params, grads, config.eta), losses

    def scan_body(params, batch):
        return local_step(params, batch)

    # (n, H, ...) -> scan over H with node axis intact
    batches_h = jax.tree.map(lambda b: jnp.moveaxis(b, 1, 0), node_batches)
    node_params, losses = jax.lax.scan(scan_body, node_params, batches_h)
    node_params = mix(node_params, w)
    return node_params, losses[-1]


def embed_w(w_live, ids, n_total: int):
    """Embed a compacted (n_live, n_live) mixing matrix into a fixed (n, n)
    one for the masked-state layout: live rows/columns are scattered to their
    original node indices ``ids``; dead rows get an identity row (their stale
    parameters are carried unchanged) and dead columns weight 0 (they feed
    nothing into live rows). This is the W contract ``dpsgd_masked_step``
    assumes, and what makes churn jit-compatible: the state keeps its full
    (n, ...) shape forever, no reshapes.
    """
    ids = np.asarray(ids, dtype=np.int64)
    w_full = np.eye(n_total, dtype=np.float64)
    w_full[np.ix_(ids, ids)] = np.asarray(w_live, dtype=np.float64)
    return w_full


def dpsgd_masked_step(
    loss_fn: Callable[[PyTree, PyTree], jax.Array],
    node_params: PyTree,
    node_batches: PyTree,
    w: jax.Array,
    live: jax.Array,
    config: DPSGDConfig = DPSGDConfig(),
    mesh=None,
) -> tuple[PyTree, jax.Array]:
    """One D-PSGD iteration on a fixed-width node state under churn.

    ``live`` is a (n,) bool mask; ``w`` must follow the ``embed_w`` contract
    (identity rows / zero columns for dead nodes). Dead rows carry their
    parameters unchanged — their gradients are masked to zero (``where``, so
    NaNs from junk batch rows cannot leak) and their identity W row returns
    them verbatim — and they never contribute to live rows, so live rows
    evolve exactly as the compacted (reshape_nodes) state would. Returned
    per-node losses are raw; mask with ``live`` before aggregating.

    Only ``local_steps == 1`` is supported (the scan path mixes every round,
    like the paper's Algorithm 1).

    ``mesh``, when given, is a mesh whose fleet axes shard the node axis
    (``train.shardings.node_param_specs``): W X is then ``exchange_mix``,
    fused with the update, in place of the one-device ``mix``.
    """
    if config.local_steps != 1:
        raise NotImplementedError(
            "dpsgd_masked_step supports local_steps == 1 only")
    losses, grads = _masked_node_grads(loss_fn, node_params, node_batches,
                                       live)
    if mesh is not None:
        if config.mix_first:
            return exchange_mix(node_params, w, mesh, grads,
                                config.eta), losses
        return exchange_mix(_sgd(node_params, grads, config.eta), w,
                            mesh), losses
    if config.mix_first:
        new_params = _mix_update(node_params, w, grads, config.eta)
    else:
        new_params = mix(_sgd(node_params, grads, config.eta), w)
    return new_params, losses


def zero_residuals(node_params: PyTree) -> PyTree:
    """Fresh error-feedback state: one fp32 zero per parameter (the residual
    lives in fp32 no matter the parameter dtype, so quantization error
    accumulates at full precision)."""
    return jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                        node_params)


def _mix_compressed(
    node_params: PyTree,
    residuals: PyTree,
    w: jax.Array,
    live: jax.Array,
    quant,
) -> tuple[PyTree, PyTree]:
    """Quantized error-feedback mixing on the masked layout.

    Per node:  m_i = Q(x_i + e_i),  e_i' = (x_i + e_i) - m_i;  receivers mix
    the **exact** own value with dequantized neighbor messages,
    x_j' = W_jj x_j + sum_{i!=j} W_ji m_i (CHOCO-SGD-flavored, ref [6] of
    the paper). Under the ``embed_w`` contract dead rows come back verbatim
    (W_jj = 1, off-diagonal 0) and dead columns weight 0, and dead residuals
    are zeroed so a node that dies mid-trace cannot leak stale quantization
    error anywhere. ``mode="none"`` degenerates to the exact ``mix``
    (bit-identical to the uncompressed step) with the residuals passed
    through untouched.

    ``quant.granularity`` picks the wire format:

    * ``"message"`` — leaves are concatenated into one (n, total) buffer
      before quantization, so the blockwise-int8 payload is exactly
      ``compression.payload_bits`` of the full model (the historical
      format; bit-identical to every pre-pytree trace).
    * ``"leaf"`` — each tensor quantizes independently with its residual
      carried as a pytree leaf matching the parameter. This never gathers
      the model into one buffer, so mesh-sharded leaves stay sharded; the
      extra tail-block padding per leaf is what
      ``compression.payload_bits_tree`` charges on the wire.

    Both paths agree bit-for-bit for bf16 (elementwise) and for int8
    whenever every leaf's flat size is a whole number of quantization
    blocks; ragged leaves change the block partitioning, which is exactly
    the wire-format difference the two granularities name.
    """
    if quant.mode == "none":
        return mix(node_params, w), residuals
    n = node_axis_size(node_params, "node_params")
    if live.shape[0] != n or w.shape[-1] != n:
        raise ValueError(
            f"live {live.shape} / w {w.shape} disagree with the node axis "
            f"n={n} of node_params")
    by_leaf = getattr(quant, "granularity", "message") == "leaf"
    with jax.named_scope("dpsgd.mix"):
        return (_mix_compressed_leaf if by_leaf else _mix_compressed_message)(
            node_params, residuals, w, live, quant)


def _quantize(flat: jax.Array, res: jax.Array, live_col: jax.Array,
              quant) -> tuple[jax.Array, jax.Array]:
    """One wire buffer's sender side, under the ``dpsgd.quantize`` name
    scope: the (n, size) rows ``flat`` plus their error-feedback residuals
    ``res``, quantized and dequantized as the receivers see them, and the
    new residuals, zero in rows not in ``live_col``."""
    from .compression import dequantize_int8_rows, quantize_int8_rows

    with jax.named_scope("dpsgd.quantize"):
        carried = flat + (res if quant.error_feedback else 0.0)
        if quant.mode == "bf16":
            deq = carried.astype(jnp.bfloat16).astype(jnp.float32)
        elif quant.mode == "int8":
            q, scale = quantize_int8_rows(carried)
            deq = dequantize_int8_rows(q, scale, carried.shape[1])
        else:
            raise ValueError(f"unknown compression mode {quant.mode!r}")
        new_res = carried - deq if quant.error_feedback else res
        return deq, jnp.where(live_col, new_res,
                              jnp.zeros((), new_res.dtype))


def _mix_compressed_message(
    node_params: PyTree,
    residuals: PyTree,
    w: jax.Array,
    live: jax.Array,
    quant,
) -> tuple[PyTree, PyTree]:
    """Concat-flat wire format: one quantized buffer per node per round."""
    leaves, treedef = jax.tree.flatten(node_params)
    res_leaves = treedef.flatten_up_to(residuals)
    n = leaves[0].shape[0]
    flat = jnp.concatenate(
        [p.reshape(n, -1).astype(jnp.float32) for p in leaves], axis=1)
    res = jnp.concatenate([r.reshape(n, -1) for r in res_leaves], axis=1)
    deq, new_res = _quantize(flat, res, live.reshape(n, 1), quant)
    w32 = w.astype(jnp.float32)
    diag = jnp.diagonal(w32)
    off = w32 - jnp.diag(diag)
    mixed = diag[:, None] * flat + jnp.matmul(off, deq,
                                              precision=_MIX_PRECISION)

    out, res_out, offset = [], [], 0
    for p in leaves:
        size = int(np.prod(p.shape[1:], dtype=np.int64))
        out.append(mixed[:, offset:offset + size]
                   .reshape(p.shape).astype(p.dtype))
        res_out.append(new_res[:, offset:offset + size].reshape(p.shape))
        offset += size
    return (jax.tree.unflatten(treedef, out),
            jax.tree.unflatten(treedef, res_out))


def _mix_compressed_leaf(
    node_params: PyTree,
    residuals: PyTree,
    w: jax.Array,
    live: jax.Array,
    quant,
) -> tuple[PyTree, PyTree]:
    """Per-tensor wire format: each leaf quantizes with its own block grid
    and carries its own error-feedback residual, so sharded leaves never
    gather. ``payload_bits_tree(..., granularity="leaf")`` charges the
    per-leaf tail padding this implies."""
    w32 = w.astype(jnp.float32)
    diag = jnp.diagonal(w32)
    off = w32 - jnp.diag(diag)
    live_col = live.reshape(live.shape[0], 1)

    def _one(p: jax.Array, r: jax.Array) -> tuple[jax.Array, jax.Array]:
        n = p.shape[0]
        flat = p.reshape(n, -1).astype(jnp.float32)
        deq, new_res = _quantize(flat, r.reshape(n, -1), live_col, quant)
        mixed = diag[:, None] * flat + jnp.matmul(off, deq,
                                              precision=_MIX_PRECISION)
        return mixed.reshape(p.shape).astype(p.dtype), new_res.reshape(p.shape)

    leaves, treedef = jax.tree.flatten(node_params)
    res_leaves = treedef.flatten_up_to(residuals)
    pairs = [_one(p, r) for p, r in zip(leaves, res_leaves)]
    return (jax.tree.unflatten(treedef, [m for m, _ in pairs]),
            jax.tree.unflatten(treedef, [e for _, e in pairs]))


def dpsgd_masked_compressed_step(
    loss_fn: Callable[[PyTree, PyTree], jax.Array],
    node_params: PyTree,
    node_batches: PyTree,
    w: jax.Array,
    live: jax.Array,
    residuals: PyTree,
    quant,
    config: DPSGDConfig = DPSGDConfig(),
) -> tuple[PyTree, PyTree, jax.Array]:
    """``dpsgd_masked_step`` with quantized error-feedback mixing.

    ``quant`` is a ``compression.QuantConfig``; every sender quantizes once
    per round — one blockwise-int8 buffer (or bf16 cast) over the
    concatenated leaves with ``granularity="message"``, or one buffer per
    tensor with ``granularity="leaf"`` (the mesh-shardable format; see
    ``_mix_compressed``) — the self term stays exact, and per-node residuals ride
    along as explicit state — pass ``zero_residuals(node_params)`` at round 0 and
    thread the returned residuals through (the train-on-trace scan carries
    them). Dead nodes (``live`` False) keep their parameters verbatim and
    their residuals zeroed, so churn composes with error feedback. With
    ``quant.mode == "none"`` this is exactly ``dpsgd_masked_step`` plus an
    untouched residual pass-through.

    Returns ``(new_params, new_residuals, losses)``. ``quant`` has no
    default on purpose: ``QuantConfig()``'s own default mode is the lossy
    ``"int8"``, so an implicit fallback would silently quantize callers who
    expected the exact baseline.
    """
    if config.local_steps != 1:
        raise NotImplementedError(
            "dpsgd_masked_compressed_step supports local_steps == 1 only")
    losses, grads = _masked_node_grads(loss_fn, node_params, node_batches,
                                       live)
    if config.mix_first:
        mixed, new_res = _mix_compressed(node_params, residuals, w, live,
                                         quant)
        new_params = _sgd(mixed, grads, config.eta)
    else:
        new_params, new_res = _mix_compressed(
            _sgd(node_params, grads, config.eta), residuals, w, live, quant)
    return new_params, new_res, losses


def make_dpsgd_step(
    loss_fn: Callable[[PyTree, PyTree], jax.Array],
    config: DPSGDConfig = DPSGDConfig(),
) -> Callable[[PyTree, PyTree, jax.Array], tuple[PyTree, jax.Array]]:
    """Bind loss_fn/config once; returns jitted (params, batches, W) -> step."""
    def step(node_params, node_batches, w):
        return dpsgd_step(loss_fn, node_params, node_batches, w, config)
    return step


def make_dpsgd_masked_step(
    loss_fn: Callable[[PyTree, PyTree], jax.Array],
    config: DPSGDConfig = DPSGDConfig(),
):
    """Bind loss_fn/config once; returns one jitted
    ``(params, batches, w, live) -> (params, losses)`` — the per-round-driver
    entry to ``dpsgd_masked_step`` (crashed/churned nodes take no gradient
    step; their ``embed_w``-contract identity rows carry stale params)."""
    @jax.jit
    def step(node_params, node_batches, w, live):
        return dpsgd_masked_step(loss_fn, node_params, node_batches, w, live,
                                 config)
    return step


def make_dpsgd_compressed_step(
    loss_fn: Callable[[PyTree, PyTree], jax.Array],
    quant,
    config: DPSGDConfig = DPSGDConfig(),
):
    """Bind (loss_fn, quant, config) once; returns one jitted
    ``(params, batches, w, live, residuals) -> (params, residuals, losses)``
    — the per-round-driver entry to ``dpsgd_masked_compressed_step`` (the
    scan path calls the unjitted body inside its own jit)."""
    @jax.jit
    def step(node_params, node_batches, w, live, residuals):
        return dpsgd_masked_compressed_step(
            loss_fn, node_params, node_batches, w, live, residuals, quant,
            config)
    return step
