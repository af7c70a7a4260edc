"""Grouped matrix product over the experts a chip holds.

The kernels are the Pallas TPU grouped matmuls installed with JAX
(``jax.experimental.pallas.ops.tpu.megablox``: ``gmm`` and its transposed
``tgmm``), under a VJP of this module's own.

``grouped_matmul(lhs, rhs, group_sizes, group_offset)``: ``lhs`` (m, k) has
its rows sorted by group, ``group_sizes`` (G,) counts the rows of each of
the G groups in that order, and ``rhs`` (H, k, n) holds the weights of
groups ``group_offset .. group_offset + H - 1``. Row r of the (m, n) result
is ``lhs[r] @ rhs[g - group_offset]`` where row r lies in a held group g,
and 0 elsewhere. Only the row tiles of the held groups run, so the work
follows the rows routed to them and not a capacity.

Each product runs in a jitted function of its own name: ``moe_gmm``
(forward), ``moe_gmm_dlhs`` and ``moe_gmm_drhs`` (the two backward
products). The compiled instruction, and so the device trace's operation,
takes that name. Under ``vmap`` (the D-PSGD node axis) each product maps
over the batched axis with ``lax.map``: the Pallas batching rule's own loop
would rename them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox.ops import backend as _megablox

from ._backend import _default_interpret

__all__ = ["grouped_matmul", "grouped_matmul_ref", "KERNEL_NAMES"]

KERNEL_NAMES = ("moe_gmm", "moe_gmm_dlhs", "moe_gmm_drhs")

_gmm = _megablox.gmm.__wrapped__
_tgmm = _megablox.tgmm.__wrapped__


def _tile(dim: int) -> int:
    """A tile of ``dim``: the whole of it up to 1536, else the largest of
    512, 256, 128 that divides it."""
    if dim <= 1536:
        return dim
    return next((t for t in (512, 256, 128) if dim % t == 0), dim)


def _tiling(m: int, k: int, n: int) -> tuple[int, int, int]:
    return min(128, m), _tile(k), _tile(n)


def _row_tile(m: int) -> int:
    return 128 if m >= 128 else -(-m // 16) * 16


def _named(name: str, fn):
    fn.__name__ = fn.__qualname__ = name
    return jax.jit(fn)


def _per_node(fn):
    """``fn`` over arrays whose ``vmap`` maps ``fn`` over the batched axis
    in a loop of its own calls."""
    f = jax.custom_batching.custom_vmap(fn)

    @f.def_vmap
    def _rule(axis_size, in_batched, *args):
        args = [a if b else jnp.broadcast_to(a, (axis_size,) + a.shape)
                for a, b in zip(args, in_batched)]
        return jax.lax.map(lambda xs: f(*xs), args), True

    return f


@functools.lru_cache(maxsize=None)
def _product(interpret: bool, held: int):
    """The product and its VJP for ``held`` groups of weights."""

    def moe_gmm(lhs, rhs, sizes, offset):
        m, k = lhs.shape
        return _gmm(lhs, rhs, sizes, lhs.dtype, _tiling(m, k, rhs.shape[2]),
                    offset, interpret=interpret)

    def moe_gmm_dlhs(g, rhs, sizes, offset):
        m, n = g.shape
        return _gmm(g, rhs, sizes, g.dtype, _tiling(m, n, rhs.shape[1]),
                    offset, transpose_rhs=True, interpret=interpret)

    def moe_gmm_drhs(lhs, g, sizes, offset):
        m, k = lhs.shape
        return _tgmm(lhs.T, g, sizes, g.dtype, _tiling(m, k, g.shape[1]),
                     offset, held, interpret=interpret)

    fwd, dlhs, drhs = (_per_node(_named(f.__name__, f))
                       for f in (moe_gmm, moe_gmm_dlhs, moe_gmm_drhs))

    @jax.custom_vjp
    def product(lhs, rhs, sizes, offset):
        return fwd(lhs, rhs, sizes, offset)

    def product_fwd(lhs, rhs, sizes, offset):
        return fwd(lhs, rhs, sizes, offset), (lhs, rhs, sizes, offset)

    def product_bwd(res, g):
        lhs, rhs, sizes, offset = res
        return (dlhs(g, rhs, sizes, offset), drhs(lhs, g, sizes, offset),
                None, None)

    product.defvjp(product_fwd, product_bwd)
    return product


def grouped_matmul(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array,
                   group_offset: jax.Array | int = 0,
                   interpret: bool | None = None) -> jax.Array:
    """(m, k) rows sorted by group x (H, k, n) held groups' weights -> (m,
    n), 0 in the rows of groups not held. ``lhs`` and ``rhs`` share a
    type, which the result keeps. ``interpret=None`` runs the compiled
    kernel on a TPU and interprets it elsewhere."""
    if interpret is None:
        interpret = _default_interpret()
    m = lhs.shape[0]
    pad = (-m) % _row_tile(m)
    if pad:
        lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
    offset = jnp.asarray(group_offset, jnp.int32)
    out = _product(bool(interpret), rhs.shape[0])(
        lhs, rhs, group_sizes.astype(jnp.int32), offset)
    return out[:m]


def grouped_matmul_ref(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array,
                       group_offset: int = 0) -> jax.Array:
    """The same product as a plain einsum over every row's group."""
    m = lhs.shape[0]
    group = jnp.repeat(jnp.arange(group_sizes.shape[0]), group_sizes,
                       total_repeat_length=m)
    local = group - group_offset
    held = (local >= 0) & (local < rhs.shape[0])
    w = rhs[jnp.clip(local, 0, rhs.shape[0] - 1)]
    out = jnp.einsum("mk,mkn->mn", lhs, w)
    return jnp.where(held[:, None], out, 0).astype(lhs.dtype)
