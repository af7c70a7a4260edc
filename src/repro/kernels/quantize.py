"""Pallas TPU kernel: block-scaled int8 pack for compressed gossip payloads.

One pass per (row-block x col-block) tile: reduce |x| over each scale block
(256 lanes), derive the per-block scale, round to int8. Used by the
compressed gossip path (core.compression / train.step) as the TPU lowering of
``_quantize_rowwise_int8`` — blocked scales rather than whole-row scales, so
each tile is self-contained in VMEM (no cross-tile reduction).

Execution mode: ``interpret=None`` (the default) auto-selects per call via
``_default_interpret`` — compiled Pallas on TPU, interpret mode elsewhere —
resolved *before* entering jit so the backend probe is never frozen into
the jit cache.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ._backend import _default_interpret

__all__ = ["quantize_int8", "dequantize_int8"]

_BLOCK = 256     # lanes per scale block (multiple of 128)
_ROWS = 8        # rows per tile
_LANES = 128     # TPU vector lanes: one padded scale row per tile
_MAX_BLOCKS = 16  # scale blocks per column tile (<= _LANES)


def _col_tile(c: int) -> int:
    """Lanes per column tile: the most whole scale blocks (at most
    ``_MAX_BLOCKS``) that divide ``c``, so the grid covers every column."""
    nb = c // _BLOCK
    return _BLOCK * max(d for d in range(1, min(nb, _MAX_BLOCKS) + 1)
                        if nb % d == 0)


def _q_kernel(x_ref, q_ref, s_ref):
    rows, cols = x_ref.shape
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, _LANES), 1)
    s_tile = jnp.ones((rows, _LANES), jnp.float32)
    for b in range(cols // _BLOCK):  # static unroll over the tile's blocks
        sl = slice(b * _BLOCK, (b + 1) * _BLOCK)
        xb = x_ref[:, sl].astype(jnp.float32)
        scale = jnp.max(jnp.abs(xb), axis=-1, keepdims=True) / 127.0
        scale = jnp.where(scale == 0, 1.0, scale)
        q_ref[:, sl] = jnp.clip(jnp.round(xb / scale), -127,
                                127).astype(jnp.int8)
        s_tile = jnp.where(lane == b, scale, s_tile)   # scale -> lane b
    s_ref[...] = s_tile


def _dq_kernel(q_ref, s_ref, o_ref):
    rows, cols = q_ref.shape
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, _LANES), 1)
    s_tile = s_ref[...]
    for b in range(cols // _BLOCK):
        sl = slice(b * _BLOCK, (b + 1) * _BLOCK)
        scale = jnp.sum(jnp.where(lane == b, s_tile, 0.0), axis=-1,
                        keepdims=True)                 # lane b -> (rows, 1)
        o_ref[:, sl] = (q_ref[:, sl].astype(jnp.float32)
                        * scale).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _quantize_int8(x: jax.Array, interpret: bool
                   ) -> tuple[jax.Array, jax.Array]:
    r, c = x.shape
    bc = _col_tile(c)
    grid = (r // _ROWS, c // bc)
    # scales leave the kernel as one padded 128-lane row per tile, so the
    # scale block (_ROWS, 128) is aligned as the TPU lowering requires
    q, s = pl.pallas_call(
        _q_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((_ROWS, bc), lambda i, j: (i, j))],
        out_specs=[
            pl.BlockSpec((_ROWS, bc), lambda i, j: (i, j)),
            pl.BlockSpec((_ROWS, _LANES), lambda i, j: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((r, c), jnp.int8),
            jax.ShapeDtypeStruct((r, grid[1] * _LANES), jnp.float32),
        ],
        interpret=interpret,
    )(x)
    s = s.reshape(r, grid[1], _LANES)[:, :, :bc // _BLOCK]
    return q, s.reshape(r, c // _BLOCK)


def quantize_int8(x: jax.Array, interpret: bool | None = None
                  ) -> tuple[jax.Array, jax.Array]:
    """x (R, C), R % 8 == 0, C % 256 == 0 -> (int8 (R, C), f32 (R, C/256)).
    ``interpret=None`` auto-selects: compiled on TPU, interpret elsewhere."""
    if interpret is None:
        interpret = _default_interpret()
    return _quantize_int8(x, bool(interpret))


@functools.partial(jax.jit, static_argnames=("dtype", "interpret"))
def _dequantize_int8(q: jax.Array, s: jax.Array, dtype,
                     interpret: bool) -> jax.Array:
    r, c = q.shape
    bc = _col_tile(c)
    grid = (r // _ROWS, c // bc)
    per_tile = bc // _BLOCK
    s = jnp.pad(s.astype(jnp.float32).reshape(r, grid[1], per_tile),
                ((0, 0), (0, 0), (0, _LANES - per_tile)))
    return pl.pallas_call(
        _dq_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((_ROWS, bc), lambda i, j: (i, j)),
            pl.BlockSpec((_ROWS, _LANES), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((_ROWS, bc), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((r, c), dtype),
        interpret=interpret,
    )(q, s.reshape(r, grid[1] * _LANES))


def dequantize_int8(q: jax.Array, s: jax.Array, dtype=jnp.float32,
                    interpret: bool | None = None) -> jax.Array:
    """Inverse of ``quantize_int8``; ``interpret=None`` auto-selects."""
    if interpret is None:
        interpret = _default_interpret()
    return _dequantize_int8(q, s, dtype, bool(interpret))
