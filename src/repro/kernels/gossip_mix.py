"""Pallas TPU kernels: fused gossip mixing  out = sum_k w_k * buf_k.

The D-PSGD mixing step (Algorithm 1 step 4 / Eq. 5 row) reads the local
parameter shard plus ``degree`` received neighbor shards and writes their
weighted sum. Done naively (one jnp op per neighbor) every buffer makes a
round trip to HBM per neighbor; fused, each output tile is produced from K
stacked input tiles resident in VMEM — one HBM read per operand, one write.

Two payload layouts:

* ``gossip_mix``     — fp/bf16 buffers (K, N), fp32 accumulate.
* ``gossip_mix_q8``  — the compressed-gossip receive path: the node's own
  **exact** fp32 buffer plus K neighbor payloads as blockwise int8 lanes
  with per-block fp32 scales (``core.compression.quantize_int8`` layout,
  2048-lane blocks). Dequantization happens on the tile in VMEM — int8
  lanes never round-trip to HBM at fp32 width — and accumulation is fp32.

Tiling: buffers are viewed as (K, N); each grid step owns an (K, bn) tile
with bn = 8*128*8 lanes (VPU-aligned, fp32). K = degree+1 <= 9 is static and
unrolled. Accumulation is fp32 regardless of payload dtype. The int8 path
views its lanes as (rows, 128) tiles and hands each tile its four scales
in one padded 128-lane row per payload, so every block's last two dims are
(8, 128)-aligned or whole, as the TPU lowering requires.

Execution mode: ``interpret=None`` (the default) auto-selects per call —
compiled Pallas when the **current** ``jax.default_backend()`` is TPU,
interpret mode otherwise (CPU/GPU CI, unit tests). The decision is made
before entering jit, so attaching a TPU backend mid-process is picked up by
the next call (an earlier ``functools.cache`` froze the first answer for
the life of the process). Pass an explicit bool to override.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ._backend import _default_interpret

__all__ = ["gossip_mix", "gossip_mix_q8"]

_BN = 8 * 128 * 8   # lanes per tile (fp32 VPU tile x 8 rows)
_SB = 2048          # int8 scale-block lanes (== core.compression._BLOCK)
_LANES = 128        # TPU vector lanes


def _kernel(w_ref, b_ref, o_ref):
    k = b_ref.shape[0]
    acc = jnp.zeros(o_ref.shape, jnp.float32)
    for i in range(k):  # static unroll: K = degree + 1 is small
        acc = acc + w_ref[i] * b_ref[i, :].astype(jnp.float32)
    o_ref[...] = acc.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _gossip_mix(bufs: jax.Array, weights: jax.Array,
                interpret: bool) -> jax.Array:
    k, n = bufs.shape
    pad = (-n) % _BN
    if pad:
        bufs = jnp.pad(bufs, ((0, 0), (0, pad)))
    np_ = bufs.shape[1]
    grid = (np_ // _BN,)
    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((k,), lambda i: (0,)),          # weights: whole vector
            pl.BlockSpec((k, _BN), lambda i: (0, i)),    # K input tiles
        ],
        out_specs=pl.BlockSpec((_BN,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((np_,), bufs.dtype),
        interpret=interpret,
    )(weights.astype(jnp.float32), bufs)
    return out[:n]


def gossip_mix(bufs: jax.Array, weights: jax.Array,
               interpret: bool | None = None) -> jax.Array:
    """bufs (K, N), weights (K,) -> (N,). N padded to the tile size.
    ``interpret=None`` auto-selects compiled execution on TPU — resolved
    here, *outside* the jit cache, so the choice tracks the live backend."""
    if interpret is None:
        interpret = _default_interpret()
    return _gossip_mix(bufs, weights, bool(interpret))


def _q8_kernel(w_ref, x_ref, q_ref, s_ref, o_ref):
    """One (rows, 128) lane tile: ``s_ref`` holds this tile's scales in the
    first ``_BN // _SB`` lanes of one 128-lane row per payload."""
    k = q_ref.shape[0]
    rows = _SB // _LANES                           # rows per scale block
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, _LANES), 1)
    x = x_ref[...]
    deq = [q_ref[i].astype(jnp.float32) for i in range(k)]
    s = s_ref[0]                                   # (K, 128)
    for b in range(_BN // _SB):  # static unroll, dequantize on the VMEM tile
        sl = slice(b * rows, (b + 1) * rows)
        acc = w_ref[0] * x[sl]                     # exact self term
        for i in range(k):
            scale = jnp.sum(jnp.where(lane == b, s[i:i + 1], 0.0), axis=1,
                            keepdims=True)         # (1, 1) lane pick
            acc = acc + w_ref[i + 1] * (deq[i][sl] * scale)
        o_ref[sl, :] = acc


@functools.partial(jax.jit, static_argnames=("interpret",))
def _gossip_mix_q8(self_buf, q_bufs, scales, weights, interpret):
    n = self_buf.shape[0]
    k, np8 = q_bufs.shape
    np_ = n + (-n) % _BN                     # tile-aligned lane count
    tiles, per_tile = np_ // _BN, _BN // _SB
    # every operand becomes (rows, 128) lanes; int8 payloads arrive as whole
    # 2048-lane blocks, padded (with one scale per padded block) out to the
    # tile width — zero lanes contribute exact zeros whatever the pad scale
    x = jnp.pad(self_buf.astype(jnp.float32), (0, np_ - n))
    q = jnp.pad(q_bufs, ((0, 0), (0, np_ - np8)))
    s = jnp.pad(scales.astype(jnp.float32),
                ((0, 0), (0, tiles * per_tile - scales.shape[1])),
                constant_values=1.0)
    # scales per tile as one padded 128-lane row per payload: the block's
    # last two dims (K, 128) span K and are lane-aligned, as the TPU
    # lowering requires
    s = jnp.pad(s.reshape(k, tiles, per_tile).transpose(1, 0, 2),
                ((0, 0), (0, 0), (0, _LANES - per_tile)),
                constant_values=1.0)
    tile_rows = _BN // _LANES
    out = pl.pallas_call(
        _q8_kernel,
        grid=(tiles,),
        in_specs=[
            pl.BlockSpec((k + 1,), lambda i: (0,)),        # self + K weights
            pl.BlockSpec((tile_rows, _LANES), lambda i: (i, 0)),       # self
            pl.BlockSpec((k, tile_rows, _LANES), lambda i: (0, i, 0)),  # q8
            pl.BlockSpec((1, k, _LANES), lambda i: (i, 0, 0)),       # scales
        ],
        out_specs=pl.BlockSpec((tile_rows, _LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((np_ // _LANES, _LANES), jnp.float32),
        interpret=interpret,
    )(weights.astype(jnp.float32), x.reshape(-1, _LANES),
      q.reshape(k, -1, _LANES), s)
    return out.reshape(-1)[:n]


def gossip_mix_q8(self_buf: jax.Array, q_bufs: jax.Array, scales: jax.Array,
                  weights: jax.Array,
                  interpret: bool | None = None) -> jax.Array:
    """Fused compressed-gossip receive:

        out = weights[0] * self_buf + sum_k weights[k+1] * deq(q_bufs[k])

    ``self_buf`` (N,) fp — the node's own exact value; ``q_bufs`` (K, Np)
    int8 with Np = N padded to whole 2048-lane blocks and ``scales``
    (K, Np/2048) fp32 — exactly what ``core.compression.quantize_int8``
    emits per neighbor; ``weights`` (K+1,) with the self weight first.
    Returns fp32 (N,). Parity against ``ref.gossip_mix_q8_ref`` is pinned
    in tests/test_kernels.py.
    """
    if interpret is None:
        interpret = _default_interpret()
    n = self_buf.shape[0]
    k, np8 = q_bufs.shape
    if weights.shape != (k + 1,):
        raise ValueError(
            f"weights must be ({k + 1},) — self weight + one per payload — "
            f"got {weights.shape}")
    if np8 % _SB or scales.shape[1] != np8 // _SB:
        raise ValueError(
            f"int8 payload must be whole {_SB}-lane blocks with one scale "
            f"each; got {np8} lanes and {scales.shape[1]} scales")
    if not np8 >= n:
        raise ValueError(
            f"padded payload ({np8} lanes) shorter than self buffer ({n})")
    return _gossip_mix_q8(self_buf, q_bufs, scales, weights, bool(interpret))
