"""Pallas TPU kernel: fused PREQUANT + Lorenzo delta + POSTQUANT.

Tiling insight (DESIGN.md §2): cuSZ's prediction is *block-independent*
(zero padding layer at every block boundary, paper §3.1.1), so the Pallas
tile IS the cuSZ block — the BlockSpec decomposition needs no halo, and
the grid is embarrassingly parallel exactly like the paper's CUDA blocks.

One HBM->VMEM read of the f32 tile produces both int32 outputs in a single
fused pass (the paper's motivation: the stage is memory-bound, so fusing
prequant/predict/postquant maximizes bandwidth utilization).

The reverse kernel computes the in-block N-D inclusive prefix sum (the
cumsum inverse) + dequant, also one pass.  Mosaic has no `cumsum`, so the
prefix sum is a log-step (Hillis-Steele) scan of zero-filled shifted adds
along each block axis: ceil(log2 b) int32 adds per axis, exact.

Layout: each block is one LANE ROW of prod(block) values in row-major
order ([nblk, 512] for the paper's 8x8x8 blocks), so tiles are lane-dense
and the flat code stream reshapes to it for free.  Block axis j becomes
lane stride s_j = prod(block[j+1:]); a shift along it is a lane shift by
k·s_j, masked to zero where it would cross the axis' edge (the zero
padding layer).  ([nblk, 8, 8, 8] tiles would pad every 8-lane row to
128 lanes, and the relayout feeding them takes the TPU compiler minutes
on the decompress path.)  The grid walks `_TILE_ELEMS`-sized
tiles of whole blocks; a partial last tile is fine because blocks are
independent.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .. import common

_TILE_ELEMS = 128 * 1024     # values per grid step (512 KB of int32)


def _axis_shift(x, block, ax, k):
    """`x` [T, prod(block)] shifted by `k` along block axis `ax`, zero
    where the shift crosses the block's edge."""
    stride = math.prod(block[ax + 1:])
    pos = (jax.lax.broadcasted_iota(jnp.int32, x.shape, 1) // stride) \
        % block[ax]
    return jnp.where(pos >= k, common.shift(x, 1, k * stride), 0)


def _dualquant_kernel(block, nbins, eb, x_ref, codes_ref, delta_ref):
    x = x_ref[...]
    dq = jnp.rint(x / (2.0 * eb)).astype(jnp.int32)           # PREQUANT
    # (same division form as the oracle: reciprocal-multiply would flip
    # rint ties and break bit-equality with ref.py)
    delta = dq
    for ax in range(len(block)):                              # ℓ-delta
        delta = delta - _axis_shift(delta, block, ax, 1)
    radius = nbins // 2                                       # POSTQUANT
    in_cap = (delta > -radius) & (delta < radius)
    codes_ref[...] = jnp.where(in_cap, delta + radius, 0).astype(jnp.int32)
    delta_ref[...] = delta


def _reverse_kernel(block, eb, delta_ref, out_ref):
    d = delta_ref[...]
    for ax in range(len(block)):                              # cumsum inverse
        k = 1
        while k < block[ax]:
            d = d + _axis_shift(d, block, ax, k)
            k *= 2
    out_ref[...] = d.astype(jnp.float32) * (2.0 * eb)


def _rows_and_spec(xb_shape, nd):
    """(flat [nblk, prod(block)] shape, block dims, grid, BlockSpec)."""
    block = tuple(xb_shape[len(xb_shape) - nd:])
    nblk = math.prod(xb_shape[:len(xb_shape) - nd])
    width = math.prod(block)
    rows = max(8, _TILE_ELEMS // width // 8 * 8)
    rows = nblk if nblk <= rows else rows
    spec = pl.BlockSpec((rows, width), lambda i: (i, 0))
    return (nblk, width), block, (pl.cdiv(nblk, rows),), spec


def dualquant_blocks_pallas(xb: jax.Array, eb: float, nbins: int, *,
                            interpret: bool):
    """xb: [nb..., b...] float32 blocked input (block axes last nd)."""
    flat_shape, block, grid, spec = _rows_and_spec(xb.shape, xb.ndim // 2)
    xf = xb.reshape(flat_shape)
    kern = functools.partial(_dualquant_kernel, block, nbins, eb)
    codes, delta = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[spec],
        out_specs=[spec, spec],
        out_shape=[jax.ShapeDtypeStruct(flat_shape, jnp.int32),
                   jax.ShapeDtypeStruct(flat_shape, jnp.int32)],
        interpret=interpret,
    )(xf)
    return codes.reshape(xb.shape), delta.reshape(xb.shape)


def reverse_blocks_pallas(delta: jax.Array, eb: float, *,
                          interpret: bool) -> jax.Array:
    flat_shape, block, grid, spec = _rows_and_spec(delta.shape,
                                                   delta.ndim // 2)
    df = delta.reshape(flat_shape)
    kern = functools.partial(_reverse_kernel, block, eb)
    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(flat_shape, jnp.float32),
        interpret=interpret,
    )(df)
    return out.reshape(delta.shape)
