"""Pallas kernel: blocked cubic interpolation predict/reconstruct.

The level step is embarrassingly parallel over rows (each row is an
independent 1D line through the field along the working axis), so the
grid tiles the row axis and each tile computes its residuals (encode) or
odd samples (decode) from four statically-offset slices of the padded
even rows — no halo exchange, the ops layer bakes the 3-sample edge
padding into the input.  One VMEM read of the (rows, me+3) tile produces
the (rows, mo) output in a single fused pass.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_ROW_TILE = 8


def _predict_tile(pe, mo: int):
    a = pe[:, 0:mo]
    b = pe[:, 1:1 + mo]
    c = pe[:, 2:2 + mo]
    d = pe[:, 3:3 + mo]
    return (9 * (b + c) - a - d + 8) >> 4


def _residual_kernel(mo, pe_ref, odd_ref, out_ref):
    out_ref[...] = odd_ref[...] - _predict_tile(pe_ref[...], mo)


def _odd_kernel(mo, pe_ref, res_ref, out_ref):
    out_ref[...] = res_ref[...] + _predict_tile(pe_ref[...], mo)


def row_tile(rows: int) -> int:
    """Rows of one grid step for a level of `rows` rows."""
    return min(_ROW_TILE, max(1, rows))


def grid_steps(rows: int) -> int:
    """Grid steps the kernel runs over a level of `rows` rows."""
    return -(-rows // row_tile(rows))


def _run(kern_fn, pe: jax.Array, other: jax.Array,
         interpret: bool) -> jax.Array:
    rows, mo = other.shape
    mp = pe.shape[1]
    tile = row_tile(rows)
    pad = (-rows) % tile
    if pad:
        pe = jnp.concatenate([pe, jnp.zeros((pad, mp), pe.dtype)], axis=0)
        other = jnp.concatenate(
            [other, jnp.zeros((pad, mo), other.dtype)], axis=0)
    grid = (grid_steps(rows),)
    kern = functools.partial(kern_fn, mo)
    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[pl.BlockSpec((tile, mp), lambda i: (i, 0)),
                  pl.BlockSpec((tile, mo), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((tile, mo), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows + pad, mo), jnp.int32),
        interpret=interpret,
    )(pe, other)
    return out[:rows]


def residual_rows_pallas(pe: jax.Array, odd: jax.Array, *,
                         interpret: bool) -> jax.Array:
    return _run(_residual_kernel, pe, odd, interpret)


def odd_rows_pallas(pe: jax.Array, resid: jax.Array, *,
                    interpret: bool) -> jax.Array:
    return _run(_odd_kernel, pe, resid, interpret)
