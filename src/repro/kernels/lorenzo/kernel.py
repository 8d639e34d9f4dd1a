"""Pallas TPU kernel: fused PREQUANT + Lorenzo delta + POSTQUANT.

Tiling insight (DESIGN.md §2): cuSZ's prediction is *block-independent*
(zero padding layer at every block boundary, paper §3.1.1), so the Pallas
tile IS the cuSZ block — the BlockSpec decomposition needs no halo, and
the grid is embarrassingly parallel exactly like the paper's CUDA blocks.

One HBM->VMEM read of the f32 tile produces the int32 codes and the
sparse outlier store in a single fused pass (the paper's motivation: the
stage is memory-bound, so fusing prequant/predict/postquant maximizes
bandwidth utilization).  The deltas never leave VMEM.  Outliers are rare
(about 0.1-0.4% of a field), so the kernel compacts them itself instead
of writing every delta for an XLA `nonzero` (a bincount scatter with one
update per value, then a gather):

  * the grid runs in order, and SMEM carries the count so far;
  * a step lays its deltas out flat, 128 lanes a row (strided stores),
    so a tile is 4096 values whatever the block, and ranks its outliers
    inside each tile (a log-step prefix count over the whole step);
  * it then walks the tiles: a tile with no outlier costs one
    reduction; one with up to `_FEW` picks each by rank with a masked
    sum; one with more is compacted whole by log-step shifts, at a cost
    that does not grow with its outliers (a dense tile would otherwise
    cost one masked sum per outlier).  Either way the outliers land in
    a VMEM staging area laid out as the output's 128-lane rows;
  * the staged rows go to the `[capacity]` outputs in HBM by DMA at the
    count's row, whole rows at a time: the first row comes in holding
    the lanes earlier steps filled.  The wrapper writes the fill past
    the true count with one `where`.

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
from jax.experimental.pallas import tpu as pltpu

from .. import common

_TILE_ELEMS = 128 * 1024     # values per grid step (512 KB of int32)
_TILE_ROWS = 32              # 128-lane rows per outlier tile (4096 values)
_FEW = 8                     # outliers a tile picks one by one
_FLUSH_ROWS = 8              # 128-lane output rows per outlier DMA


def _axis_shift(x, block, ax, k):
    """`x` [T, prod(block)] shifted by `k` along block axis `ax`, zero
    where the shift crosses the block's edge."""
    stride = math.prod(block[ax + 1:])
    pos = (jax.lax.broadcasted_iota(jnp.int32, x.shape, 1) // stride) \
        % block[ax]
    return jnp.where(pos >= k, common.shift(x, 1, k * stride), 0)


def _dualquant_kernel(block, nbins, eb, nblk, cap, x_ref, codes_ref,
                      idx_hbm, val_hbm, n_ref, hit_ref,
                      d_s, r_s, c_idx, c_val, s_idx, s_val, acc, sem):
    step = pl.program_id(0)
    rows, width = x_ref.shape
    radius = nbins // 2

    @pl.when(step == 0)
    def _init():
        acc[0] = 0                          # outliers so far
        acc[1] = 0                          # tiles holding one
        r_s[...] = jnp.zeros(r_s.shape, jnp.int32)   # rows past the
        #   step's end hold no outlier

    x = x_ref[...]
    q = jnp.rint(x / (2.0 * eb)).astype(jnp.int32)            # PREQUANT
    # (same division form as the oracle: reciprocal-multiply would flip
    # rint ties and break bit-equality with ref.py)
    delta = q
    for ax in range(len(block)):                              # ℓ-delta
        delta = delta - _axis_shift(delta, block, ax, 1)
    in_cap = (delta > -radius) & (delta < radius)             # POSTQUANT
    codes_ref[...] = jnp.where(in_cap, delta + radius, 0).astype(jnp.int32)
    # the last step's rows past the final block hold no outliers
    real = step * rows + jax.lax.broadcasted_iota(
        jnp.int32, (rows, 1), 0) < nblk
    out = (real & ~in_cap).astype(jnp.int32)
    c = jnp.sum(out)
    off = acc[0]

    @pl.when(c > 0)
    def _store():
        _to_flat(delta, d_s)
        _to_flat(out, r_s)
        o = r_s[...]                        # rows left from the init or
        #   an earlier rank past the step's end read 0 or -1
        r_s[...] = jnp.where(o > 0, _rank(o), -1)
        acc[1] += _extract(pl.cdiv(rows * width // 128, _TILE_ROWS),
                           step * rows * width, off, cap, d_s, r_s,
                           (c_idx, c_val), (s_idx, s_val))

        @pl.when(off < cap)
        def _flush():
            _flush_stage(off, c, cap, s_idx, s_val, idx_hbm, val_hbm, sem)

        acc[0] = off + c

    n_ref[0] = acc[0]
    hit_ref[0] = acc[1]


def _to_flat(v, ref):
    """Store [rows, width] `v` into the first rows of `ref` in row-major
    order, 128 values a row: lane slice j of every block row goes to
    every (width / 128)-th row from row j."""
    rows, width = v.shape
    k = width // 128
    if k == 1:
        ref[pl.ds(0, rows), :] = v
        return
    for j in range(k):
        ref[pl.ds(j, rows, stride=k), :] = v[:, j * 128:(j + 1) * 128]


def _rank(o):
    """Each outlier's rank in its tile: the exclusive row-major prefix
    sum of int32 [rows, 128] `o`, restarting every `_TILE_ROWS` rows.
    Log-step shifted adds along the lanes, then down the row totals,
    over the whole step at once."""
    incl = o
    k = 1
    while k < 128:
        incl = incl + common.shift(incl, 1, k)
        k *= 2
    tot = incl[:, 127:128]
    seg = jax.lax.broadcasted_iota(jnp.int32, tot.shape, 0) % _TILE_ROWS
    run = tot
    k = 1
    while k < min(_TILE_ROWS, o.shape[0]):
        run = run + jnp.where(seg >= k, common.shift(run, 0, k), 0)
        k *= 2
    return incl - o + (run - tot)


def _extract(tiles, base, off, cap, d_s, r_s, c_s, s_s):
    """Stage this step's outliers, in ascending order, from lane
    ``off % 128`` of staging row 0 on, a tile at a time.  A tile with no
    outlier costs one reduction.  A tile with up to `_FEW` picks each by
    its rank with a masked sum (`_pick`); one with more is compacted
    whole (`_compact`), at a cost that does not grow with its outliers,
    moved on to the lane where the staged run ends and copied into the
    staging rows it covers.  Returns the tiles holding an outlier."""
    lead = off % 128
    shape = (_TILE_ROWS, 128)
    flat = (jax.lax.broadcasted_iota(jnp.int32, shape, 0) * 128
            + jax.lax.broadcasted_iota(jnp.int32, shape, 1))
    pad = jnp.zeros((8, 128), jnp.int32)

    def tile(t, carry):
        done, hits = carry
        r0 = pl.multiple_of(t * _TILE_ROWS, 8)
        rank = r_s[pl.ds(r0, _TILE_ROWS), :]
        ct = jnp.sum((rank >= 0).astype(jnp.int32))
        live = off + done < cap
        pos = lead + done
        gidx = base + r0 * 128 + flat

        @pl.when(live & (ct > 0) & (ct <= _FEW))
        def _few():
            _pick(rank, (gidx, d_s[pl.ds(r0, _TILE_ROWS), :]), pos, s_s)

        @pl.when(live & (ct > _FEW))
        def _many():
            # an outlier moves toward the tile's start by the places
            # before it that hold none (stored + 1: 0 marks no outlier)
            gap = jnp.where(rank >= 0, flat - rank + 1, 0)
            gap, (d,) = _compact(gap, [d_s[pl.ds(r0, _TILE_ROWS), :]])
            for ref, v in zip(c_s, (gidx + gap - 1, d)):
                ref[...] = _move_on(jnp.concatenate([v, pad]), pos % 128)
            _copy_rows(pos, ct, c_s, s_s)
        return done + ct, hits + jnp.where(ct > 0, 1, 0)

    return jax.lax.fori_loop(0, tiles, tile, (0, 0))[1]


def _pick(rank, vals, pos, s_s):
    """Write a tile's outliers (at most `_FEW`) at places `pos` on of
    the staging rows, each picked by its rank with a masked sum of each
    of `vals`; the places past the last get 0, which a later tile
    overwrites or which lie past the final count."""
    ra = pos // 128
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, 128), 1)
    rows = [[st[pl.ds(ra + h, 1), :] for h in (0, 1)] for st in s_s]
    for j in range(_FEW):
        sel = rank == j
        got = [_sum2(jnp.where(sel, v, 0)) for v in vals]
        p = pos + j
        for h in (0, 1):
            at = (lane == p % 128) & (p // 128 == ra + h)
            for rows_h, v in zip(rows, got):
                rows_h[h] = jnp.where(at, v, rows_h[h])
    for st, rows_h in zip(s_s, rows):
        for h in (0, 1):
            st[pl.ds(ra + h, 1), :] = rows_h[h]


def _sum2(x):
    return jnp.sum(jnp.sum(x, axis=1, keepdims=True), axis=0, keepdims=True)


def _compact(gap, vals):
    """Move every outlier of a tile toward its start by its gap: `gap`
    holds gap + 1 at an outlier and 0 elsewhere, and each of `vals`
    travels with it.  One log step per bit of the gaps, low bits first,
    all places at once: an outlier never lands on another, because the
    gaps do not fall along the tile.  Afterwards the outliers fill the
    first places in order."""
    k = 1
    while k < gap.size:
        gin = _flat_take(gap, k)
        come = (gin > 0) & (((gin - 1) & k) != 0)
        stay = (gap > 0) & (((gap - 1) & k) == 0)
        vals = [jnp.where(come, _flat_take(v, k), v) for v in vals]
        gap = jnp.where(come, gin, jnp.where(stay, gap, 0))
        k *= 2
    return gap, vals


def _flat_take(x, k):
    """[R, 128] `x` read in row-major order, ``x'[f] = x[f + k]`` for a
    static ``k > 0`` (0 past the end)."""
    rows, rem = divmod(k, 128)
    a = _row_take(x, rows)
    if rem == 0:
        return a
    b = _row_take(x, rows + 1)
    return jnp.concatenate([a[:, rem:], b[:, :rem]], axis=1)


def _row_take(x, n):
    """``x'[r] = x[r + n]``, 0 past either end."""
    if n == 0:
        return x
    if abs(n) >= x.shape[0]:
        return jnp.zeros_like(x)
    z = jnp.zeros((abs(n), x.shape[1]), x.dtype)
    return (jnp.concatenate([x[n:], z]) if n > 0
            else jnp.concatenate([z, x[:n]]))


def _move_on(x, q):
    """[R, 128] `x` moved `q` places toward its end in row-major order
    (traced, ``0 <= q < 128``): a lane rotation, each row's wrapped lanes
    taken from the row before."""
    y = pltpu.roll(x, q, 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return jnp.where(lane >= q, y, _row_take(y, -1))


def _copy_rows(pos, ct, c_s, s_s):
    """Copy the `ct` outliers of `c_s`, which start at lane ``pos % 128``
    of row 0, into the staging rows from ``pos // 128`` on; the first row
    keeps the lanes before `pos` it already holds."""
    ra, q = pos // 128, pos % 128
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, 128), 1)
    for c, st in zip(c_s, s_s):
        st[pl.ds(ra, 1), :] = jnp.where(lane >= q, c[pl.ds(0, 1), :],
                                        st[pl.ds(ra, 1), :])

    def row(i, carry):
        for c, st in zip(c_s, s_s):
            st[pl.ds(ra + i, 1), :] = c[pl.ds(i, 1), :]
        return carry
    jax.lax.fori_loop(1, pl.cdiv(q + ct, 128), row, 0)


def _flush_stage(off, c, cap, s_idx, s_val, idx_hbm, val_hbm, sem):
    """DMA the staged rows to output rows ``off // 128`` on, then carry
    the last, partly filled row to row 0.  Every `_FLUSH_ROWS`-row chunk
    of both outputs is started before the first is waited on.  Row 0
    came in holding the lanes earlier steps filled, so every row is
    written whole; lanes past the last outlier are overwritten by a later
    step or lie past the final count.  Rows from the capacity on are not
    written."""
    row0 = off // 128
    cap_rows = pl.cdiv(cap, 128)
    used = off % 128 + c

    def each_chunk(act):
        def chunk(q, carry):
            r = row0 + q * _FLUSH_ROWS

            @pl.when(r < cap_rows)
            def _():
                for i, (src, dst) in enumerate(((s_idx, idx_hbm),
                                                (s_val, val_hbm))):
                    act(pltpu.make_async_copy(
                        src.at[pl.ds(q * _FLUSH_ROWS, _FLUSH_ROWS)],
                        dst.at[pl.ds(r, _FLUSH_ROWS)], sem.at[i]))
            return carry
        jax.lax.fori_loop(0, pl.cdiv(pl.cdiv(used, 128), _FLUSH_ROWS),
                          chunk, 0)

    each_chunk(lambda cp: cp.start())
    each_chunk(lambda cp: cp.wait())
    last = used // 128
    s_idx[pl.ds(0, 1), :] = s_idx[pl.ds(last, 1), :]
    s_val[pl.ds(0, 1), :] = s_val[pl.ds(last, 1), :]


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


def dualquant_blocks_pallas(xb: jax.Array, eb: float, nbins: int,
                            capacity: int, *, interpret: bool):
    """xb: [nb..., b...] float32 blocked input (block axes last nd).

    Returns (codes shaped like xb, out_idx [capacity], out_val
    [capacity], n_outliers, outlier_tiles): the outlier store of
    `core.dualquant.extract_outliers`, written by the same kernel that
    computes the deltas, which never leave the chip, and int32 [tiles
    holding an outlier, tiles walked]."""
    (nblk, width), block, grid, spec = _rows_and_spec(xb.shape,
                                                      xb.ndim // 2)
    rows = spec.block_shape[0]
    xf = xb.reshape(nblk, width)
    if width % 128:
        raise ValueError(f"the Pallas dual-quant kernel needs a block of a "
                         f"multiple of 128 values, got {block}")
    cap_rows = pl.cdiv(capacity, 128)
    per_row = width // 128
    tiled_rows = pl.cdiv(rows * per_row, _TILE_ROWS) * _TILE_ROWS
    last_rows = nblk - (grid[0] - 1) * rows
    tiles = ((grid[0] - 1) * pl.cdiv(rows * per_row, _TILE_ROWS)
             + pl.cdiv(last_rows * per_row, _TILE_ROWS))
    # a step's outliers from lane 127 on, and the row after the last
    stage_rows = pl.cdiv(pl.cdiv(127 + rows * width, 128) + 1,
                         _FLUSH_ROWS) * _FLUSH_ROWS
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    store = jax.ShapeDtypeStruct((cap_rows + _FLUSH_ROWS, 128), jnp.int32)
    count = jax.ShapeDtypeStruct((1,), jnp.int32)
    kern = functools.partial(_dualquant_kernel, block, nbins, eb, nblk,
                             capacity)
    codes, oidx, oval, n_out, hit = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[spec],
        out_specs=[spec, hbm, hbm, smem, smem],
        out_shape=[jax.ShapeDtypeStruct((nblk, width), jnp.int32),
                   store, store, count, count],
        scratch_shapes=[
            pltpu.VMEM((tiled_rows, 128), jnp.int32),         # deltas
            pltpu.VMEM((tiled_rows, 128), jnp.int32),         # ranks
            pltpu.VMEM((_TILE_ROWS + 8, 128), jnp.int32),     # a tile,
            pltpu.VMEM((_TILE_ROWS + 8, 128), jnp.int32),     #   compacted
            pltpu.VMEM((stage_rows, 128), jnp.int32),         # staging
            pltpu.VMEM((stage_rows, 128), jnp.int32),
            pltpu.SMEM((2,), jnp.int32),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        # steps run in order: each appends at the count the last one left
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(xf)
    n_out = n_out[0]
    with jax.named_scope("stage.outliers"):       # the fill past the count
        used = jnp.arange(capacity, dtype=jnp.int32) < n_out
        oidx = jnp.where(used, oidx.reshape(-1)[:capacity], nblk * width)
        oval = jnp.where(used, oval.reshape(-1)[:capacity], 0)
    return (codes.reshape(xb.shape), oidx, oval, n_out,
            jnp.stack([hit[0], jnp.int32(tiles)]))


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
