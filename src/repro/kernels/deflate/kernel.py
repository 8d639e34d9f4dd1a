"""Pallas TPU kernel: Huffman deflate (bitstream concatenation, cuSZ §3.2.4).

The CUDA version packs each chunk sequentially in one thread (atomic ORs).
TPU-native formulation, all vectorized.  A chunk of C symbols is laid out
as C/128 rows of 128 lanes, and each grid step takes whole chunks
(`common.chunks_per_tile`) so blocks meet the (8, 128) rule:

  1. exclusive prefix sum of bitwidths -> per-symbol bit offsets: a
     log-step scan of shifted adds along the lanes, then a segmented
     log-step scan of the row totals down the sublanes (reset at every
     chunk boundary) — Mosaic has no `cumsum`;
  2. each codeword splits into <=2 disjoint fragments (hi at word w, lo
     at word w+1);
  3. fragments land in their words through a ONE-HOT CONTRACTION that
     factors the word index into (row, lane): an [4·rows, S] matrix of
     fragment bytes masked by target row, times the [128, S] one-hot of
     target lanes, over the S symbols of the step.  Fragments sharing a
     word have disjoint bits, so the byte sums are ORs and the int8 x
     int8 -> int32 product is exact (`kernels.common`).

Alongside the packed words the kernel samples the exclusive prefix sums
at every `sub_size`-th symbol, emitting the gap arrays (bit offset +
valid-symbol offset per subchunk boundary) that the gap-array inflate
kernel decodes from in parallel — the phase-1 half of Rivera et al.
(arXiv 2201.09118), essentially free at encode time.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .. import common


def _chunk_prefix(v, seg_row, rc):
    """Exclusive prefix sum of [R, 128] int32 `v` in row-major order,
    restarting every `rc` rows.  Returns (exclusive prefix, inclusive
    prefix at the end of each row [R, 1])."""
    incl = v
    k = 1
    while k < 128:
        incl = incl + common.shift(incl, 1, k)
        k *= 2
    tot = incl[:, 127:128]
    run = tot
    k = 1
    while k < rc:
        run = run + jnp.where(seg_row >= k, common.shift(run, 0, k), 0)
        k *= 2
    return incl + (run - tot) - v, run


def _samples(x, sub):
    """Row-major samples of `x` [R, 128] at every `gcd(sub, 128)`-th lane
    (each row's lanes that can start a subchunk), as one lane row
    [1, spr·R] ordered (lane sample j, row r)."""
    step = math.gcd(sub, 128)
    return jnp.concatenate([x[:, j:j + 1].reshape(1, -1)
                            for j in range(0, 128, step)], axis=1)


def _place(frag, trow, tlane, rows):
    """Byte accumulators [4·rows, 128] of `frag` [1, S] summed into word
    (trow, tlane)."""
    s = frag.shape[1]
    hit = jax.lax.broadcasted_iota(jnp.int32, (rows, s), 0) == trow
    a = jnp.concatenate([jnp.where(hit, b, 0)
                         for b in common.to_bytes(frag)], axis=0)
    oh = jax.lax.broadcasted_iota(jnp.int32, (128, s), 0) == tlane
    return common.dot_i8(a, oh, transpose_b=True)


def _deflate_kernel(chunk, sub, cw_ref, bw_ref, words_ref, stats_ref):
    rows = cw_ref.shape[0]
    rc = chunk // 128
    cw = cw_ref[...]                                         # bit patterns
    bw = bw_ref[...]
    row = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
    seg_row = row % rc                                       # row in chunk
    offs, bits = _chunk_prefix(bw, seg_row, rc)
    valid = bw > 0
    vcnt, _ = _chunk_prefix(valid.astype(jnp.int32), seg_row, rc)
    # one lane row per step: row-end bit totals, then the gap samples
    stats_ref[pl.ds(pl.program_id(0) % 8, 1), :] = jnp.concatenate(
        [bits.reshape(1, -1), _samples(offs, sub), _samples(vcnt, sub)],
        axis=1)

    w = offs >> 5
    b = offs & 31
    sh = 32 - b - bw
    hi = jnp.where(sh >= 0, cw << jnp.clip(sh, 0, 31),
                   jax.lax.shift_right_logical(cw, jnp.clip(-sh, 0, 31)))
    lo = jnp.where(sh < 0, cw << jnp.clip(32 + sh, 0, 31), 0)
    hi = jnp.where(valid, hi, 0)
    lo = jnp.where(valid, lo, 0)
    tw = (row - seg_row) * 128 + w           # word index within the step
    s = rows * 128
    flat = lambda v: v.reshape(1, s)         # noqa: E731
    acc = (_place(flat(hi), flat(tw >> 7), flat(tw & 127), rows)
           + _place(flat(lo), flat((tw + 1) >> 7), flat((tw + 1) & 127),
                    rows))
    words_ref[...] = common.from_bytes(
        [acc[k * rows:(k + 1) * rows] for k in range(4)])


def deflate_pallas(cw: jax.Array, bw: jax.Array, chunk_size: int = 512,
                   sub_size: int = 128, *, interpret: bool):
    n = cw.shape[0]
    rc = chunk_size // 128
    g = common.chunks_per_tile(chunk_size)
    nc = -(-n // chunk_size)
    ncp = -(-nc // g) * g
    pad = ncp * chunk_size - n
    cwp = jnp.pad(jax.lax.bitcast_convert_type(cw.astype(jnp.uint32),
                                               jnp.int32), (0, pad))
    bwp = jnp.pad(bw.astype(jnp.int32), (0, pad))
    rows, spr = g * rc, 128 // math.gcd(sub_size, 128)
    steps = ncp // g
    spec = pl.BlockSpec((rows, 128), lambda i: (i, 0))
    # per-step stats rows, 8 steps to a block (the (8, 128) rule)
    stats_w = (1 + 2 * spr) * rows
    words, stats = pl.pallas_call(
        functools.partial(_deflate_kernel, chunk_size, sub_size),
        grid=(steps,),
        in_specs=[spec, spec],
        out_specs=[spec, pl.BlockSpec((8, stats_w), lambda i: (i // 8, 0))],
        out_shape=[jax.ShapeDtypeStruct((ncp * rc, 128), jnp.int32),
                   jax.ShapeDtypeStruct((-(-steps // 8) * 8, stats_w),
                                        jnp.int32)],
        interpret=interpret,
    )(cwp.reshape(-1, 128), bwp.reshape(-1, 128))
    words = jax.lax.bitcast_convert_type(words, jnp.uint32)
    stats = stats[:steps]
    bits = stats[:, :rows].reshape(ncp, rc)[:nc, -1]
    # every stride-th sample starts a subchunk (every stride-th row's
    # first lane, for a sub_size that is a multiple of 128)
    stride = sub_size // math.gcd(sub_size, 128)

    def gaps(a):                          # [steps, spr·R] -> [nc, n_sub]
        a = a.reshape(steps, spr, rows).transpose(0, 2, 1)
        return a.reshape(ncp, rc * spr)[:nc, ::stride]

    return (words.reshape(ncp, chunk_size)[:nc], bits,
            gaps(stats[:, rows:(1 + spr) * rows]),
            gaps(stats[:, (1 + spr) * rows:]))
