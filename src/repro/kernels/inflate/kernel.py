"""Pallas TPU kernel: gap-array parallel Huffman inflate (phase 2 of
Rivera et al., arXiv 2201.09118).

The sequential decoder walks `chunk_size` symbols per chunk because every
codeword boundary depends on the previous one — the RAW hazard cuSZ §V
concedes.  The gap array breaks the chain: deflate records the bit offset
at every `sub_size`-symbol boundary, so each subchunk decodes
independently from its recorded start and the sequential walk shrinks to
`sub_size` steps with one cursor per subchunk running in lockstep.

Each grid step takes whole chunks (`common.chunks_per_tile`): their
words as rows of 128 lanes and one cursor row per subchunk.  Per step of
the walk, for every cursor:

  1. fetch the word under the cursor and the word after it (0 past the
     end of its chunk): a ONE-HOT CONTRACTION over the step's word rows
     picks the cursor's row of word bytes (int8 x int8 -> int32, exact,
     see `kernels.common`), then a lane mask picks the word in the row;
  2. splice the 32-bit left-aligned peek window;
  3. canonical length-interval compare: left-aligned code intervals tile
     [0, 2^32) contiguously in length order, so
     `len = 1 + sum_l lmask[l] * [peek >= thresh[l]]` — no LUT in VMEM
     (the compare serves every max-length regime);
  4. look up the canonical symbol with lane masks over the tables.

Cursor c writes its i-th symbol to column i of row c, so the output rows
in row-major order are chunk order.  Bit-exact with
`core.huffman.inflate_gap` (the vmapped jax reference of the same shape)
and with the sequential decoder.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import huffman as hf

from .. import common

_TB = 128                    # padded length-table lanes (MAXLEN + 1 = 33)
_SIGN = -(2 ** 31)           # xor turns unsigned order into signed order


def _pick(mask, table):
    """Per-row sum of `table` where `mask` (one hit per row) -> [rows, 1]."""
    return jnp.sum(jnp.where(mask, table, 0), axis=1, keepdims=True)


def _inflate_kernel(chunk, sub, words_ref, cursor_ref, thresh_ref, lmask_ref,
                    fcode_ref, sidx_ref, scanon_ref, out_ref):
    rows = words_ref.shape[0]
    rc, n_sub = chunk // 128, chunk // sub
    ncur = rows * 128 // sub
    # this step's cursor row: start bit offsets, then symbol counts
    cursors = cursor_ref[pl.ds(pl.program_id(0) % 8, 1), :]
    start = cursors[:, :ncur].reshape(ncur, 1)
    count = cursors[:, ncur:].reshape(ncur, 1)
    x = words_ref[...]                                        # [R, 128]
    row = jax.lax.broadcasted_iota(jnp.int32, (rows, 128), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, 128), 1)
    below = jnp.concatenate([x[1:], jnp.zeros((1, 128), x.dtype)], axis=0)
    nxt = jnp.concatenate([x[:, 1:], below[:, :1]], axis=1)   # next word
    nxt = jnp.where((row % rc == rc - 1) & (lane == 127), 0, nxt)
    table = jnp.concatenate(common.to_bytes(x) + common.to_bytes(nxt),
                            axis=1)                           # [R, 1024]
    cur_row0 = (jax.lax.broadcasted_iota(jnp.int32, (ncur, 1), 0)
                // n_sub) * rc                                # chunk's row 0
    row_iota = jax.lax.broadcasted_iota(jnp.int32, (ncur, rows), 1)
    lane_iota = jax.lax.broadcasted_iota(jnp.int32, (ncur, 128), 1)
    tb_iota = jax.lax.broadcasted_iota(jnp.int32, (ncur, _TB), 1)
    sym_iota = jax.lax.broadcasted_iota(
        jnp.int32, (ncur, scanon_ref.shape[1]), 1)
    out_iota = jax.lax.broadcasted_iota(jnp.int32, (ncur, sub), 1)
    thresh = thresh_ref[...]
    live = lmask_ref[...] > 0
    fcode, sidx, scanon = fcode_ref[...], sidx_ref[...], scanon_ref[...]
    k = scanon_ref.shape[1]

    def step(i, carry):
        bitpos, out = carry
        wi = bitpos >> 5
        bo = bitpos & 31
        oh = (row_iota == cur_row0 + (wi >> 7)) & (wi < chunk)  # [ncur, R]
        got = common.dot_i8(oh, table)                        # [ncur, 1024]
        at = lane_iota == (wi & 127)
        byte = [_pick(at, got[:, 128 * j:128 * (j + 1)]) for j in range(8)]
        cur = common.from_bytes(byte[:4])
        nxt_w = common.from_bytes(byte[4:])
        peek = (cur << bo) | jnp.where(
            bo > 0, jax.lax.shift_right_logical(nxt_w, 32 - bo), 0)
        hit = ((peek ^ _SIGN) >= thresh) & live               # [ncur, TB]
        ln = 1 + jnp.sum(hit.astype(jnp.int32), axis=1, keepdims=True)
        lnc = jnp.clip(ln, 1, hf.MAXLEN)
        code = jax.lax.shift_right_logical(peek, 32 - lnc)
        at_len = tb_iota == lnc
        idx = _pick(at_len, sidx) + code - _pick(at_len, fcode)
        sym = _pick(sym_iota == jnp.clip(idx, 0, k - 1), scanon)
        ok = i < count
        out = jnp.where(out_iota == i, jnp.where(ok, sym, 0), out)
        return bitpos + jnp.where(ok, ln, 0), out

    _, out = jax.lax.fori_loop(
        0, sub, step, (start, jnp.zeros((ncur, sub), jnp.int32)))
    out_ref[...] = out


def _row(x, n, dtype):
    x = jnp.asarray(x).astype(dtype)
    return jnp.pad(x, (0, n - x.shape[0]))[None, :]


def inflate_pallas(words: jax.Array, n_valid: jax.Array, gap_bits: jax.Array,
                   table: hf.DecodeTable, sub_size: int, *,
                   interpret: bool) -> jax.Array:
    """words: [nc, W] uint32, n_valid: [nc], gap_bits: [nc, W//sub_size].
    Returns codes [nc, W] int32 (chunk order)."""
    nc, W = words.shape
    n_sub = gap_bits.shape[1]
    if n_sub * sub_size != W:
        raise ValueError(f"gap array [{nc}, {n_sub}] does not tile chunks "
                         f"of {W} symbols with sub_size={sub_size}")
    g = common.chunks_per_tile(W, sub_size)
    ncp = -(-nc // g) * g
    steps = ncp // g
    rows, ncur = g * (W // 128), g * n_sub
    words = jnp.pad(jax.lax.bitcast_convert_type(words, jnp.int32),
                    ((0, ncp - nc), (0, 0))).reshape(-1, 128)
    base = jnp.arange(n_sub, dtype=jnp.int32) * sub_size
    count = jnp.clip(n_valid.astype(jnp.int32)[:, None] - base, 0, sub_size)
    # one row of cursor state per step, 8 steps to a block (the (8, 128)
    # rule); padded chunks decode nothing (count 0)
    cursors = jnp.concatenate(
        [jnp.pad(a, ((0, ncp - nc), (0, 0))).reshape(steps, ncur)
         for a in (gap_bits.astype(jnp.int32), count)], axis=1)
    cursors = jnp.pad(cursors, ((0, -steps % 8), (0, 0)))
    cb = table.cb
    k = cb.sym_canon.shape[0]
    thresh = jax.lax.bitcast_convert_type(
        table.thresh.astype(jnp.uint32), jnp.int32) ^ _SIGN
    fcode = jax.lax.bitcast_convert_type(cb.first_code.astype(jnp.uint32),
                                         jnp.int32)
    tables = [_row(thresh, _TB, jnp.int32), _row(table.lmask, _TB, jnp.int32),
              _row(fcode, _TB, jnp.int32), _row(cb.start_idx, _TB, jnp.int32),
              _row(cb.sym_canon, -(-k // 128) * 128, jnp.int32)]
    full = [pl.BlockSpec(t.shape, lambda i: (0, 0)) for t in tables]
    out = pl.pallas_call(
        functools.partial(_inflate_kernel, W, sub_size),
        grid=(steps,),
        in_specs=[pl.BlockSpec((rows, 128), lambda i: (i, 0)),
                  pl.BlockSpec((8, 2 * ncur), lambda i: (i // 8, 0))] + full,
        out_specs=pl.BlockSpec((ncur, sub_size), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((ncp * n_sub, sub_size), jnp.int32),
        interpret=interpret,
    )(words, cursors, *tables)
    return out.reshape(ncp, W)[:nc]
