"""Pallas TPU kernel: gap-array parallel Huffman inflate (phase 2 of
Rivera et al., arXiv 2201.09118).

The sequential decoder walks `chunk_size` symbols per chunk because every
codeword boundary depends on the previous one — the RAW hazard cuSZ §V
concedes.  The gap array breaks the chain: deflate records the bit offset
at every `sub_size`-symbol boundary, so each subchunk decodes
independently from its recorded start and the sequential walk shrinks to
`sub_size` steps with one cursor per subchunk running in lockstep.

**Width.**  A grid step walks `8 x 128` cursors (fewer for small inputs
or long subchunks, `tile`, within `_VMEM_BUDGET`; a subchunk of 4096
symbols or more does not fit one step, `fits`): the cursors lie one per lane, so every per-cursor value of the
walk (bit position, the two words under the cursor, length, index) is
one vreg and each op of a walk step serves up to 1024 symbols.  The
chunks of a step are whole, their words rows of 128 lanes.

**Gather.**  The walk runs in phases of up to 128 steps (the last one
shorter where `sub_size` > 128 is no multiple of 128).  Before each
phase each cursor's WORD WINDOW is gathered once: the two
word rows from the one holding the cursor, 0 past the end of its chunk
(128 symbols of at most 32 bits read at most 129 words from the
cursor's own, so they never leave those 256).  The gather is block
diagonal: the 128 cursors of a lane row contract, as int8 byte planes
on the MXU (`kernels.common`), against the rows of their own chunks
only, so its cost per cursor does not grow with the tile.  The windows
are stored window-position-major, `[256, rows, 128]`, so in the walk a
cursor's word is a select tree over 256 vregs: no cross-lane op and no
MXU push on the carried chain.  The cursor keeps
the word under it and the next one, and fetches one word when it
crosses a word boundary.

**Walk step.**  Splice the 32-bit left-aligned peek; canonical
length-interval compare against scalar thresholds: left-aligned code
intervals tile [0, 2^32) contiguously in length order, so
`len = 1 + sum_l lmask[l] * [peek >= thresh[l]]` (the compare serves
every max-length regime) and, the hits being a prefix in `l`, the
canonical index offset `start_idx[len] - first_code[len]` follows from
the last hit.  Only the bit position and the two words are carried; the
step records the CANONICAL INDEX of its symbol.

**Symbols.**  After each phase each lane row's indices map to symbols
(an in-vreg lane gather per 128 entries of `sym_canon`, selected by
the index's high bits), and a transpose puts cursor c's symbols in row
c, so the output rows in row-major order are chunk order.  Bit-exact with
`core.huffman.inflate_gap` and with the sequential decoder.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import huffman as hf

from .. import common

_SIGN = -(2 ** 31)           # xor turns unsigned order into signed order
_LANES = 128                 # cursors in one lane row
_MAX_ROWS = 8                # lane rows a step walks: one vreg per value
_PHASE = 128                 # walk steps one gathered window serves
_WINDOW = 2 * _LANES         # words a gathered window holds (two rows)
# VMEM one grid step may hold (double-buffered blocks, scratch, the
# gather's and the symbol map's temporaries): half of the v5e's 16 MiB
# scoped default
_VMEM_BUDGET = 8 << 20


class Tile(NamedTuple):
    chunks: int              # chunks per grid step
    rows: int                # lane rows of 128 cursors per grid step
    steps: int               # grid steps


def _span(s: int, chunk: int, sub: int, rows: int) -> tuple:
    """(a, b, slab): the word rows [a, b) of a step's block that hold the
    chunks of lane row `s`'s cursors, widened to whole 8-row tiles, and
    the rows the gather contracts at a time (128, or all of them)."""
    rc, n_sub = chunk // 128, chunk // sub
    q_lo = s * _LANES // n_sub
    q_hi = (s * _LANES + _LANES - 1) // n_sub
    a, b = q_lo * rc // 8 * 8, min(rows, -(-(q_hi + 1) * rc // 8) * 8)
    return a, b, _LANES if (b - a) % _LANES == 0 else b - a


def _vmem_bytes(chunk: int, sub: int, g: int, s: int) -> int:
    rows = g * (chunk // 128)
    slab = max(_span(r, chunk, sub, rows)[2] for r in range(s))
    blocks = 2 * 4 * 128 * (rows + s * sub)          # words in, codes out
    # windows, one phase's indices
    scratch = 4 * 128 * s * (_WINDOW + min(sub, _PHASE))
    # transposed words and their byte planes, one-hot, products
    gather = 4 * 128 * slab * 5 + slab * _WINDOW + 2 * 4 * 512 * _WINDOW
    # the symbol map of one block: indices, their two halves, a table
    # row's pick, the symbols and their transpose
    symbols = 6 * 4 * 128 * min(sub, _PHASE)
    return blocks + scratch + gather + symbols


def _base(chunk: int, sub: int) -> tuple:
    """(chunks, lane rows) of the smallest step: the fewest chunks that
    fill whole lane rows of cursors and whole 8-row tiles of words."""
    if chunk % 128:
        raise ValueError(f"the Pallas Huffman kernels need chunk_size a "
                         f"multiple of 128, got {chunk}")
    rc, n_sub = chunk // 128, chunk // sub
    g0 = math.lcm(_LANES // math.gcd(n_sub, _LANES), 8 // math.gcd(rc, 8))
    return g0, g0 * n_sub // _LANES


def fits(chunk: int, sub: int) -> bool:
    """Whether the smallest step over chunks of `chunk` symbols in
    subchunks of `sub` keeps to `_VMEM_BUDGET` (not so for a subchunk of
    4096 symbols or more: 128 cursors' codes and words fill it)."""
    return _vmem_bytes(chunk, sub, *_base(chunk, sub)) <= _VMEM_BUDGET


def tile(nc: int, chunk: int, sub: int) -> Tile:
    """The grid of a call over `nc` chunks: `_base`'s chunks times the
    most that keeps to `_MAX_ROWS` lane rows, to the chunks there are and
    to `_VMEM_BUDGET`.  Raises for a shape whose smallest step does not
    fit (`fits`)."""
    g0, s0 = _base(chunk, sub)
    if not fits(chunk, sub):
        raise ValueError(
            f"inflate of chunks of {chunk} symbols in subchunks of {sub}: "
            f"one grid step of {g0} chunks needs "
            f"{_vmem_bytes(chunk, sub, g0, s0)} bytes of VMEM, over the "
            f"{_VMEM_BUDGET}-byte budget")
    m = max(1, min(_MAX_ROWS // s0, -(-nc // g0)))
    while m > 1 and _vmem_bytes(chunk, sub, m * g0, m * s0) > _VMEM_BUDGET:
        m -= 1
    g = m * g0
    return Tile(g, m * s0, -(-nc // g))


def walk_steps(nc: int, chunk: int, sub: int) -> int:
    """Serial walk steps of one call: grid steps x `sub_size`."""
    return tile(nc, chunk, sub).steps * sub


def _select(entries, j):
    """entries[j] per lane: a select tree on the bits of `j` over the
    leading axis of `entries` (a power of two long)."""
    bit = 0
    while entries.shape[0] > 1:
        pair = entries.reshape(entries.shape[0] // 2, 2, *entries.shape[1:])
        entries = jnp.where(((j >> bit) & 1) == 1, pair[:, 1], pair[:, 0])
        bit += 1
    return entries[0]


def _inflate_kernel(chunk, sub, max_len, k, words_ref, cur_ref, thresh_ref,
                    lmask_ref, off_ref, sym_ref, out_ref, win_ref, idx_ref):
    n_rows = cur_ref.shape[1]
    rows = words_ref.shape[0]
    rc, n_sub = chunk // 128, chunk // sub
    p_len = min(sub, _PHASE)
    count = cur_ref[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, _LANES), 1)

    def gather(bitpos):
        """Each cursor's window, word rows r0 and r0 + 1 of its chunk
        (r0 the row under the cursor), into `win_ref`; returns the word
        index of the window's start."""
        r0 = bitpos >> 12                      # (bitpos >> 5) // 128
        for s in range(n_rows):
            a, b, slab = _span(s, chunk, sub, rows)
            r0s = r0[s:s + 1]
            row = (s * _LANES + lane) // n_sub * rc + r0s - a  # [1, 128]

            def part(t, acc, a=a, slab=slab, row=row, r0s=r0s):
                # one hit per output column across all slabs
                at = pl.multiple_of(a + t * slab, 8)
                xt = words_ref[pl.ds(at, slab), :].T        # [128, slab]
                xb = jnp.concatenate(common.to_bytes(xt), axis=0)
                m = jax.lax.broadcasted_iota(jnp.int32, (slab, _LANES),
                                             0) + t * slab
                oh = jnp.concatenate([(m == row + d) & (r0s + d < rc)
                                      for d in range(2)], axis=1)
                return acc + common.dot_i8(xb, oh)          # [512, 256]

            got = jax.lax.fori_loop(0, (b - a) // slab, part, jnp.zeros(
                (4 * _LANES, _WINDOW), jnp.int32))
            w = common.from_bytes(
                [got[_LANES * p:_LANES * (p + 1)] for p in range(4)])
            win_ref[:, s, :] = jnp.concatenate(
                [w[:, :_LANES], w[:, _LANES:]], axis=0)      # [256, 128]
        return r0 << 7

    def fetch(j):
        return _select(win_ref[...], j)

    def phase(at0, n, bitpos):
        """Walk `n` steps (at most `_PHASE`) from step `at0`."""
        base = gather(bitpos)
        wi = bitpos >> 5
        first = (bitpos, fetch(wi - base), fetch(wi + 1 - base))

        def step(i, carry):
            bitpos, cur, nxt = carry
            bo = bitpos & 31
            peek = (cur << bo) | jnp.where(
                bo > 0, jax.lax.shift_right_logical(nxt, 32 - bo), 0)
            ps = peek ^ _SIGN
            ln = jnp.ones_like(bitpos)
            off = jnp.full_like(bitpos, off_ref[1])
            for lv in range(1, max_len):
                hit = (ps >= thresh_ref[lv]) & (lmask_ref[lv] > 0)
                ln = ln + hit.astype(jnp.int32)
                off = jnp.where(hit, off_ref[lv + 1], off)
            code = jax.lax.shift_right_logical(peek, 32 - ln)
            ok = at0 + i < count
            idx_ref[i] = jnp.where(
                ok, jnp.clip(off + code, 0, k - 1), -1)
            nbp = bitpos + jnp.where(ok, ln, 0)
            adv = (nbp >> 5) != (bitpos >> 5)
            fresh = fetch((nbp >> 5) + 1 - base)
            return nbp, jnp.where(adv, nxt, cur), jnp.where(adv, fresh, nxt)

        return jax.lax.fori_loop(0, n, step, first)[0]

    table = sym_ref[...]                                       # [nt, 128]

    def emit(at, n):
        """The symbols of the `n` steps just walked into the codes'
        columns [at, at + n)."""
        for s in range(n_rows):
            v = idx_ref[:n, s, :]                            # [n, 128]
            lo, hi = v & (_LANES - 1), v >> 7
            sym = jnp.zeros_like(v)          # a padded step's -1: no row
            for h in range(table.shape[0]):
                sym = jnp.where(hi == h, jnp.take_along_axis(
                    jnp.broadcast_to(table[h:h + 1], v.shape), lo, axis=1,
                    mode="promise_in_bounds"), sym)
            out_ref[_LANES * s:_LANES * (s + 1), pl.ds(at, n)] = sym.T

    # whole phases of `p_len` steps, then a last one of the rest (a
    # `sub_size` over 128 that is no multiple of it)
    n_full, rest = sub // p_len, sub % p_len

    def full(p, bitpos):
        at = pl.multiple_of(p * p_len, p_len)
        bitpos = phase(at, p_len, bitpos)
        emit(at, p_len)
        return bitpos

    if n_full == 1:
        bitpos = full(0, cur_ref[0])
    else:
        bitpos = jax.lax.fori_loop(0, n_full, full, cur_ref[0])
    if rest:
        phase(n_full * p_len, rest, bitpos)
        emit(n_full * p_len, rest)


def _padded(x, n, dtype):
    x = jnp.asarray(x).astype(dtype)
    return jnp.pad(x, (0, n - x.shape[0]))


def inflate_pallas(words: jax.Array, n_valid: jax.Array, gap_bits: jax.Array,
                   table: hf.DecodeTable, sub_size: int, *,
                   max_len: int, interpret: bool) -> jax.Array:
    """words: [nc, W] uint32, n_valid: [nc], gap_bits: [nc, W//sub_size];
    `max_len` bounds the codebook's longest codeword (the decode's static
    bucket).  Returns codes [nc, W] int32 (chunk order)."""
    nc, W = words.shape
    n_sub = gap_bits.shape[1]
    if n_sub * sub_size != W:
        raise ValueError(f"gap array [{nc}, {n_sub}] does not tile chunks "
                         f"of {W} symbols with sub_size={sub_size}")
    t = tile(nc, W, sub_size)
    ncp = t.steps * t.chunks
    rows = t.chunks * (W // 128)
    # the last step's block runs past the words and the codes: its
    # chunks past `nc` decode nothing (count 0) and their codes are not
    # written, so neither array is padded or copied
    words = jax.lax.bitcast_convert_type(words, jnp.int32).reshape(-1, 128)
    base = jnp.arange(n_sub, dtype=jnp.int32) * sub_size
    count = jnp.clip(n_valid.astype(jnp.int32)[:, None] - base, 0, sub_size)
    # one [rows, 128] plane of cursors per step: start bits, then counts
    cursors = jnp.stack(
        [jnp.pad(a, ((0, ncp - nc), (0, 0))).reshape(t.steps, t.rows, 128)
         for a in (gap_bits.astype(jnp.int32), count)], axis=1)
    cb = table.cb
    k = cb.sym_canon.shape[0]
    ml = max(1, min(int(max_len), hf.MAXLEN))
    thresh = jax.lax.bitcast_convert_type(
        table.thresh.astype(jnp.uint32), jnp.int32) ^ _SIGN
    # start_idx[l] - first_code[l], wrapping as the reference's int32 sum
    off = cb.start_idx.astype(jnp.int32) - jax.lax.bitcast_convert_type(
        cb.first_code.astype(jnp.uint32), jnp.int32)
    syms = _padded(cb.sym_canon, -(-k // 128) * 128,
                   jnp.int32).reshape(-1, 128)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    out = pl.pallas_call(
        functools.partial(_inflate_kernel, W, sub_size, ml, k),
        grid=(t.steps,),
        in_specs=[pl.BlockSpec((rows, 128), lambda i: (i, 0)),
                  pl.BlockSpec((None, 2, t.rows, 128),
                               lambda i: (i, 0, 0, 0)),
                  smem, smem, smem,
                  pl.BlockSpec(syms.shape, lambda i: (0, 0))],
        out_specs=pl.BlockSpec((t.rows * 128, sub_size), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nc * n_sub, sub_size), jnp.int32),
        scratch_shapes=[pltpu.VMEM((_WINDOW, t.rows, 128), jnp.int32),
                        pltpu.VMEM((min(sub_size, _PHASE), t.rows, 128),
                                   jnp.int32)],
        interpret=interpret,
    )(words, cursors, *(_padded(x, 64, jnp.int32)
                        for x in (thresh, table.lmask, off)), syms)
    return out.reshape(nc, W)
