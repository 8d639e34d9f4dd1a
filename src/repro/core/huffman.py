"""Customized canonical Huffman coding (cuSZ §3.2) in JAX, adapted for TPU.

Stages (paper Fig. 1, bottom):
  1. histogram of quant codes                      -> `histogram`
  2. Huffman tree + base codebook                  -> `codeword_lengths`
  3. canonization                                  -> `canonical_codebook`
  4. encode (codebook gather) + deflate (bit-pack) -> `encode`, `deflate`
  decode: reverse-codebook retrieval + inflate     -> `inflate`

TPU adaptations (DESIGN.md §2):
  * tree build: two-queue O(k) merge over frequency-sorted symbols inside a
    single `lax.fori_loop` (device-resident, like the paper's one-GPU-thread
    build which avoids PCIe round trips); a NumPy heap oracle is provided
    for testing.
  * canonization: pure vectorized math from bitlengths (first-code
    recurrence over ≤32 lengths) — replaces the cooperative-groups kernel.
  * deflate: exclusive prefix-sum of bitwidths gives each codeword its bit
    offset; every codeword splits into ≤2 32-bit word fragments combined by
    scatter-add (add ≡ OR on disjoint bits).  Chunked exactly like the
    paper so that inflate retains coarse-grained chunk parallelism.  The
    same prefix sum is sampled every `sub_size` symbols into a per-chunk
    GAP ARRAY (Rivera et al., arXiv 2201.09118): the bit offset and the
    valid-symbol offset at each subchunk boundary.
  * inflate: gap-array two-phase decode.  Phase 1 is the gap array emitted
    by deflate; phase 2 (`inflate_gap`) decodes every subchunk
    independently from its recorded bit offset — the RAW-bound sequential
    walk shrinks from `chunk_size` symbols to `sub_size` symbols, with
    nc·(chunk/sub) subchunks running in lockstep.  Decode-side tables
    (`DecodeTable`: the LUT when max codeword length ≤ LUT_BITS, else the
    canonical length-interval bounds) are built ONCE per codebook via the
    identity-keyed `decode_table` cache, not re-executed on-device per
    call.  The legacy per-chunk sequential decoders (`inflate_lut` /
    `inflate_bitscan`) remain for gap-less (format v1) containers.

This module holds the reference algorithms; the pipeline's hot stages
(histogram / encode / deflate / inflate) are *dispatched* through
`repro.kernels.*.ops`, which select between these forms and the Pallas
kernels per backend (see kernels/dispatch.py).
"""
from __future__ import annotations

import heapq
from collections import OrderedDict
from functools import partial
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.debug import spans

MAXLEN = 32          # hard cap on codeword bitlength (u32 stream words)
LUT_BITS = 16        # use table decoder when max bitlength <= this
SUBCHUNK = 128       # default gap-array subchunk (symbols per decode unit):
#   6 B of gap per boundary => ~0.05 B/symbol storage overhead, while the
#   sequential decode walk drops from chunk_size to SUBCHUNK steps
# static LUT-size buckets: every max codeword length maps to the next
# bucket so decode compiles one executable per bucket, not one per field
LUT_BUCKETS = (8, 12, 16)


def bucket_max_len(max_len: int) -> int:
    """Round a practical max codeword length up to the static bucket set.

    The decoder specializes on `max_len_static` (it sizes the LUT), so
    passing the raw per-field value compiles a distinct executable for
    every distinct max length.  Bucketing to {8, 12, 16} keeps the
    adaptive-repr win (small books get small LUTs) while capping the
    number of compiled decode variants; anything above LUT_BITS falls
    into the single bit-interval (bitscan) regime at MAXLEN."""
    for b in LUT_BUCKETS:
        if max_len <= b:
            return b
    return MAXLEN


def histogram(codes: jax.Array, nbins: int) -> jax.Array:
    """Frequency of each quant bin (paper §3.2.1).  `jnp.bincount` lowers to
    a scatter-add; the Pallas one-hot-MXU variant lives in kernels/histogram."""
    return jnp.bincount(codes.reshape(-1), length=nbins).astype(jnp.int32)


# ---------------------------------------------------------------------------
# Tree build -> codeword lengths
# ---------------------------------------------------------------------------

def codeword_lengths_host(freq: np.ndarray) -> np.ndarray:
    """NumPy heap-based Huffman (oracle).  Returns bitlength per symbol
    (0 for unused symbols)."""
    freq = np.asarray(freq)
    k = freq.shape[0]
    active = [int(s) for s in np.nonzero(freq)[0]]
    if not active:
        return np.zeros(k, np.int32)
    if len(active) == 1:
        out = np.zeros(k, np.int32)
        out[active[0]] = 1
        return out
    heap = [(int(freq[s]), i, (s,)) for i, s in enumerate(active)]
    heapq.heapify(heap)
    lengths = np.zeros(k, np.int64)
    uid = len(heap)
    while len(heap) > 1:
        f1, _, s1 = heapq.heappop(heap)
        f2, _, s2 = heapq.heappop(heap)
        for s in s1 + s2:
            lengths[s] += 1
        heapq.heappush(heap, (f1 + f2, uid, s1 + s2))
        uid += 1
    return lengths.astype(np.int32)


@partial(jax.jit, static_argnames=())
def codeword_lengths(freq: jax.Array) -> jax.Array:
    """Two-queue Huffman on device.

    With symbols sorted by frequency, merged internal nodes are produced in
    non-decreasing frequency order, so two pointer-queues replace the heap:
    O(k) merges in one fori_loop.  Returns int32 bitlengths (0 = unused).
    """
    k = freq.shape[0]
    n_active = jnp.sum(freq > 0)
    big = jnp.iinfo(jnp.int32).max // 4
    keyed = jnp.where(freq > 0, freq.astype(jnp.int32), big)
    order = jnp.argsort(keyed)                       # active symbols first
    lf = keyed[order]                                # leaf freqs, sorted

    n_int = k - 1                                    # max internal nodes
    intq = jnp.full((n_int,), big, jnp.int32)        # merged-node freqs
    ch1 = jnp.zeros((n_int,), jnp.int32)             # children (node ids:
    ch2 = jnp.zeros((n_int,), jnp.int32)             #  leaf t<k, internal k+t)

    def pick(i, j, m, intq_):
        take_leaf = (i < n_active) & ((j >= m) | (lf[jnp.clip(i, 0, k - 1)] <= intq_[jnp.clip(j, 0, n_int - 1)]))
        f = jnp.where(take_leaf, lf[jnp.clip(i, 0, k - 1)], intq_[jnp.clip(j, 0, n_int - 1)])
        node = jnp.where(take_leaf, i, k + j)
        return f, node, i + take_leaf, j + (~take_leaf)

    def body(t, st):
        i, j, intq_, ch1_, ch2_ = st
        f1, n1, i, j = pick(i, j, t, intq_)
        f2, n2, i, j = pick(i, j, t, intq_)
        intq_ = intq_.at[t].set(f1 + f2)
        ch1_ = ch1_.at[t].set(n1)
        ch2_ = ch2_.at[t].set(n2)
        return (i, j, intq_, ch1_, ch2_)

    i, j, intq, ch1, ch2 = jax.lax.fori_loop(
        0, jnp.maximum(n_active - 1, 0), body,
        (jnp.int32(0), jnp.int32(0), intq, ch1, ch2))

    # Depth pass: parents are created after children, so walk internal nodes
    # in reverse creation order propagating depth.
    depth = jnp.zeros((k + n_int,), jnp.int32)

    def dbody(s, depth_):
        t = n_active - 2 - s                          # last created -> first
        d = depth_[jnp.clip(k + t, 0, k + n_int - 1)]
        depth_ = depth_.at[ch1[jnp.clip(t, 0, n_int - 1)]].set(d + 1)
        depth_ = depth_.at[ch2[jnp.clip(t, 0, n_int - 1)]].set(d + 1)
        return depth_

    depth = jax.lax.fori_loop(0, jnp.maximum(n_active - 1, 0), dbody, depth)

    lengths_sorted = depth[:k]
    lengths = jnp.zeros((k,), jnp.int32).at[order].set(lengths_sorted)
    # single-symbol edge case: give it a 1-bit code
    lengths = jnp.where((freq > 0) & (n_active == 1), 1, lengths)
    return jnp.where(freq > 0, lengths, 0)


# ---------------------------------------------------------------------------
# Canonical codebook (paper §3.2.3)
# ---------------------------------------------------------------------------

class Codebook(NamedTuple):
    lengths: jax.Array      # [k] int32 bitlength per symbol (0 = unused)
    codes: jax.Array        # [k] uint32 canonical codeword (right-aligned)
    first_code: jax.Array   # [MAXLEN+1] uint32 canonical first code per length
    start_idx: jax.Array    # [MAXLEN+1] int32 index of first symbol of length l
    sym_canon: jax.Array    # [k] int32 symbols in canonical order
    max_len: jax.Array      # scalar int32


def canonical_codebook(lengths: jax.Array) -> Codebook:
    """Canonical codes from bitlengths alone (Schwartz-Kallick).

    Bijective, bitlength-preserving (same ratio as the base tree, paper
    §3.2.3) and decodable without the tree via (first_code, start_idx,
    sym_canon)."""
    k = lengths.shape[0]
    cnt = jnp.bincount(jnp.clip(lengths, 0, MAXLEN), length=MAXLEN + 1
                       ).at[0].set(0)                  # [MAXLEN+1]
    # first_code[l] = (first_code[l-1] + cnt[l-1]) << 1
    def fc_body(l, fc):
        return fc.at[l].set((fc[l - 1] + cnt[l - 1].astype(jnp.uint32)) << 1)
    first_code = jax.lax.fori_loop(1, MAXLEN + 1, fc_body,
                                   jnp.zeros((MAXLEN + 1,), jnp.uint32))
    start_idx = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                 jnp.cumsum(cnt)[:-1].astype(jnp.int32)])
    # canonical order: (length, symbol) ascending, unused symbols last
    key = jnp.where(lengths > 0, lengths, MAXLEN + 1) * jnp.int32(2 * k) \
        + jnp.arange(k, dtype=jnp.int32)
    sym_canon = jnp.argsort(key).astype(jnp.int32)
    pos = jnp.zeros((k,), jnp.int32).at[sym_canon].set(
        jnp.arange(k, dtype=jnp.int32))               # canonical rank of sym
    rank = pos - start_idx[jnp.clip(lengths, 0, MAXLEN)]
    codes = (first_code[jnp.clip(lengths, 0, MAXLEN)]
             + rank.astype(jnp.uint32))
    codes = jnp.where(lengths > 0, codes, 0).astype(jnp.uint32)
    return Codebook(lengths.astype(jnp.int32), codes, first_code,
                    start_idx, sym_canon, jnp.max(lengths).astype(jnp.int32))


def packed_codebook(cb: Codebook, unit_bits: int) -> jax.Array:
    """Paper Fig. 4: fixed-width unit holding bitwidth (MSB side) and the
    codeword (LSB side).  `unit_bits` in {32, 64}; the adaptive u32/u64
    selection (paper §3.2.2) picks 32 when max_len + 6 <= 32."""
    if unit_bits == 32:
        return (cb.lengths.astype(jnp.uint32) << 26) | cb.codes
    hi = cb.lengths.astype(jnp.uint32)        # emulate u64 as 2x u32
    return jnp.stack([hi, cb.codes], axis=-1)


def select_repr(max_len) -> int:
    """Adaptive codeword representation (paper §3.2.2)."""
    return 32 if int(max_len) + 6 <= 32 else 64


# ---------------------------------------------------------------------------
# Encode + deflate
# ---------------------------------------------------------------------------

def encode(codes: jax.Array, cb: Codebook) -> Tuple[jax.Array, jax.Array]:
    """Codebook gather: per-symbol (codeword, bitwidth).  Massively parallel
    (paper §3.2.4: 'basically memory copy')."""
    flat = codes.reshape(-1)
    return cb.codes[flat], cb.lengths[flat]


def norm_sub_size(chunk_size: int, sub_size: int) -> int:
    """Clamp the gap-array subchunk to the chunk and check divisibility."""
    sub = min(int(sub_size), int(chunk_size))
    if chunk_size % sub:
        raise ValueError(f"sub_size {sub} must divide chunk_size "
                         f"{chunk_size}")
    return sub


def deflate(cw: jax.Array, bw: jax.Array, chunk_size: int,
            sub_size: int = SUBCHUNK
            ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Concatenate variable-length codes into dense per-chunk bitstreams.

    Prefix-sum formulation: exclusive cumsum of bitwidths = bit offset of
    every codeword; each codeword contributes <=2 disjoint u32 fragments,
    combined with scatter-add.  MSB-first within each word.

    The exclusive prefix sum is additionally sampled every `sub_size`
    symbols into the GAP ARRAY (Rivera et al., arXiv 2201.09118) that
    makes inflate parallel over subchunks: `gap_bits[c, s]` is the bit
    offset of subchunk s inside chunk c, `gap_syms[c, s]` the count of
    valid (non-pad) symbols before it.

    Returns (words[nc, chunk_size] uint32, bits_used[nc] int32,
    gap_bits[nc, chunk_size//sub_size] int32, gap_syms[...] int32).
    """
    sub = norm_sub_size(chunk_size, sub_size)
    n = cw.shape[0]
    nc = -(-n // chunk_size)
    pad = nc * chunk_size - n
    cw = jnp.pad(cw.astype(jnp.uint32), (0, pad)).reshape(nc, chunk_size)
    bw = jnp.pad(bw.astype(jnp.int32), (0, pad)).reshape(nc, chunk_size)

    offs = jnp.cumsum(bw, axis=1) - bw                    # exclusive
    bits_used = (offs[:, -1] + bw[:, -1]).astype(jnp.int32)
    gap_bits = offs[:, ::sub].astype(jnp.int32)           # [nc, n_sub]
    valid_cnt = jnp.cumsum((bw > 0).astype(jnp.int32), axis=1) - (bw > 0)
    gap_syms = valid_cnt[:, ::sub].astype(jnp.int32)

    w = (offs >> 5).astype(jnp.int32)
    b = (offs & 31).astype(jnp.int32)
    sh = 32 - b - bw                                       # may be negative
    shp = jnp.clip(sh, 0, 31)
    shn = jnp.clip(-sh, 0, 31)
    hi = jnp.where(sh >= 0, cw << shp.astype(jnp.uint32),
                   cw >> shn.astype(jnp.uint32))
    lo = jnp.where(sh < 0,
                   cw << jnp.clip(32 + sh, 0, 31).astype(jnp.uint32),
                   jnp.uint32(0))
    valid = bw > 0
    hi = jnp.where(valid, hi, 0)
    lo = jnp.where(valid, lo, 0)

    out = jnp.zeros((nc, chunk_size), jnp.uint32)          # 32 bits/symbol cap
    ci = jnp.broadcast_to(jnp.arange(nc)[:, None], w.shape)
    out = out.at[ci, w].add(hi, mode="drop")
    out = out.at[ci, w + 1].add(lo, mode="drop")
    return out, bits_used, gap_bits, gap_syms


# ---------------------------------------------------------------------------
# Inflate (decode)
# ---------------------------------------------------------------------------

def _build_lut(cb: Codebook, lut_bits: int) -> Tuple[jax.Array, jax.Array]:
    """Dense (symbol, length) table keyed by the next `lut_bits` bits.

    Left-aligned canonical codes are strictly increasing in canonical order,
    so a scatter of group starts + cummax fill builds the table without
    variable-length repeats."""
    k = cb.lengths.shape[0]
    L = lut_bits
    len_canon = cb.lengths[cb.sym_canon]
    shift = jnp.clip(L - len_canon, 0, 31).astype(jnp.uint32)
    starts = (cb.codes[cb.sym_canon] << shift).astype(jnp.uint32)
    active = len_canon > 0
    starts = jnp.where(active, starts, jnp.uint32(1) << L)  # OOB -> dropped
    mark = jnp.zeros((1 << L,), jnp.int32)
    mark = mark.at[starts.astype(jnp.int32)].max(
        jnp.where(active, jnp.arange(k, dtype=jnp.int32) + 1, 0), mode="drop")
    fill = jax.lax.cummax(mark) - 1                        # canonical rank
    fill = jnp.clip(fill, 0)
    return cb.sym_canon[fill], len_canon[fill]


def inflate_lut(words: jax.Array, n_valid: jax.Array, cb: Codebook,
                lut_bits: int = LUT_BITS,
                lut: Optional[Tuple[jax.Array, jax.Array]] = None
                ) -> jax.Array:
    """O(symbols) per-chunk decode via the LUT; vmapped over chunks.

    words: [nc, W] uint32; n_valid: [nc] symbols per chunk.
    Returns codes [nc, chunk_symbols] (chunk_symbols == W: one u32 per
    symbol capacity, mirroring deflate).  Pass `lut` (from a cached
    `DecodeTable`) to skip the in-trace table build."""
    lut_sym, lut_len = lut if lut is not None else _build_lut(cb, lut_bits)
    nc, W = words.shape
    n_sym = W

    def chunk_decode(wrow, nv):
        wext = jnp.concatenate([wrow, jnp.zeros((1,), jnp.uint32)])

        def step(bitpos, i):
            wi = bitpos >> 5
            bo = (bitpos & 31).astype(jnp.uint32)
            cur = wext[wi] << bo
            nxt = jnp.where(bo > 0, wext[wi + 1] >> (jnp.uint32(32) - bo),
                            jnp.uint32(0))
            peek = ((cur | nxt) >> jnp.uint32(32 - lut_bits)).astype(jnp.int32)
            sym = lut_sym[peek]
            ln = lut_len[peek]
            ok = i < nv
            return bitpos + jnp.where(ok, ln, 0), jnp.where(ok, sym, 0)

        _, syms = jax.lax.scan(step, jnp.int32(0),
                               jnp.arange(n_sym, dtype=jnp.int32))
        return syms

    return jax.vmap(chunk_decode)(words, n_valid)


def inflate_bitscan(words: jax.Array, bits_used: jax.Array, n_valid: jax.Array,
                    cb: Codebook) -> jax.Array:
    """O(bits) per-chunk decode (fallback when max_len > LUT_BITS).  Walks
    one bit at a time exactly like the paper's sequential inflate."""
    nc, W = words.shape
    n_sym = W
    total_bits = W * 32

    def chunk_decode(wrow, nb, nv):
        def step(carry, bitpos):
            acc, ln, outpos, out = carry
            wi = bitpos >> 5
            bit = (wrow[wi] >> jnp.uint32(31 - (bitpos & 31))) & 1
            acc = (acc << 1) | bit
            ln = ln + 1
            lnc = jnp.clip(ln, 0, MAXLEN)
            # match if there are codes of this length and acc falls in range
            lo = cb.first_code[lnc]
            idx = cb.start_idx[lnc] + (acc - lo).astype(jnp.int32)
            in_range = (acc >= lo) & (idx < cb.start_idx[lnc] +
                                      _len_count(cb, lnc))
            active = (bitpos < nb) & (outpos < nv)
            emit = in_range & active
            sym = cb.sym_canon[jnp.clip(idx, 0, cb.sym_canon.shape[0] - 1)]
            out = jnp.where(emit, out.at[outpos].set(sym, mode="drop"), out)
            acc = jnp.where(emit, jnp.uint32(0), acc)
            ln = jnp.where(emit, 0, ln)
            outpos = outpos + emit.astype(jnp.int32)
            return (acc, ln, outpos, out), None

        init = (jnp.uint32(0), jnp.int32(0), jnp.int32(0),
                jnp.zeros((n_sym,), jnp.int32))
        (_, _, _, out), _ = jax.lax.scan(
            step, init, jnp.arange(total_bits, dtype=jnp.int32))
        return out

    return jax.vmap(chunk_decode)(words, bits_used, n_valid)


def _len_count(cb: Codebook, l: jax.Array) -> jax.Array:
    nxt = jnp.where(l < MAXLEN,
                    cb.start_idx[jnp.clip(l + 1, 0, MAXLEN)],
                    jnp.sum(cb.lengths > 0).astype(jnp.int32))
    return nxt - cb.start_idx[l]


def inflate(words: jax.Array, bits_used: jax.Array, n_valid: jax.Array,
            cb: Codebook, max_len_static: int) -> jax.Array:
    """Dispatch LUT vs bit-scan on the *static* bound for max codeword
    length (callers pass the practical bound; paper's adaptive-repr idea).
    This is the legacy per-chunk SEQUENTIAL decode, kept for gap-less
    (format v1) streams; gap-array streams use `inflate_gap`."""
    if max_len_static <= LUT_BITS:
        return inflate_lut(words, n_valid, cb,
                           lut_bits=max(1, max_len_static))
    return inflate_bitscan(words, bits_used, n_valid, cb)


# ---------------------------------------------------------------------------
# Gap-array two-phase decode (Rivera et al., arXiv 2201.09118)
# ---------------------------------------------------------------------------

class DecodeTable(NamedTuple):
    """Everything the decode side derives from a codebook, built once per
    codebook (see `decode_table`) instead of inside every decode trace.

    `lut_sym`/`lut_len` are the dense LUT (LUT regime, max_len <= LUT_BITS;
    [1]-sized dummies otherwise).  `thresh`/`lmask` are the canonical
    length-interval bounds used by the LUT-free decoders: left-aligned
    canonical code intervals tile [0, 2^32) contiguously in length order
    (base_al[l+1] == end_al[l]), so for a 32-bit left-aligned peek of a
    valid stream the codeword length is

        len = 1 + sum_l lmask[l] * [peek >= thresh[l]]

    with thresh[l] = (first_code[l] + count[l]) << (32 - l) and lmask
    enabling 1 <= l < max_len (for those l the end never reaches 2^32, so
    the u32 compare is exact)."""
    cb: Codebook
    lut_sym: jax.Array      # [1 << lut_bits] int32 (or [1] dummy)
    lut_len: jax.Array      # [1 << lut_bits] int32 (or [1] dummy)
    thresh: jax.Array       # [MAXLEN + 1] uint32 end-of-interval bounds
    lmask: jax.Array        # [MAXLEN + 1] int32 validity of each bound


def _length_bounds(cb: Codebook) -> Tuple[jax.Array, jax.Array]:
    cnt = jnp.bincount(jnp.clip(cb.lengths, 0, MAXLEN),
                       length=MAXLEN + 1).at[0].set(0)
    ell = jnp.arange(MAXLEN + 1, dtype=jnp.int32)
    span = cb.first_code + cnt.astype(jnp.uint32)     # first_code[l]+count[l]
    thresh = span << jnp.clip(32 - ell, 0, 31).astype(jnp.uint32)
    lmask = ((ell >= 1) & (ell < cb.max_len)).astype(jnp.int32)
    return thresh, lmask


@partial(jax.jit, static_argnames=("max_len_static",))
def build_decode_table(lengths: jax.Array, max_len_static: int) -> DecodeTable:
    """Codebook + decode tables from stored bitlengths (one jit per
    (nbins, bucketed max_len) — NOT per field)."""
    with jax.named_scope("stage.decode_table"):
        cb = canonical_codebook(lengths)
        thresh, lmask = _length_bounds(cb)
        if max_len_static <= LUT_BITS:
            lut_sym, lut_len = _build_lut(cb, max(1, max_len_static))
        else:
            lut_sym = jnp.zeros((1,), jnp.int32)
            lut_len = jnp.zeros((1,), jnp.int32)
        return DecodeTable(cb, lut_sym, lut_len, thresh, lmask)


# identity-keyed LRU: repeated decodes of the same stored codebook (serve
# eviction-restore, checkpoint restore retries) reuse the built tables
# with zero host syncs; entries hold a strong ref to the key array so an
# id() can never be reused while its entry is alive.
_DECODE_TABLE_CACHE: "OrderedDict[Tuple[int, int], Tuple[jax.Array, DecodeTable]]" = OrderedDict()
_DECODE_TABLE_CACHE_SIZE = 64


def decode_table(lengths: jax.Array, max_len_static: int) -> DecodeTable:
    """Cached `build_decode_table`: the (1 << lut_bits)-entry scatter +
    cummax LUT build runs once per codebook array, not on-device at every
    restore / eviction-restore step."""
    key = (id(lengths), int(max_len_static))
    hit = _DECODE_TABLE_CACHE.get(key)
    if hit is not None and hit[0] is lengths:
        _DECODE_TABLE_CACHE.move_to_end(key)
        spans.count("decode_table.hits")
        return hit[1]
    spans.count("decode_table.builds")
    tbl = build_decode_table(lengths, int(max_len_static))
    _DECODE_TABLE_CACHE[key] = (lengths, tbl)
    while len(_DECODE_TABLE_CACHE) > _DECODE_TABLE_CACHE_SIZE:
        _DECODE_TABLE_CACHE.popitem(last=False)
    return tbl


def inflate_gap(words: jax.Array, n_valid: jax.Array, gap_bits: jax.Array,
                table: DecodeTable, sub_size: int, max_len_static: int
                ) -> jax.Array:
    """Phase-2 gap-array decode: every subchunk decodes independently from
    its recorded bit offset, so the sequential walk is `sub_size` symbols
    (not `chunk_size`) and nc·n_sub subchunks run in lockstep.

    words: [nc, W] uint32; n_valid: [nc]; gap_bits: [nc, W // sub_size].
    LUT regime (max_len <= LUT_BITS) peeks `lut_bits` bits through the
    cached LUT; otherwise the canonical length-interval compare decodes a
    full 32-bit peek without any table (see `DecodeTable`).  Returns
    codes [nc, W], bit-exact with the sequential `inflate`."""
    nc, W = words.shape
    n_sub = gap_bits.shape[1]
    if n_sub * sub_size != W:
        raise ValueError(f"gap array [{nc}, {n_sub}] does not tile chunks "
                         f"of {W} symbols with sub_size={sub_size}")
    use_lut = max_len_static <= LUT_BITS
    lut_bits = max(1, max_len_static)
    cb = table.cb

    def chunk_decode(wrow, nv, gaps):
        wext = jnp.concatenate([wrow, jnp.zeros((1,), jnp.uint32)])
        base = jnp.arange(n_sub, dtype=jnp.int32) * sub_size

        def step(bitpos, i):
            wi = bitpos >> 5
            bo = (bitpos & 31).astype(jnp.uint32)
            cur = wext[wi] << bo
            nxt = jnp.where(bo > 0,
                            wext[jnp.minimum(wi + 1, W)]
                            >> (jnp.uint32(32) - bo), jnp.uint32(0))
            peek = cur | nxt                      # 32-bit left-aligned window
            if use_lut:
                slot = (peek >> jnp.uint32(32 - lut_bits)).astype(jnp.int32)
                sym = table.lut_sym[slot]
                ln = table.lut_len[slot]
            else:
                hit = (peek[:, None] >= table.thresh[None, :]) \
                    & (table.lmask[None, :] > 0)
                ln = 1 + jnp.sum(hit.astype(jnp.int32), axis=1)
                lnc = jnp.clip(ln, 1, MAXLEN)
                code = peek >> (jnp.uint32(32) - lnc.astype(jnp.uint32))
                idx = cb.start_idx[lnc] \
                    + (code - cb.first_code[lnc]).astype(jnp.int32)
                sym = cb.sym_canon[jnp.clip(idx, 0,
                                            cb.sym_canon.shape[0] - 1)]
            ok = (base + i) < nv
            return bitpos + jnp.where(ok, ln, 0), jnp.where(ok, sym, 0)

        _, syms = jax.lax.scan(step, gaps.astype(jnp.int32),
                               jnp.arange(sub_size, dtype=jnp.int32))
        return syms.T.reshape(W)                  # [sub, n_sub] -> chunk order

    return jax.vmap(chunk_decode)(words, n_valid, gap_bits)
