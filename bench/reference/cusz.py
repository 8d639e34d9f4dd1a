"""Plain NumPy reference of the cuSZ container (codec "cusz", version 2).

Written from the format and the paper alone; it imports nothing of
`repro`.  `decode` reads a packed container as `codecs.to_arrays` gives
it (header JSON + host arrays) and returns the field with every
inconsistency it met counted; `encode` writes such a container.  Both
take the precision they compute in, so the same code serves as the
lower-precision control.

The format, as the paper and the header define it:

* PREQUANT d° = rint(d / 2eb), in blocks (8x8x8 in 3-D, 256 in 1-D) of
  the edge-padded field, block axes last; POSTQUANT δ = the in-block
  first difference along every block axis (zero outside the block).
* codes: δ + nbins/2 where |δ| < nbins/2, else 0 with (index, δ) in the
  sparse outlier store; the code stream is the blocked array flattened.
* canonical Huffman over the code histogram, codeword bitlengths stored
  as `lengths`; the stream is cut into chunks of `chunk_size` symbols,
  each packed MSB-first into 32-bit words of its own (`bits_used` bits),
  only the used words stored (`words_packed`).
* gap arrays: per chunk, the bit offset (`gap_bits`) and the count of
  symbols (`gap_syms`) at every `sub_size`-symbol boundary.
* `checksum`: crc32 over, per field in sorted order,
  ``f"{name}:{dtype.str}:{shape};"`` followed by the field's bytes.
"""
from __future__ import annotations

import heapq
import json
import zlib
from typing import Dict, Tuple

import numpy as np

CODEC, VERSION = "cusz", 2
MAXLEN = 32


# -- canonical Huffman --------------------------------------------------------

def huffman_lengths(freq: np.ndarray) -> np.ndarray:
    """Codeword bitlength per symbol by the textbook heap construction
    (0 for unused symbols; a lone symbol gets 1 bit)."""
    active = [int(s) for s in np.flatnonzero(freq)]
    lengths = np.zeros(freq.shape[0], np.int64)
    if len(active) == 1:
        lengths[active[0]] = 1
    heap = [(int(freq[s]), i, (s,)) for i, s in enumerate(active)]
    heapq.heapify(heap)
    uid = len(heap)
    while len(heap) > 1:
        f1, _, a = heapq.heappop(heap)
        f2, _, b = heapq.heappop(heap)
        lengths[list(a + b)] += 1
        heapq.heappush(heap, (f1 + f2, uid, a + b))
        uid += 1
    return lengths


def canonical(lengths: np.ndarray):
    """(codes, first_code, start, sym_canon, count) of the canonical code:
    symbols ordered by (length, symbol), consecutive codes within a
    length, first_code[l] = (first_code[l-1] + count[l-1]) << 1."""
    lengths = np.asarray(lengths, np.int64)
    count = np.bincount(lengths, minlength=MAXLEN + 1)[:MAXLEN + 1].copy()
    count[0] = 0
    first_code = np.zeros(MAXLEN + 1, np.int64)
    for ln in range(1, MAXLEN + 1):
        first_code[ln] = (first_code[ln - 1] + count[ln - 1]) << 1
    start = np.concatenate([[0], np.cumsum(count)[:-1]])
    used = np.flatnonzero(lengths > 0)
    sym_canon = used[np.lexsort((used, lengths[used]))]
    codes = np.zeros(lengths.shape[0], np.int64)
    rank = np.arange(sym_canon.shape[0]) - start[lengths[sym_canon]]
    codes[sym_canon] = first_code[lengths[sym_canon]] + rank
    return codes, first_code, start, sym_canon, count


def payload_crc32(arrays: Dict[str, np.ndarray]) -> int:
    crc = 0
    for k in sorted(arrays):
        a = np.ascontiguousarray(np.asarray(arrays[k]))
        crc = zlib.crc32(f"{k}:{a.dtype.str}:{a.shape};".encode(), crc)
        crc = zlib.crc32(a.tobytes(), crc)
    return crc & 0xFFFFFFFF


def container_nbytes(header: dict, arrays: Dict[str, np.ndarray]) -> int:
    """What the packed container occupies: its arrays and its header."""
    return (sum(int(np.asarray(a).nbytes) for a in arrays.values())
            + len(json.dumps(header, sort_keys=True).encode()))


# -- blocking -----------------------------------------------------------------

def padded_shape(shape, block) -> Tuple[int, ...]:
    return tuple(-(-s // b) * b for s, b in zip(shape, block))


def _to_blocks(x: np.ndarray, block) -> np.ndarray:
    """[D...] (already a multiple of block) -> [nb..., b...]."""
    nd = x.ndim
    split = [v for s, b in zip(x.shape, block) for v in (s // b, b)]
    return x.reshape(split).transpose(list(range(0, 2 * nd, 2))
                                      + list(range(1, 2 * nd, 2)))


def _from_blocks(xb: np.ndarray, block) -> np.ndarray:
    nd = len(block)
    perm = [v for i in range(nd) for v in (i, nd + i)]
    x = xb.transpose(perm)
    return x.reshape([x.shape[2 * i] * x.shape[2 * i + 1]
                      for i in range(nd)])


# -- encode -------------------------------------------------------------------

def _deflate(cw: np.ndarray, bw: np.ndarray, chunk: int, sub: int):
    """cw/bw [nc, chunk] -> (dense words [nc, chunk] uint32, bits_used,
    gap_bits, gap_syms).  A codeword at bit offset b of word w is the
    top of the 64-bit big-endian pair (w, w+1) shifted to 64 - b - len."""
    nc = cw.shape[0]
    offs = np.cumsum(bw, axis=1) - bw
    bits_used = offs[:, -1] + bw[:, -1]
    valid = (bw > 0).astype(np.int64)
    gap_bits = offs[:, ::sub]
    gap_syms = (np.cumsum(valid, axis=1) - valid)[:, ::sub]
    w = (offs >> 5) + np.arange(nc)[:, None] * (chunk + 1)
    pair = np.where(bw > 0, cw.astype(np.uint64)
                    << (64 - (offs & 31) - bw).astype(np.uint64), 0)
    size = nc * (chunk + 1)
    # disjoint bit fields: a sum is an OR, exact in float64 below 2**53
    words = (np.bincount(w.ravel(), (pair >> 32).astype(np.float64).ravel(),
                         size)
             + np.bincount((w + 1).ravel(),
                           (pair & 0xFFFFFFFF).astype(np.float64).ravel(),
                           size))
    words = words.astype(np.uint64).reshape(nc, chunk + 1)[:, :chunk]
    return words.astype(np.uint32), bits_used, gap_bits, gap_syms


def encode(x: np.ndarray, *, eb_rel: float, nbins: int, chunk_size: int,
           sub_size: int, block, outlier_frac: float, dtype=np.float32,
           chunks_per_pass: int = 4096):
    """Compress `x` at a value-range-relative bound, computing PREQUANT in
    `dtype`.  Returns (header JSON, packed arrays)."""
    shape = tuple(x.shape)
    xq = np.asarray(x).astype(dtype)
    eb = float(eb_rel) * (float(xq.max()) - float(xq.min()))
    pshape = padded_shape(shape, block)
    xp = np.pad(xq, [(0, p - s) for s, p in zip(shape, pshape)], mode="edge")
    dq = np.rint((_to_blocks(xp, block) / dtype(2.0 * eb)).astype(dtype)
                 ).astype(np.int32)
    nd = len(block)
    delta = dq
    for ax in range(nd, 2 * nd):
        prev = np.zeros_like(delta)
        idx = [slice(None)] * delta.ndim
        idx[ax] = slice(1, None)
        src = [slice(None)] * delta.ndim
        src[ax] = slice(0, -1)
        prev[tuple(idx)] = delta[tuple(src)]
        delta = delta - prev
    delta = delta.reshape(-1)
    radius = nbins // 2
    in_cap = (delta > -radius) & (delta < radius)
    codes = np.where(in_cap, delta + radius, 0)
    n = codes.shape[0]
    out_idx = np.flatnonzero(~in_cap)
    lengths = huffman_lengths(np.bincount(codes, minlength=nbins))
    cbook = canonical(lengths)[0]

    nc = -(-n // chunk_size)
    parts = {k: [] for k in ("words", "bits", "gb", "gs")}
    for c0 in range(0, nc, chunks_per_pass):
        c1 = min(nc, c0 + chunks_per_pass)
        seg = codes[c0 * chunk_size:c1 * chunk_size]
        pad = (c1 - c0) * chunk_size - seg.shape[0]
        cw = np.pad(cbook[seg], (0, pad)).reshape(c1 - c0, chunk_size)
        bw = np.pad(lengths[seg], (0, pad)).reshape(c1 - c0, chunk_size)
        words, bits, gb, gs = _deflate(cw, bw, chunk_size, sub_size)
        used = (bits + 31) // 32
        keep = np.arange(chunk_size)[None, :] < used[:, None]
        for k, v in zip(parts, (words[keep], bits, gb, gs)):
            parts[k].append(v)
    n_valid = np.minimum(chunk_size, n - np.arange(nc) * chunk_size)
    arrays = {
        "words_packed": np.concatenate(parts["words"]).astype(np.uint32),
        "bits_used": np.concatenate(parts["bits"]).astype(np.int32),
        "n_valid": n_valid.astype(np.int32),
        "lengths": lengths.astype(np.uint8),
        "max_len": np.asarray(lengths.max(), np.int32),
        "chunk_words": np.asarray(chunk_size, np.int32),
        "gap_bits": np.concatenate(parts["gb"]).astype(np.int32),
        "gap_syms": np.concatenate(parts["gs"]).astype(np.uint16),
        "out_idx": out_idx.astype(np.int32),
        "out_val": delta[out_idx].astype(np.int32),
        "out_capacity": np.asarray(max(16, int(n * outlier_frac)), np.int32),
    }
    header = {"format": 1, "codec": CODEC, "version": VERSION,
              "dtype": np.dtype(np.float32).name, "shape": list(shape),
              "params": {"block": list(block), "chunk_size": chunk_size,
                         "eb": eb, "nbins": nbins,
                         "outlier_frac": outlier_frac, "packed": True,
                         "sub_size": sub_size,
                         "checksum": payload_crc32(arrays)}}
    return header, arrays


# -- decode -------------------------------------------------------------------

def _decode_stream(arrays, lengths, n_sym, chunk, sub, faults):
    """Huffman decode of every sub_size-symbol subchunk from its recorded
    bit offset, all subchunks in lockstep; checks that each one ends
    where the next begins (or at bits_used) and returns the codes."""
    bits = arrays["bits_used"].astype(np.int64)
    nc = bits.shape[0]
    n_sub = chunk // sub
    nwords = (bits + 31) // 32
    wp = arrays["words_packed"].astype(np.uint64)
    if wp.shape[0] != int(nwords.sum()):
        faults["word_count"] += 1
        return None
    wp = np.concatenate([wp, np.zeros(2, np.uint64)])
    _, first_code, start, sym_canon, count = canonical(lengths)
    max_len = int(lengths.max())
    ell = np.arange(1, max_len + 1)
    # left-aligned end of each length's code interval (contiguous and
    # non-decreasing for a prefix code): a 32-bit window w holds a code
    # of the first length l with w < limit[l]
    limit = (first_code[ell] + count[ell]) << (32 - ell)

    gap_bits = arrays["gap_bits"].astype(np.int64)
    n_valid = arrays["n_valid"].astype(np.int64)
    lane_cnt = np.clip(n_valid[:, None] - np.arange(n_sub) * sub, 0, sub)
    pos = gap_bits + (32 * (np.cumsum(nwords) - nwords))[:, None]
    pos, lane_cnt = pos.reshape(-1), lane_cnt.reshape(-1)
    begin = pos.copy()
    out = np.zeros((sub, pos.shape[0]), np.int32)
    bad = np.zeros(pos.shape[0], bool)
    for i in range(sub):
        live = i < lane_cnt
        wi, bo = pos >> 5, (pos & 31).astype(np.uint64)
        win = (((wp[wi] << np.uint64(32) | wp[wi + 1]) << bo)
               >> np.uint64(32)).astype(np.int64)
        li = np.searchsorted(limit, win, side="right")
        bad |= live & (li >= max_len)
        ln = np.minimum(li, max_len - 1) + 1
        code = win >> (32 - ln)
        k = np.clip(start[ln] + code - first_code[ln], 0,
                    sym_canon.shape[0] - 1)
        out[i] = np.where(live, sym_canon[k], 0)
        pos = pos + np.where(live, ln, 0)
    faults["bad_codeword"] += int(bad.sum())
    # each subchunk must end where the next one starts
    ends = (pos - begin).reshape(nc, n_sub) + gap_bits
    want = np.concatenate([gap_bits[:, 1:], bits[:, None]], axis=1)
    faults["gap_chain"] += int(np.count_nonzero(ends != want))
    return out.T.reshape(-1)[:n_sym]


def decode(header: dict, arrays: Dict[str, np.ndarray], *,
           dtype=np.float32) -> Tuple[np.ndarray, Dict[str, int]]:
    """Decompress a packed container, dequantising in `dtype`.  Returns
    (float32 field or None, faults per check)."""
    faults = {k: 0 for k in ("header", "checksum", "lengths", "word_count",
                             "n_valid", "gap_syms", "bad_codeword",
                             "gap_chain", "outliers")}
    p = header["params"]
    if (header["codec"], header["version"], header["dtype"]) != (
            CODEC, VERSION, "float32") or not p.get("packed"):
        faults["header"] += 1
        return None, faults
    arrays = {k: np.asarray(v) for k, v in arrays.items()}
    if int(p.get("checksum", -1)) != payload_crc32(arrays):
        faults["checksum"] += 1
    shape, block = tuple(header["shape"]), tuple(p["block"])
    chunk, sub, nbins = int(p["chunk_size"]), int(p["sub_size"]), \
        int(p["nbins"])
    eb = float(p["eb"])
    pshape = padded_shape(shape, block)
    n_sym = int(np.prod(pshape))
    nc = -(-n_sym // chunk)

    lengths = arrays["lengths"].astype(np.int64)
    if (lengths.shape != (nbins,) or lengths.max() > MAXLEN
            or int(arrays["max_len"]) != int(lengths.max())
            or np.sum(np.ldexp(1.0, -lengths[lengths > 0])) > 1.0
            or int(arrays["chunk_words"]) != chunk):
        faults["lengths"] += 1
        return None, faults
    want_valid = np.minimum(chunk, n_sym - np.arange(nc) * chunk)
    if arrays["n_valid"].shape != (nc,) or arrays["gap_bits"].shape != (
            nc, chunk // sub):
        faults["n_valid"] += 1
        return None, faults
    faults["n_valid"] += int(np.count_nonzero(arrays["n_valid"]
                                              != want_valid))
    want_syms = np.minimum(np.arange(chunk // sub) * sub,
                           want_valid[:, None])
    faults["gap_syms"] += int(np.count_nonzero(
        arrays["gap_syms"].astype(np.int64) != want_syms))

    codes = _decode_stream(arrays, lengths, n_sym, chunk, sub, faults)
    if codes is None:
        return None, faults
    radius = nbins // 2
    delta = np.where(codes == 0, 0, codes - radius).astype(np.int32)
    oi, ov = arrays["out_idx"].astype(np.int64), arrays["out_val"]
    zero = np.flatnonzero(codes == 0)
    if (oi.shape != zero.shape or oi.shape[0] > int(arrays["out_capacity"])
            or np.any(oi != zero)):
        faults["outliers"] += 1 + abs(oi.shape[0] - zero.shape[0])
    ok = (oi >= 0) & (oi < n_sym)
    delta[oi[ok]] = ov[ok]
    nb = tuple(q // b for q, b in zip(pshape, block))
    dq = delta.reshape(nb + block)
    for ax in range(len(block), 2 * len(block)):
        dq = np.cumsum(dq, axis=ax, dtype=np.int32)
    full = _from_blocks(dq, block)[tuple(slice(0, s) for s in shape)]
    y = (full.astype(dtype) * dtype(2.0 * eb)).astype(np.float32)
    return y, faults
