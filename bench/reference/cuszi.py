"""Plain NumPy reference of the cuSZ-i container (codec "cusz-i", version 1).

Written from the scheme and the container's format; it imports nothing
of `repro`, and takes the canonical Huffman code, the crc32 and the
stream decoder from `bench.reference.cusz` (the Huffman tail is the
cusz one).  `decode` reads a packed container as `codecs.to_arrays`
gives it and returns the field with every inconsistency it met counted;
`encode` writes such a container.  Both take the precision they compute
in, so the same code serves as the lower-precision control.

The scheme:

* PREQUANT d° = rint(d / 2eb), once, over the whole field.
* The plan: while any axis is longer than 4, each such axis in turn
  (0, 1, 2, ...) splits its s samples into ceil(s/2) evens and
  floor(s/2) odds; the evens go on to the next split.  A field with no
  axis longer than 4 (and more than one value) splits its longest axis
  once.  What is left at the end is the anchor grid.
* Each odd sample o_j is predicted from the evens e around it by the
  integer cubic p_j = (9 (e_j + e_{j+1}) - e_{j-1} - e_{j+2} + 8) >> 4,
  with edge replication (e_{-1} = e_0, e_m = e_{m+1} = e_{m-1} for m
  evens), and r_j = o_j - p_j is kept.  The arithmetic is exact integer
  arithmetic and >> floors, so the decode, o_j = r_j + p_j over the
  evens already rebuilt, is the exact inverse.
* Residuals are stored level-major (one split after the other), each
  split's in row-major order with the split axis moved last; the anchor
  grid is stored raw as int32 (`anchor`), in row-major order.
* codes: r + nbins/2 where |r| < nbins/2, else 0 with (index, r) in the
  sparse outlier store; then the cusz Huffman stream (chunks, gap
  arrays, `checksum`) over the codes, as `bench.reference.cusz` reads
  it.  A field of one value codes one residual 0.

Where this departs from the published cuSZ-i (Liu et al., arXiv
2312.05492):

* the anchor grid is what is left after splitting every axis down to 4
  samples or fewer, not anchors at a fixed stride (cuSZ-i: every 8th
  point of each axis);
* one fixed cubic stencil and a fixed axis order, where cuSZ-i chooses
  between linear and cubic splines and between axis orders per level
  from sampled errors;
* one prequantisation and exact integer lifting, where cuSZ-i quantises
  each level's prediction error against its own (level-dependent)
  error bound;
* no lossless pass after the Huffman stream (cuSZ-i follows it with a
  de-redundancy pass, Bitcomp in its evaluation).
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

from bench.reference import cusz

CODEC, VERSION, PREDICTOR = "cusz-i", 1, "interp"
ANCHOR = 4


# -- the plan and one split ---------------------------------------------------

def plan(shape) -> Tuple[List[Tuple[int, Tuple[int, ...]]], Tuple[int, ...]]:
    """(steps, anchor shape): each step is (axis, shape before the split)."""
    s = [int(v) for v in shape]
    steps = []
    while max(s) > ANCHOR:
        for a in range(len(s)):
            if s[a] > ANCHOR:
                steps.append((a, tuple(s)))
                s[a] = (s[a] + 1) // 2
    if not steps and max(s) > 1:
        a = int(np.argmax(s))
        steps.append((a, tuple(s)))
        s[a] = (s[a] + 1) // 2
    return steps, tuple(s)


def predict(e: np.ndarray, n_odd: int) -> np.ndarray:
    """The cubic prediction of the `n_odd` odd samples from the evens `e`
    along the last axis, edge-replicated (indices clamped into e)."""
    m = e.shape[-1]
    j = np.arange(n_odd)

    def at(k):
        return e[..., np.clip(j + k, 0, m - 1)]
    return (9 * (at(0) + at(1)) - at(-1) - at(2) + 8) >> 4


def odd_counts(steps) -> List[int]:
    """Residuals each step stores."""
    return [int(np.prod(shp)) // shp[a] * (shp[a] // 2) for a, shp in steps]


# -- encode -------------------------------------------------------------------

def _huffman_stream(codes: np.ndarray, nbins: int, chunk: int, sub: int,
                    chunks_per_pass: int = 4096) -> Dict[str, np.ndarray]:
    """The cusz Huffman tail over `codes`: canonical book, chunked words,
    gap arrays."""
    lengths = cusz.huffman_lengths(np.bincount(codes, minlength=nbins))
    cbook = cusz.canonical(lengths)[0]
    n = codes.shape[0]
    nc = -(-n // chunk)
    parts = {k: [] for k in ("words", "bits", "gb", "gs")}
    for c0 in range(0, nc, chunks_per_pass):
        c1 = min(nc, c0 + chunks_per_pass)
        seg = codes[c0 * chunk:c1 * chunk]
        pad = (c1 - c0) * chunk - seg.shape[0]
        cw = np.pad(cbook[seg], (0, pad)).reshape(c1 - c0, chunk)
        bw = np.pad(lengths[seg], (0, pad)).reshape(c1 - c0, chunk)
        words, bits, gb, gs = cusz._deflate(cw, bw, chunk, sub)
        used = (bits + 31) // 32
        keep = np.arange(chunk)[None, :] < used[:, None]
        for k, v in zip(parts, (words[keep], bits, gb, gs)):
            parts[k].append(v)
    return {
        "words_packed": np.concatenate(parts["words"]).astype(np.uint32),
        "bits_used": np.concatenate(parts["bits"]).astype(np.int32),
        "n_valid": np.minimum(chunk, n - np.arange(nc) * chunk
                              ).astype(np.int32),
        "lengths": lengths.astype(np.uint8),
        "max_len": np.asarray(lengths.max(), np.int32),
        "chunk_words": np.asarray(chunk, np.int32),
        "gap_bits": np.concatenate(parts["gb"]).astype(np.int32),
        "gap_syms": np.concatenate(parts["gs"]).astype(np.uint16),
    }


def encode(x: np.ndarray, *, eb_rel: float, nbins: int, chunk_size: int,
           sub_size: int, block, outlier_frac: float, dtype=np.float32):
    """Compress `x` at a value-range-relative bound, computing PREQUANT in
    `dtype`.  `block` (a Lorenzo block) plays no part in the scheme; it
    is recorded in the header as the program records its own.  Returns
    (header JSON, packed arrays)."""
    shape = tuple(x.shape)
    xq = np.asarray(x).astype(dtype)
    eb = float(eb_rel) * (float(xq.max()) - float(xq.min()))
    d = np.rint((xq / dtype(2.0 * eb)).astype(dtype)).astype(np.int64)
    steps, _ = plan(shape)
    parts = []
    for axis, _ in steps:
        xm = np.moveaxis(d, axis, -1)
        e, o = xm[..., 0::2], xm[..., 1::2]
        parts.append((o - predict(e, o.shape[-1])).reshape(-1))
        d = np.moveaxis(e, -1, axis)
    resid = np.concatenate(parts) if parts else np.zeros(1, np.int64)
    radius = nbins // 2
    in_cap = np.abs(resid) < radius
    codes = np.where(in_cap, resid + radius, 0)
    out_idx = np.flatnonzero(~in_cap)
    n = int(np.prod(shape))
    arrays = _huffman_stream(codes, nbins, chunk_size, sub_size)
    arrays.update({
        "out_idx": out_idx.astype(np.int32),
        "out_val": resid[out_idx].astype(np.int32),
        "out_capacity": np.asarray(max(16, int(n * outlier_frac)), np.int32),
        "anchor": d.reshape(-1).astype(np.int32),
    })
    header = {"format": 1, "codec": CODEC, "version": VERSION,
              "dtype": np.dtype(np.float32).name, "shape": list(shape),
              "params": {"block": list(block), "chunk_size": chunk_size,
                         "eb": eb, "nbins": nbins,
                         "outlier_frac": outlier_frac, "packed": True,
                         "predictor": PREDICTOR, "sub_size": sub_size,
                         "checksum": cusz.payload_crc32(arrays)}}
    return header, arrays


# -- decode -------------------------------------------------------------------

def decode(header: dict, arrays: Dict[str, np.ndarray], *,
           dtype=np.float32) -> Tuple[np.ndarray, Dict[str, int]]:
    """Decompress a packed container, dequantising in `dtype`.  Returns
    (float32 field or None, faults per check)."""
    faults = {k: 0 for k in ("header", "checksum", "lengths", "word_count",
                             "n_valid", "gap_syms", "bad_codeword",
                             "gap_chain", "outliers", "anchor")}
    p = header["params"]
    if (header["codec"], header["version"], header["dtype"]) != (
            CODEC, VERSION, "float32") or not p.get("packed") \
            or p.get("predictor") != PREDICTOR:
        faults["header"] += 1
        return None, faults
    arrays = {k: np.asarray(v) for k, v in arrays.items()}
    if int(p.get("checksum", -1)) != cusz.payload_crc32(arrays):
        faults["checksum"] += 1
    shape = tuple(header["shape"])
    chunk, sub, nbins = int(p["chunk_size"]), int(p["sub_size"]), \
        int(p["nbins"])
    eb = float(p["eb"])
    steps, anchor_shape = plan(shape)
    counts = odd_counts(steps)
    n_sym = max(1, sum(counts))
    nc = -(-n_sym // chunk)

    lengths = arrays["lengths"].astype(np.int64)
    if (lengths.shape != (nbins,) or lengths.max() > cusz.MAXLEN
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
    anchor = arrays["anchor"].astype(np.int64)
    if anchor.shape != (int(np.prod(anchor_shape)),):
        faults["anchor"] += 1
        return None, faults

    codes = cusz._decode_stream(arrays, lengths, n_sym, chunk, sub, faults)
    if codes is None:
        return None, faults
    radius = nbins // 2
    resid = np.where(codes == 0, 0, codes - radius).astype(np.int64)
    oi, ov = arrays["out_idx"].astype(np.int64), arrays["out_val"]
    zero = np.flatnonzero(codes == 0)
    if (oi.shape != zero.shape or oi.shape[0] > int(arrays["out_capacity"])
            or np.any(oi != zero)):
        faults["outliers"] += 1 + abs(oi.shape[0] - zero.shape[0])
    ok = (oi >= 0) & (oi < n_sym)
    resid[oi[ok]] = ov[ok]

    ends = np.cumsum(counts)
    d = anchor.reshape(anchor_shape)
    for (axis, shp), end, k in reversed(list(zip(steps, ends, counts))):
        em = np.moveaxis(d, axis, -1)
        n_odd = shp[axis] // 2
        r = resid[end - k:end].reshape(em.shape[:-1] + (n_odd,))
        full = np.empty(em.shape[:-1] + (shp[axis],), np.int64)
        full[..., 0::2] = em
        full[..., 1::2] = r + predict(em, n_odd)
        d = np.moveaxis(full, -1, axis)
    y = (d.astype(dtype) * dtype(2.0 * eb)).astype(np.float32)
    return y, faults
