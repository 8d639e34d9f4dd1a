"""End-to-end compression pipeline as a staged composition:
one `Predictor` + one `Encoder` (see `repro.core.stages`).

`CompressorConfig.predictor` / `.encoder` pick the stages by registry id
("lorenzo"+"huffman" is the paper's cuSZ pipeline and the default; the
"interp" predictor and "bitshuffle" encoder compose into the cusz-i and
fz codecs with no pipeline changes).  Every hot stage routes through the
`repro.kernels` ops layer, so the same pipeline runs the XLA reference
impls, the interpret-mode Pallas kernels (CI parity), or the compiled
Pallas kernels (TPU/GPU), selected by the dispatch policy:
`CompressorConfig.kernel_impl`, overridden by the `REPRO_KERNEL_IMPL`
env var or a `kernels.dispatch.kernel_policy` context.  The policy is
resolved to a static `PipelinePolicy` outside jit, so each policy gets
its own compiled executable.

Two equivalent surfaces:

* The generic dict surface (`StagedPipeline`, `staged_compress` /
  `staged_decompress`): stage payloads are flat dicts of arrays — the
  union of the predictor's and encoder's disjoint key sets — packed and
  unpacked per stage.  Any predictor x encoder composition works here.
* The `CompressedBlob` surface (`compress` / `decompress`, `pack_blob` /
  `unpack_blob`): the historical named-tuple form whose fields are the
  lorenzo/interp + huffman payload keys.  This is the cusz container
  format; it is byte-identical to the pre-staged pipeline (golden-
  fixture tested) and remains the API of the ratio/throughput tooling.

`compress` / `decompress` are jittable for fixed (shape, config,
policy); payloads are pytrees of device arrays so they can live
on-device (e.g. checkpoint write path) or be pulled to host for storage.

Compressed-size accounting matches the paper's: Huffman bitstream (word
aligned per chunk) + sparse outliers + codebook (bitlengths suffice to
rebuild the canonical book) + the per-subchunk gap arrays that make the
decode parallel (Rivera et al., arXiv 2201.09118) + O(1) header (+ the
interp predictor's anchor grid, when present).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.debug import spans
from repro.kernels import dispatch

from . import dualquant as dq
from . import huffman as hf
from . import stages


@dataclasses.dataclass(frozen=True)
class CompressorConfig:
    eb: float = 1e-4                 # absolute error bound (see eb_mode)
    eb_mode: str = "abs"             # "abs" | "valrel" (relative to range)
    nbins: int = 1024                # quantization bins (paper default)
    chunk_size: int = 4096           # encoder chunk (symbols)
    sub_size: int = 128              # gap-array subchunk (symbols); the
    #   parallel decode unit — must divide chunk_size
    block: Optional[Tuple[int, ...]] = None   # Lorenzo block; None = paper default
    outlier_frac: float = 0.10       # sparse outlier capacity fraction
    use_tpu_blocks: bool = False     # lane-aligned blocks (beyond-paper)
    kernel_impl: Optional[str] = None  # dispatch default: "auto" | "jax" |
    #   "pallas" | "pallas-interpret"; None defers to the ambient policy
    predictor: str = "lorenzo"       # stage registry id (core.stages)
    encoder: str = "huffman"         # stage registry id (core.stages)

    def block_for(self, ndim: int) -> Tuple[int, ...]:
        if self.block is not None:
            return self.block
        table = dq.TPU_BLOCKS if self.use_tpu_blocks else dq.DEFAULT_BLOCKS
        if ndim <= 3:
            return table[ndim]
        # >3D (e.g. QMCPACK 4D): block the trailing 3 dims (paper treats
        # the leading dim as a batch of 3D fields)
        return (1,) * (ndim - 3) + table[3]


class CompressedBlob(NamedTuple):
    words: jax.Array         # [nc, chunk] uint32 deflated bitstream
    bits_used: jax.Array     # [nc] int32
    n_valid: jax.Array       # [nc] int32 symbols per chunk
    lengths: jax.Array       # [k] int32 codeword bitlengths (rebuilds book)
    out_idx: jax.Array       # [cap] int32 outlier flat indices (-1 fill)
    out_val: jax.Array       # [cap] int32 outlier deltas
    n_outliers: jax.Array    # scalar int32
    max_len: jax.Array       # scalar int32 practical max codeword length
    # gap arrays (None on format-v1 blobs, which decode sequentially):
    gap_bits: Optional[jax.Array] = None   # [nc, n_sub] int32 bit offset at
    #   every sub_size-symbol boundary (phase-1 of the two-phase decode)
    gap_syms: Optional[jax.Array] = None   # [nc, n_sub] int32 valid symbols
    #   before each boundary
    # interp-predictor anchor grid (None for the lorenzo predictor):
    anchor: Optional[jax.Array] = None     # [n_anchor] int32
    # Pallas lorenzo kernel: int32 [tiles holding an outlier, tiles];
    # counted by pack_blob, never stored, not passed to decompress
    outlier_tiles: Optional[jax.Array] = None


@jax.jit
def _eb_stats(data: jax.Array) -> jax.Array:
    """min, max, max|d| as ONE fused reduction -> one [3] device array.
    One dispatch + one device_get per compress call (the previous form
    issued two separate blocking reductions)."""
    with jax.named_scope("stage.eb_stats"):
        f = data.astype(jnp.float32)
        return jnp.stack([jnp.min(f), jnp.max(f), jnp.max(jnp.abs(f))])


def resolve_eb(cfg: CompressorConfig, data) -> float:
    with spans.span("codec.resolve_eb"):
        stats = _eb_stats(data)
        spans.count_sync(stats)
        # repro-lint: allow[host-sync] single fused 3-stat reduction; the
        # eb must be a host float (jit cache key) before compression starts
        dmin, dmax, amax = (float(v) for v in
                            np.asarray(jax.device_get(stats)))
    if cfg.eb_mode == "abs":
        eb = float(cfg.eb)
    else:
        rng = dmax - dmin
        eb = float(cfg.eb) * (rng if rng > 0 else 1.0)
    # fp32/int32 domain guard (paper stores d° in FP for the same reason):
    # d° = d/(2eb) must stay within exact-integer float32/int32 range,
    # otherwise the bound is unrepresentable in fp32 to begin with.
    if amax > 0 and amax / (2 * eb) >= 2 ** 23:
        raise ValueError(
            f"error bound {eb:g} is below float32 resolution for data with "
            f"max |d|={amax:g} (d° would exceed 2^23); choose eb >= "
            f"{amax / 2 ** 24:g}")
    return eb


# shared shape metadata now lives with the stage protocols
_shape_meta = stages.shape_meta


# ---------------------------------------------------------------------------
# Generic staged pipeline (dict payloads, any predictor x encoder)
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("cfg", "eb", "pp"))
def _staged_compress_impl(data: jax.Array, cfg: CompressorConfig, eb: float,
                          pp: dispatch.PipelinePolicy) -> dict:
    pred = stages.get_predictor(cfg.predictor)
    enc = stages.get_encoder(cfg.encoder)
    codes, ppay = pred.predict(data, cfg, eb, pp)
    epay = enc.encode(codes, cfg, pp)
    return {**epay, **ppay}


@partial(jax.jit, static_argnames=("cfg", "eb", "shape", "static_meta",
                                   "pp"))
def _staged_decompress_impl(payload: dict, aux, cfg: CompressorConfig,
                            eb: float, shape: Tuple[int, ...],
                            static_meta: Tuple, pp: dispatch.PipelinePolicy
                            ) -> jax.Array:
    pred = stages.get_predictor(cfg.predictor)
    enc = stages.get_encoder(cfg.encoder)
    codes = enc.decode(payload, aux, static_meta, cfg, pp)
    return pred.reconstruct(codes, payload, cfg, eb, shape, pp)


def staged_compress(data: jax.Array, cfg: CompressorConfig
                    ) -> Tuple[dict, float]:
    """Generic staged compress.  Returns (payload dict, resolved abs eb)."""
    eb = resolve_eb(cfg, data)
    pp = dispatch.pipeline_policy(cfg.kernel_impl)
    with spans.span("codec.dispatch"):
        payload = _staged_compress_impl(data, cfg, eb, pp)
    stages.get_predictor(cfg.predictor).record_call(data.shape, cfg, pp)
    return payload, eb


def staged_decompress(payload: dict, cfg: CompressorConfig, eb: float,
                      shape: Tuple[int, ...]) -> jax.Array:
    """Generic staged decompress of a (device-form) payload dict."""
    # a fresh payload and an unpacked one share one decompress program
    payload = {k: v for k, v in payload.items() if k != "outlier_tiles"}
    enc = stages.get_encoder(cfg.encoder)
    static_meta, aux = enc.decode_meta(payload, cfg)
    pp = dispatch.pipeline_policy(cfg.kernel_impl)
    with spans.span("codec.dispatch"):
        out = _staged_decompress_impl(payload, aux, cfg, eb, tuple(shape),
                                      static_meta, pp)
    enc.record_call(payload, pp)
    return out


@dataclasses.dataclass(frozen=True)
class StagedPipeline:
    """A concrete predictor + encoder composition with the host-side
    storage/validity surface codecs build on (`codecs.fz` is the
    reference consumer; `codecs.cusz` keeps the CompressedBlob form of
    the same composition for container-format stability)."""
    predictor: stages.Predictor
    encoder: stages.Encoder

    @staticmethod
    def from_cfg(cfg: CompressorConfig) -> "StagedPipeline":
        return StagedPipeline(stages.get_predictor(cfg.predictor),
                              stages.get_encoder(cfg.encoder))

    def compress(self, data: jax.Array, cfg: CompressorConfig
                 ) -> Tuple[dict, float]:
        return staged_compress(data, cfg)

    def decompress(self, payload: dict, cfg: CompressorConfig, eb: float,
                   shape: Tuple[int, ...]) -> jax.Array:
        return staged_decompress(payload, cfg, eb, shape)

    def valid(self, payload: dict) -> bool:
        return self.predictor.valid(payload)

    # -- storage boundary (host) -------------------------------------------
    def pack(self, payload: dict) -> dict:
        spans.count_sync(payload)
        # repro-lint: allow[host-sync] pack() is the storage boundary
        host = jax.device_get(payload)
        pkeys = set(self.predictor.payload_keys)
        ppart = {k: v for k, v in host.items() if k in pkeys}
        epart = {k: v for k, v in host.items() if k not in pkeys}
        return {**self.encoder.pack_payload(epart),
                **self.predictor.pack_payload(ppart)}

    def unpack(self, packed: dict, cfg: CompressorConfig,
               shape: Tuple[int, ...]) -> dict:
        n_sym = self.predictor.n_codes(tuple(shape), cfg)
        d = dict(self.encoder.unpack_payload(packed, cfg, n_sym))
        d.update(self.predictor.unpack_payload(packed, cfg, tuple(shape)))
        return {k: jnp.asarray(v) for k, v in d.items()}

    def stored_nbytes(self, packed: dict) -> int:
        return (self.encoder.stored_nbytes(packed)
                + self.predictor.stored_nbytes(packed) + HEADER_BYTES)


# ---------------------------------------------------------------------------
# CompressedBlob surface (cusz container format; bit-identical to the
# pre-staged pipeline)
# ---------------------------------------------------------------------------

def _blob_from_payload(payload: dict) -> CompressedBlob:
    return CompressedBlob(**{f: payload.get(f)
                             for f in CompressedBlob._fields})


@partial(jax.jit, static_argnames=("cfg", "eb", "pp"))
def _compress_impl(data: jax.Array, cfg: CompressorConfig, eb: float,
                   pp: dispatch.PipelinePolicy) -> CompressedBlob:
    return _blob_from_payload(_staged_compress_impl(data, cfg, eb, pp))


def compress(data: jax.Array, cfg: CompressorConfig) -> Tuple[CompressedBlob, float]:
    """Returns (blob, resolved_abs_eb)."""
    if cfg.encoder != "huffman":
        raise ValueError(
            f"the CompressedBlob surface encodes the huffman payload "
            f"layout; encoder {cfg.encoder!r} needs staged_compress()")
    eb = resolve_eb(cfg, data)
    pp = dispatch.pipeline_policy(cfg.kernel_impl)
    with spans.span("codec.dispatch"):
        blob = _compress_impl(data, cfg, eb, pp)
    stages.get_predictor(cfg.predictor).record_call(data.shape, cfg, pp)
    return blob, eb


@partial(jax.jit, static_argnames=("cfg", "eb", "shape", "max_len_static",
                                   "pp"))
def _decompress_impl(blob: CompressedBlob, table: hf.DecodeTable,
                     cfg: CompressorConfig, eb: float,
                     shape: Tuple[int, ...], max_len_static: int,
                     pp: dispatch.PipelinePolicy) -> jax.Array:
    payload = {f: v for f, v in zip(CompressedBlob._fields, blob)
               if v is not None}
    pred = stages.get_predictor(cfg.predictor)
    enc = stages.get_encoder(cfg.encoder)
    codes = enc.decode(payload, table, (max_len_static,), cfg, pp)
    return pred.reconstruct(codes, payload, cfg, eb, shape, pp)


def decompress(blob: CompressedBlob, cfg: CompressorConfig, eb: float,
               shape: Tuple[int, ...]) -> jax.Array:
    enc = stages.get_encoder(cfg.encoder)
    static_meta, table = enc.decode_meta(
        {"max_len": blob.max_len, "lengths": blob.lengths}, cfg)
    pp = dispatch.pipeline_policy(cfg.kernel_impl)
    # a fresh blob and an unpacked one share one decompress program
    blob = blob._replace(outlier_tiles=None)
    with spans.span("codec.dispatch"):
        out = _decompress_impl(blob, table, cfg, eb, tuple(shape),
                               static_meta[0], pp)
    enc.record_call(blob._asdict(), pp)
    return out


# ---------------------------------------------------------------------------
# Size accounting / ratio
# ---------------------------------------------------------------------------

HEADER_BYTES = 64


def compressed_bytes(blob: CompressedBlob, nbins: int) -> int:
    spans.count_sync(blob.bits_used)
    # repro-lint: allow[host-sync] ratio reporting is a host-side metric
    bits = np.asarray(jax.device_get(blob.bits_used), dtype=np.int64)
    stream = int(np.sum((bits + 31) // 32) * 4)
    spans.count_sync(blob.n_outliers)
    n_out = int(jax.device_get(blob.n_outliers))  # repro-lint: allow[host-sync] ratio reporting

    outliers = n_out * 8                       # (idx, delta) int32 pairs
    book = nbins                               # 1 B bitlength per symbol
    gaps = 0
    if blob.gap_bits is not None:              # 4 B bit + 2 B symbol offset
        gaps = blob.gap_bits.size * 4 + blob.gap_syms.size * 2
    anchor = 0 if blob.anchor is None else blob.anchor.size * 4
    return stream + outliers + book + gaps + anchor + HEADER_BYTES


def compression_ratio(data: jax.Array, blob: CompressedBlob, nbins: int) -> float:
    raw = data.size * data.dtype.itemsize
    return raw / compressed_bytes(blob, nbins)


def roundtrip(data: jax.Array, cfg: CompressorConfig):
    """compress -> decompress; returns (recon, blob, eb, ratio)."""
    blob, eb = compress(data, cfg)
    recon = decompress(blob, cfg, eb, tuple(data.shape))
    return recon, blob, eb, compression_ratio(data, blob, cfg.nbins)


# ---------------------------------------------------------------------------
# Host-side packing for storage: keep only the used words per chunk (the
# device blob keeps a dense [nc, chunk] buffer for fixed shapes; storing
# that verbatim would waste the saved ratio).  Delegated to the stage
# pack/unpack implementations (stages.HuffmanEncoder carries the
# vectorized word packing); output keys are unchanged from the
# pre-staged pipeline, so stored cusz v2 payloads are bit-identical.
# ---------------------------------------------------------------------------

def pack_blob(blob: CompressedBlob) -> dict:
    with spans.span("codec.pack.d2h"):
        spans.count_sync(blob)
        # repro-lint: allow[host-sync] pack_blob() is the storage boundary
        b = jax.device_get(blob)
    payload = {f: v for f, v in zip(CompressedBlob._fields, b)
               if v is not None}
    with spans.span("codec.pack.words"):
        d = stages.get_encoder("huffman").pack_payload(payload)
        d.update(stages._pack_outliers(payload))
    if payload.get("anchor") is not None:
        d["anchor"] = np.asarray(payload["anchor"], np.int32)
    return d


def packed_nbytes(d: dict) -> int:
    return sum(np.asarray(v).nbytes for v in d.values())


def unpack_blob(d: dict) -> CompressedBlob:
    with spans.span("codec.unpack.words"):
        enc = stages.get_encoder("huffman").unpack_payload(d, None, None)
        out = stages._unpack_outliers(d)
    payload = {**enc, **out}
    if d.get("anchor") is not None:
        payload["anchor"] = np.asarray(d["anchor"], np.int32)
    with spans.span("codec.unpack.h2d"):
        return CompressedBlob(**{
            f: (jnp.asarray(payload[f]) if payload.get(f) is not None
                else None)
            for f in CompressedBlob._fields})
