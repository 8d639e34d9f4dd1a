"""Staged compression pipeline: `Predictor` and `Encoder` stage protocols
with string-keyed registries mirroring `repro.codecs.base`.

The cuSZ pipeline decomposes into two orthogonal stages:

  Predictor  lossy-maps a float field to integer quant codes (plus a
             sparse exact side channel for out-of-cap residuals) and
             reconstructs the field from them within the error bound.
  Encoder    losslessly encodes the quant-code stream to a compact
             payload and decodes it back bit-exactly.

`core.compressor.StagedPipeline` composes one of each under the existing
`CompressorConfig` / dispatch machinery; `CompressorConfig.predictor` /
`.encoder` select the stages by registry id.  Registered stages:

  predictors  "lorenzo"    blocked first-difference (paper §3.1)
              "interp"     multi-level cubic interpolation (cuSZ-i,
                           arXiv 2312.05492) — `core.interp`
  encoders    "huffman"    canonical Huffman + gap-array deflate (§3.2)
              "bitshuffle" bit-plane shuffle + zero-plane elision
                           (FZ-GPU, arXiv 2304.12557) — `core.bitplane`

Stage methods that run inside the jitted pipeline (`predict`,
`reconstruct`, `encode`, `decode`) receive the static
`dispatch.PipelinePolicy` and route every hot kernel through
`repro.kernels.*.ops`; each stage declares its kernel names in
`kernels` so repro-lint R4 can statically tie the stage to its
jax-reference + Pallas registrations.  Host-only methods (`decode_meta`,
`pack_payload`, `unpack_payload`, `stored_nbytes`, `valid`) handle the
jit-boundary readbacks and the storage form.

Payloads are flat dicts of arrays; a predictor's and an encoder's key
sets are disjoint, so the composed pipeline payload is their union.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.debug import spans
from repro.kernels import dispatch
from repro.kernels.deflate import ops as deflate_ops
from repro.kernels.encode import ops as encode_ops
from repro.kernels.histogram import ops as hist_ops
from repro.kernels.inflate import kernel as inflate_kernel
from repro.kernels.inflate import ops as inflate_ops
from repro.kernels.lorenzo import ops as lorenzo_ops

from . import dualquant as dq
from . import huffman as hf

Payload = Dict[str, jax.Array]


# ---------------------------------------------------------------------------
# Stage protocols
# ---------------------------------------------------------------------------

class Predictor:
    """Lossy prediction stage: float field <-> integer quant codes.

    Implementations are stateless singletons (all per-field knobs ride in
    `CompressorConfig`), hashable by identity, so an instance is a valid
    jit static argument.
    """
    name: str = "abstract"
    #: dispatch kernel names this stage routes through (repro-lint R4
    #: checks each is registered by a kernels/<op>/ops.py)
    kernels: Tuple[str, ...] = ()
    #: payload keys this stage owns (disjoint from any encoder's)
    payload_keys: Tuple[str, ...] = ()

    def n_codes(self, shape: Tuple[int, ...], cfg) -> int:
        """Static quant-code count for a field of `shape` (the encoder
        contract: `predict` emits exactly this many symbols in row-major
        order; `reconstruct` consumes `codes_flat[:n_codes]`)."""
        raise NotImplementedError

    def predict(self, data: jax.Array, cfg, eb: float,
                pp: dispatch.PipelinePolicy) -> Tuple[jax.Array, Payload]:
        """data -> (quant codes, predictor payload).  Traced (inside jit).

        Codes may be any shape with `n_codes` elements; code 0 is the
        OUTLIER sentinel, in-cap codes are >= 1 (`dq.postquant_codes`).
        """
        raise NotImplementedError

    def reconstruct(self, codes_flat: jax.Array, payload: Payload, cfg,
                    eb: float, shape: Tuple[int, ...],
                    pp: dispatch.PipelinePolicy) -> jax.Array:
        """(decoded flat codes [>= n_codes], payload) -> float32 field.
        Traced (inside jit)."""
        raise NotImplementedError

    def record_call(self, shape: Tuple[int, ...], cfg,
                    pp: dispatch.PipelinePolicy) -> None:
        """Host-side counters of one compress call, from the static shape
        and policy (no device read).  Called once per call, outside jit."""

    def header_params(self, shape: Tuple[int, ...], cfg) -> Dict[str, Any]:
        """Decode-side parameters a codec should record in its header."""
        return {}

    def valid(self, payload: Payload) -> bool:
        """Host-side post-encode validity check (e.g. outlier overflow)."""
        return True

    def pack_payload(self, payload: Dict[str, np.ndarray]
                     ) -> Dict[str, np.ndarray]:
        """Device payload (host-fetched) -> compact storage arrays."""
        return dict(payload)

    def unpack_payload(self, packed: Dict[str, np.ndarray], cfg,
                       shape: Tuple[int, ...]) -> Dict[str, np.ndarray]:
        """Inverse of `pack_payload` (dense, decode-ready arrays)."""
        return dict(packed)

    def stored_nbytes(self, packed: Dict[str, np.ndarray]) -> int:
        """Accounted storage bytes of this stage's packed payload."""
        return sum(int(np.asarray(packed[k]).nbytes) for k in packed)


class Encoder:
    """Lossless quant-code encoding stage (same singleton contract)."""
    name: str = "abstract"
    kernels: Tuple[str, ...] = ()
    payload_keys: Tuple[str, ...] = ()

    def encode(self, codes: jax.Array, cfg,
               pp: dispatch.PipelinePolicy) -> Payload:
        """Quant codes (any shape, row-major symbol order) -> payload.
        Traced (inside jit)."""
        raise NotImplementedError

    def decode_meta(self, payload: Payload, cfg
                    ) -> Tuple[Tuple[Any, ...], Any]:
        """Host-side decode preparation, OUTSIDE the jitted decode.

        Returns (static_meta, aux): `static_meta` is a hashable tuple of
        jit-static decode parameters (may require a host readback — e.g.
        Huffman's practical max codeword length); `aux` is a pytree of
        device arrays derived from the payload (e.g. the cached decode
        table).  Both feed `decode`.
        """
        return ((), None)

    def decode(self, payload: Payload, aux: Any,
               static_meta: Tuple[Any, ...], cfg,
               pp: dispatch.PipelinePolicy) -> jax.Array:
        """payload -> flat int32 codes (padded to the encoder's chunk
        granularity; callers slice `[:n_codes]`).  Traced (inside jit)."""
        raise NotImplementedError

    def record_call(self, payload: Payload,
                    pp: dispatch.PipelinePolicy) -> None:
        """Host-side counters of one decompress call, from the payload's
        static shapes and the policy (no device read).  Called once per
        call, outside jit."""

    def pack_payload(self, payload: Dict[str, np.ndarray]
                     ) -> Dict[str, np.ndarray]:
        return dict(payload)

    def unpack_payload(self, packed: Dict[str, np.ndarray], cfg,
                       n_sym: int) -> Dict[str, np.ndarray]:
        return dict(packed)

    def stored_nbytes(self, packed: Dict[str, np.ndarray]) -> int:
        return sum(int(np.asarray(packed[k]).nbytes) for k in packed)


# ---------------------------------------------------------------------------
# Registries (mirroring codecs.base: string id -> factory, instantiated
# once — stages are stateless singletons)
# ---------------------------------------------------------------------------

_PREDICTORS: Dict[str, Predictor] = {}
_ENCODERS: Dict[str, Encoder] = {}


def register_predictor(name: str, factory: Callable[[], Predictor]) -> None:
    _PREDICTORS[name] = factory()


def register_encoder(name: str, factory: Callable[[], Encoder]) -> None:
    _ENCODERS[name] = factory()


def get_predictor(name: str) -> Predictor:
    try:
        return _PREDICTORS[name]
    except KeyError:
        raise KeyError(f"unknown predictor {name!r}; registered: "
                       f"{sorted(_PREDICTORS)}") from None


def get_encoder(name: str) -> Encoder:
    try:
        return _ENCODERS[name]
    except KeyError:
        raise KeyError(f"unknown encoder {name!r}; registered: "
                       f"{sorted(_ENCODERS)}") from None


def predictor_names() -> Tuple[str, ...]:
    return tuple(sorted(_PREDICTORS))


def encoder_names() -> Tuple[str, ...]:
    return tuple(sorted(_ENCODERS))


# ---------------------------------------------------------------------------
# Shared shape metadata (formerly compressor._shape_meta)
# ---------------------------------------------------------------------------

def shape_meta(shape: Tuple[int, ...], cfg):
    ndim = len(shape)
    block = cfg.block_for(ndim)
    pshape = dq.padded_shape(shape, block)
    n = int(np.prod(pshape))
    cap = max(16, int(n * cfg.outlier_frac))
    return ndim, block, pshape, n, cap


def outlier_capacity(n: int, cfg) -> int:
    return max(16, int(n * cfg.outlier_frac))


def _outlier_valid(payload: Dict[str, np.ndarray]) -> bool:
    spans.count_sync(payload["n_outliers"])
    # repro-lint: allow[host-sync] one scalar readback per validity check
    n_out = int(jax.device_get(payload["n_outliers"]))
    return n_out <= int(payload["out_idx"].shape[0])


def _pack_outliers(payload: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
    """Trim the fixed-capacity outlier store to its used prefix.  Counts
    the values the predictor sent to the store (`outliers.values`, more
    than it holds where it overflowed) and the outlier tiles it reported
    (`outliers.tiles_hit` of `outliers.tiles`) from this host copy; the
    tiles are not stored."""
    if payload.get("outlier_tiles") is not None:
        hit, tiles = (int(v) for v in payload["outlier_tiles"])
        spans.count("outliers.tiles_hit", hit)
        spans.count("outliers.tiles", tiles)
    n_out = int(payload["n_outliers"])
    spans.count("outliers.values", n_out)
    return {
        "out_idx": np.asarray(payload["out_idx"][:n_out], np.int32),
        "out_val": np.asarray(payload["out_val"][:n_out], np.int32),
        "out_capacity": np.int32(payload["out_idx"].shape[0]),
    }


def _unpack_outliers(packed: Dict[str, np.ndarray]
                     ) -> Dict[str, np.ndarray]:
    cap = int(packed["out_capacity"])
    n_out = len(packed["out_idx"])
    # out-of-range fill: the decode-side scatter (mode="drop") ignores it
    oi = np.full((cap,), 2 ** 31 - 1, np.int32)
    ov = np.zeros((cap,), np.int32)
    oi[:n_out] = packed["out_idx"]
    ov[:n_out] = packed["out_val"]
    return {"out_idx": oi, "out_val": ov,
            "n_outliers": np.int32(n_out)}


# ---------------------------------------------------------------------------
# "lorenzo": the paper's blocked first-difference predictor, ported onto
# the protocol bit-identically (same ops, same order, same payload).
# ---------------------------------------------------------------------------

class LorenzoPredictor(Predictor):
    name = "lorenzo"
    kernels = ("lorenzo.dualquant", "lorenzo.reverse")
    payload_keys = ("out_idx", "out_val", "n_outliers", "outlier_tiles")

    def n_codes(self, shape, cfg) -> int:
        return shape_meta(shape, cfg)[3]

    def predict(self, data, cfg, eb, pp):
        ndim, block, pshape, n, cap = shape_meta(data.shape, cfg)
        with jax.named_scope("stage.blocks"):
            xb = dq.block_split(dq.pad_to_blocks(data, block), block)
        # fused PREQUANT + ℓ-delta + POSTQUANT and the outlier store: one
        # blocked kernel call (the deltas never leave the kernel)
        with jax.named_scope("stage.dualquant"):
            codes, oidx, oval, n_out, tiles = lorenzo_ops.dualquant_blocks(
                xb, eb, cfg.nbins, cap, **pp.for_kernel("lorenzo.dualquant")
                .as_kwargs())
        payload = {"out_idx": oidx, "out_val": oval, "n_outliers": n_out}
        if tiles is not None:
            payload["outlier_tiles"] = tiles
        return codes, payload

    def reconstruct(self, codes_flat, payload, cfg, eb, shape, pp):
        ndim, block, pshape, n, cap = shape_meta(shape, cfg)
        with jax.named_scope("stage.scatter"):
            delta = dq.codes_to_delta(codes_flat[:n], cfg.nbins)
            delta = dq.scatter_outliers(delta, payload["out_idx"],
                                        payload["out_val"])
        nb = tuple(p // b for p, b in zip(pshape, block))
        with jax.named_scope("stage.reverse"):
            recon = lorenzo_ops.reverse_blocks(
                delta.reshape(nb + tuple(block)), eb,
                **pp.for_kernel("lorenzo.reverse").as_kwargs())
        with jax.named_scope("stage.blocks"):
            full = dq.block_merge(recon, block)
            return full[tuple(slice(0, s) for s in shape)]

    def header_params(self, shape, cfg):
        return {"block": tuple(cfg.block_for(len(shape))),
                "outlier_frac": float(cfg.outlier_frac)}

    def valid(self, payload):
        return _outlier_valid(payload)

    def pack_payload(self, payload):
        return _pack_outliers(payload)

    def unpack_payload(self, packed, cfg, shape):
        return _unpack_outliers(packed)

    def stored_nbytes(self, packed):
        # (idx, delta) int32 pairs of the used prefix, as in the paper's
        # sparse outlier accounting
        return len(packed["out_idx"]) * 8


# ---------------------------------------------------------------------------
# "huffman": canonical Huffman + gap-array deflate, ported bit-identically
# (payload keys match CompressedBlob field names so the cusz v2 container
# format is unchanged).
# ---------------------------------------------------------------------------

class HuffmanEncoder(Encoder):
    name = "huffman"
    kernels = ("histogram", "encode", "deflate", "inflate")
    payload_keys = ("words", "bits_used", "n_valid", "lengths", "max_len",
                    "gap_bits", "gap_syms")

    def encode(self, codes, cfg, pp):
        with jax.named_scope("stage.histogram"):
            hist = hist_ops.histogram(
                codes, cfg.nbins, **pp.for_kernel("histogram").as_kwargs())
        with jax.named_scope("stage.codebook"):
            lengths = hf.codeword_lengths(hist)
            cb = hf.canonical_codebook(lengths)
        with jax.named_scope("stage.encode"):
            cw, bw = encode_ops.encode(codes, cb,
                                       **pp.for_kernel("encode").as_kwargs())
        with jax.named_scope("stage.deflate"):
            words, bits, gap_bits, gap_syms = deflate_ops.deflate(
                cw, bw, cfg.chunk_size, cfg.sub_size,
                **pp.for_kernel("deflate").as_kwargs())
            nc = words.shape[0]
            n_valid = jnp.minimum(
                jnp.full((nc,), cfg.chunk_size, jnp.int32),
                jnp.maximum(codes.size - jnp.arange(nc, dtype=jnp.int32)
                            * cfg.chunk_size, 0))
        return {"words": words, "bits_used": bits, "n_valid": n_valid,
                "lengths": lengths, "max_len": cb.max_len,
                "gap_bits": gap_bits, "gap_syms": gap_syms}

    def decode_meta(self, payload, cfg):
        with spans.span("codec.decode_meta"):
            spans.count_sync(payload["max_len"])
            # repro-lint: allow[host-sync] max_len picks the LUT-vs-bitscan
            # decode variant, a static jit arg; one readback per decode
            max_len = int(jax.device_get(payload["max_len"]))
            # bucket the static max length (8/12/16/32) so decode compiles
            # once per bucket, not once per field's exact max codeword
            # length
            ml_b = hf.bucket_max_len(max(1, max_len))
            # decode tables built OUTSIDE the jitted decode, cached per book
            table = hf.decode_table(payload["lengths"], ml_b)
        return (ml_b,), table

    def decode(self, payload, aux, static_meta, cfg, pp):
        (ml_b,) = static_meta
        with jax.named_scope("stage.inflate"):
            return inflate_ops.inflate(
                payload["words"], payload["bits_used"], payload["n_valid"],
                aux, ml_b, gaps=payload.get("gap_bits"),
                **pp.for_kernel("inflate").as_kwargs()).reshape(-1)

    def record_call(self, payload, pp):
        gaps = payload.get("gap_bits")
        if gaps is None:
            return
        nc, chunk = payload["words"].shape
        sub = chunk // gaps.shape[1]
        r = inflate_ops.for_shape(pp.for_kernel("inflate"), chunk, sub)
        if r.impl == "pallas":
            spans.count("inflate.walk_steps",
                        inflate_kernel.walk_steps(nc, chunk, sub))

    def pack_payload(self, payload):
        bits = np.asarray(payload["bits_used"], dtype=np.int64)
        words = np.asarray(payload["words"])
        chunk_ids, cols = _packed_coords(bits)
        d = {
            "words_packed": words[chunk_ids, cols].astype(np.uint32),
            "bits_used": np.asarray(payload["bits_used"], np.int32),
            "n_valid": np.asarray(payload["n_valid"], np.int32),
            "lengths": np.asarray(payload["lengths"], np.uint8),
            "max_len": np.asarray(payload["max_len"], np.int32),
            "chunk_words": np.int32(words.shape[1]),
        }
        if payload.get("gap_bits") is not None:
            d["gap_bits"] = np.asarray(payload["gap_bits"], np.int32)
            # symbol offsets are < chunk_size; u16 when that fits
            sdt = np.uint16 if words.shape[1] <= (1 << 16) else np.int32
            d["gap_syms"] = np.asarray(payload["gap_syms"]).astype(sdt)
        return d

    def unpack_payload(self, packed, cfg, n_sym):
        bits = np.asarray(packed["bits_used"], np.int64)
        nc = bits.shape[0]
        cw = int(packed["chunk_words"])
        words = np.zeros((nc, cw), np.uint32)
        chunk_ids, cols = _packed_coords(bits)
        words[chunk_ids, cols] = np.asarray(packed["words_packed"],
                                            np.uint32)
        d = {"words": words,
             "bits_used": np.asarray(packed["bits_used"], np.int32),
             "n_valid": np.asarray(packed["n_valid"], np.int32),
             "lengths": np.asarray(packed["lengths"], np.int32),
             "max_len": np.asarray(packed["max_len"], np.int32)}
        if packed.get("gap_bits") is not None:
            d["gap_bits"] = np.asarray(packed["gap_bits"], np.int32)
            d["gap_syms"] = np.asarray(packed["gap_syms"], np.int32)
        return d

    def stored_nbytes(self, packed):
        bits = np.asarray(packed["bits_used"], dtype=np.int64)
        stream = int(np.sum((bits + 31) // 32) * 4)
        book = len(packed["lengths"])          # 1 B bitlength per symbol
        gaps = 0
        if packed.get("gap_bits") is not None:
            gaps = (np.asarray(packed["gap_bits"]).size * 4
                    + np.asarray(packed["gap_syms"]).size * 2)
        return stream + book + gaps


def _packed_coords(bits: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """(chunk_id, in-chunk column) of every used word, packed order."""
    nwords = (bits + 31) // 32                       # [nc]
    chunk_ids = np.repeat(np.arange(bits.shape[0]), nwords)
    starts = np.cumsum(nwords) - nwords              # packed offset per chunk
    cols = np.arange(int(nwords.sum())) - np.repeat(starts, nwords)
    return chunk_ids, cols


register_predictor("lorenzo", LorenzoPredictor)
register_encoder("huffman", HuffmanEncoder)

# Populate the rest of the registry: sibling stage modules register on
# import (they import this module for the protocol, so the imports live
# at the bottom — the standard registry-population idiom, mirroring
# codecs/__init__).
from . import interp as _interp          # noqa: E402,F401  (registers "interp")
from . import bitplane as _bitplane      # noqa: E402,F401  (registers "bitshuffle")
