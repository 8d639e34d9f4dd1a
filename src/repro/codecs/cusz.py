"""The cuSZ pipeline (dual-quant + canonical Huffman) behind the `Codec`
protocol.

`encode` resolves the error bound (valrel -> abs) on the host, runs the
jitted pipeline (kernel dispatch policy threaded via
`CompressorConfig.kernel_impl` / the ambient `kernels.dispatch` policy),
and records every decode-side parameter in the header: the resolved abs
eb, nbins, chunk size, the resolved Lorenzo block and the outlier
capacity fraction.  The source dtype/shape ride in the header too, so a
bf16 tensor comes back as bf16 — the historical `(packed, eb)` +
caller-side shape/dtype plumbing is gone.

`pack` switches the payload to the per-chunk word-packed host form
(`compressor.pack_blob`); `decode` accepts either form.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import compressor as CZ
from repro.debug import spans

from .base import Codec, register
from .container import Container, stamp_checksum


@dataclasses.dataclass(frozen=True)
class CuszCodec(Codec):
    cfg: CZ.CompressorConfig = CZ.CompressorConfig()
    name = "cusz"
    # v2: payload carries the per-subchunk gap arrays (gap_bits/gap_syms)
    # + sub_size in the header, enabling the parallel two-phase inflate;
    # gap-less v1 containers still decode via the sequential path
    version = 2
    # Lorenzo prediction crosses slice boundaries: encoding slices
    # independently changes the decode, so sharded saves keep each
    # leaf whole on one owner shard.
    shardable = False

    @staticmethod
    def make(cfg: Optional[CZ.CompressorConfig] = None, **kw) -> "CuszCodec":
        if cfg is None:
            cfg = CZ.CompressorConfig(**kw)
        elif kw:
            cfg = dataclasses.replace(cfg, **kw)
        return CuszCodec(cfg=cfg)

    # -- protocol -----------------------------------------------------------
    def encode(self, x, *, cfg: Optional[CZ.CompressorConfig] = None
               ) -> Container:
        c = cfg if cfg is not None else self.cfg
        x32 = jnp.asarray(x, jnp.float32) \
            if jnp.asarray(x).dtype != jnp.float32 else jnp.asarray(x)
        blob, eb = CZ.compress(x32, c)
        # "predictor" is recorded only when non-default so lorenzo headers
        # stay bit-identical to every container written before stages
        extra = {} if c.predictor == "lorenzo" else {"predictor": c.predictor}
        header = self._header(
            x, eb=float(eb), nbins=int(c.nbins), chunk_size=int(c.chunk_size),
            sub_size=int(c.sub_size), block=tuple(c.block_for(x32.ndim)),
            outlier_frac=float(c.outlier_frac), **extra)
        return Container(header, _blob_payload(blob))

    def decode(self, c: Container, *, like=None) -> jax.Array:
        c = self.unpack(c)
        h = c.header
        cfg = self._decode_cfg(h)
        blob = _payload_blob(c.payload, asarray=True)
        y = CZ.decompress(blob, cfg, float(h.param("eb")), h.shape)
        return self._finish(y, h, like)

    # -- storage form: per-chunk word packing -------------------------------
    def pack(self, c: Container) -> Container:
        if c.header.param("packed"):
            return c
        blob = _payload_blob(c.payload)
        return stamp_checksum(Container(c.header.with_params(packed=True),
                                        CZ.pack_blob(blob)))

    def unpack(self, c: Container) -> Container:
        if not c.header.param("packed"):
            return c
        blob = CZ.unpack_blob(dict(c.payload))
        return Container(
            c.header.with_params(packed=False).without_params("checksum"),
            _blob_payload(blob))

    def valid(self, c: Container) -> bool:
        """False when the sparse outlier store overflowed its capacity
        (the blob would decode lossily beyond the bound)."""
        if c.header.param("packed"):
            return True                       # pack() is post-validation
        spans.count_sync(c.payload["n_outliers"])
        # repro-lint: allow[host-sync] one scalar readback per validity check
        n_out = int(jax.device_get(c.payload["n_outliers"]))
        return n_out <= int(c.payload["out_idx"].shape[0])

    # -- helpers ------------------------------------------------------------
    def _decode_cfg(self, h) -> CZ.CompressorConfig:
        return CZ.CompressorConfig(
            eb=float(h.param("eb")), eb_mode="abs",
            nbins=int(h.param("nbins")),
            chunk_size=int(h.param("chunk_size")),
            # v1 headers predate the gap arrays; the default is inert
            # there (a gap-less blob decodes sequentially regardless)
            sub_size=int(h.param("sub_size", 128)),
            block=tuple(h.param("block")),
            outlier_frac=float(h.param("outlier_frac")),
            predictor=str(h.param("predictor", "lorenzo")),
            kernel_impl=self.cfg.kernel_impl)


def _blob_payload(blob: CZ.CompressedBlob) -> dict:
    """Blob -> payload dict; None fields (gap-less v1 blobs) are omitted
    so the payload stays an arrays-only mapping."""
    return {f: v for f, v in zip(CZ.CompressedBlob._fields, blob)
            if v is not None}


def _payload_blob(payload, asarray: bool = False) -> CZ.CompressedBlob:
    """Payload dict -> blob; gap fields absent on v1 payloads stay None."""
    conv = jnp.asarray if asarray else (lambda v: v)
    return CZ.CompressedBlob(**{
        f: conv(payload[f]) if f in payload else None
        for f in CZ.CompressedBlob._fields})


register("cusz", CuszCodec.make)
