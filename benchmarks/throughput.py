"""Paper Table 7: per-stage throughput breakdown of the full pipeline —
now swept over the kernel-dispatch IMPL AXIS:

  jax               XLA reference impls (the pre-dispatch baseline)
  pallas-interpret  Pallas kernels in interpret mode (route validation;
                    its absolute timings are NOT a perf claim on CPU)
  pallas            compiled Pallas kernels (added automatically when the
                    backend is tpu/gpu)

The stage axis is DERIVED from the configured pipeline: each benchmarked
kernel row comes from the predictor's and encoder's declared ``kernels``
tuples (``core.stages`` registries), so a new stage composition gets its
rows without touching this file — no hard-coded stage list to go stale.
The lorenzo+huffman composition additionally keeps its historical rows
(`dualquant_unfused`, `codebook`, `inflate_seq`, the jitted
compress/decompress totals) and historical short stage names
(``dualquant`` for ``lorenzo.dualquant`` etc.) so the perf trajectory
stays comparable across runs.  A second sweep times the cusz-i and fz
stage compositions end to end (``pipeline_compress``/
``pipeline_decompress`` rows).

CPU wall-clock numbers are *relative* signals (DESIGN.md §9); the TPU
story is the roofline.  Emits CSV lines on stdout and writes
BENCH_throughput.json records: {stage, field, impl, seconds, GBps}.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import compressor as C, dualquant as dq, huffman as hf
from repro.core import interp as interp_mod
from repro.core import stages
from repro.data import scidata
from repro.kernels import dispatch
from repro.kernels.bitshuffle import ops as bitshuffle_ops
from repro.kernels.deflate import ops as deflate_ops
from repro.kernels.encode import ops as encode_ops
from repro.kernels.histogram import ops as hist_ops
from repro.kernels.inflate import ops as inflate_ops
from repro.kernels.interp import ops as interp_ops
from repro.kernels.lorenzo import ops as lorenzo_ops
from .common import emit, timeit, write_json

JSON_NAME = "BENCH_throughput.json"

#: historical short row names for the original six pipeline stages (the
#: CI trend lines key on these); new stage kernels report under their
#: registry key verbatim
_SHORT = {v: k for k, v in dispatch._LEGACY_FIELDS.items()}


def _impl_axis() -> List[str]:
    impls = ["jax", "pallas-interpret"]
    if jax.default_backend() in ("tpu", "gpu", "cuda", "rocm"):
        impls.append("pallas")
    return impls


def _fields(small: bool) -> Dict[str, np.ndarray]:
    if small:
        return {
            "hacc": scidata.hacc_like(1 << 16),
            "cesm": scidata.cesm_like((90, 180)),
            "hurricane": scidata.hurricane_like((10, 50, 50)),
            "nyx": scidata.nyx_like((32, 32, 32)),
        }
    return {
        "hacc": scidata.hacc_like(1 << 21),
        "cesm": scidata.cesm_like((450, 900)),
        "hurricane": scidata.hurricane_like((25, 250, 250)),
        "nyx": scidata.nyx_like((96, 96, 96)),
        "qmcpack": scidata.qmcpack_like((12, 36, 36, 36)),
    }


def _stage_timers(f: jax.Array, cfg: C.CompressorConfig, eb: float,
                  needed) -> Dict[str, Callable[[str], Tuple[Callable,
                                                             tuple]]]:
    """Kernel-name -> (impl -> (callable, args)) table for exactly the
    stage kernels the configured pipeline composes.  Inputs are prepared
    once per field from the reference (jax) path, so each timer measures
    one stage in isolation."""
    timers: Dict[str, Callable] = {}
    need = set(needed)

    if {"lorenzo.dualquant", "lorenzo.reverse"} & need:
        block = cfg.block_for(f.ndim)
        xb = dq.block_split(dq.pad_to_blocks(f, block), block)
        nb = tuple(p // b for p, b in
                   zip(dq.padded_shape(f.shape, block), block))
        dblk = jnp.zeros(nb + tuple(block), jnp.int32)
        timers["lorenzo.dualquant"] = lambda impl: (
            lambda x: lorenzo_ops.dualquant_blocks(
                x, eb, cfg.nbins, stages.outlier_capacity(x.size, cfg),
                impl=impl), (xb,))
        timers["lorenzo.reverse"] = lambda impl: (
            lambda d: lorenzo_ops.reverse_blocks(d, eb, impl=impl), (dblk,))

    if {"interp.predict", "interp.reconstruct"} & need:
        steps, _ = interp_mod.interp_plan(f.shape)
        axis, _ = steps[0]
        xm = jnp.moveaxis(dq.prequant(f, eb), axis, -1)
        even, odd = xm[..., 0::2], xm[..., 1::2]
        e2 = interp_mod._pad_even(even.reshape(-1, even.shape[-1]))
        o2 = odd.reshape(-1, odd.shape[-1])
        r2 = interp_ops.residual_rows(e2, o2, impl="jax")
        timers["interp.predict"] = lambda impl: (
            lambda a, b: interp_ops.residual_rows(a, b, impl=impl),
            (e2, o2))
        timers["interp.reconstruct"] = lambda impl: (
            lambda a, b: interp_ops.odd_rows(a, b, impl=impl), (e2, r2))

    # every downstream (encoder) stage consumes the predictor's codes
    if need - {"lorenzo.dualquant", "lorenzo.reverse",
               "interp.predict", "interp.reconstruct"}:
        pred = stages.get_predictor(cfg.predictor)
        codes, _ = pred.predict(f, cfg, eb, dispatch.pipeline_policy("jax"))
        codes_flat = codes.reshape(-1)

    if {"histogram", "encode", "deflate", "inflate"} & need:
        hist = hist_ops.histogram(codes, cfg.nbins, impl="jax")
        cb = hf.canonical_codebook(hf.codeword_lengths(hist))
        cw, bw = encode_ops.encode(codes, cb, impl="jax")
        words, bits_used, gap_bits, _ = deflate_ops.deflate(
            cw, bw, cfg.chunk_size, cfg.sub_size, impl="jax")
        nv = jnp.minimum(
            jnp.maximum(0, codes_flat.shape[0]
                        - jnp.arange(bits_used.shape[0]) * cfg.chunk_size),
            cfg.chunk_size).astype(jnp.int32)
        ml = hf.bucket_max_len(max(1, int(jnp.max(cb.lengths))))
        table = hf.decode_table(cb.lengths, ml)
        timers["histogram"] = lambda impl: (
            lambda c: hist_ops.histogram(c, cfg.nbins, impl=impl), (codes,))
        timers["encode"] = lambda impl: (
            lambda c: encode_ops.encode(c, cb, impl=impl), (codes,))
        timers["deflate"] = lambda impl: (
            lambda c, b: deflate_ops.deflate(c, b, cfg.chunk_size,
                                             cfg.sub_size, impl=impl)[0],
            (cw, bw))
        timers["inflate"] = lambda impl: (
            lambda w, bu, n, g: inflate_ops.inflate(
                w, bu, n, table, ml, gaps=g, impl=impl),
            (words, bits_used, nv, gap_bits))

    if {"bitshuffle.encode", "bitshuffle.decode"} & need:
        chunk = int(cfg.chunk_size)
        n = codes_flat.shape[0]
        nc = -(-n // chunk)
        flat = jnp.concatenate(
            [codes_flat, jnp.full((nc * chunk - n,), cfg.nbins // 2,
                                  jnp.int32)]) if nc * chunk != n \
            else codes_flat
        codes2 = flat.reshape(nc, chunk)
        planes = bitshuffle_ops.encode_planes(codes2, cfg.nbins, impl="jax")
        timers["bitshuffle.encode"] = lambda impl: (
            lambda c: bitshuffle_ops.encode_planes(c, cfg.nbins, impl=impl),
            (codes2,))
        timers["bitshuffle.decode"] = lambda impl: (
            lambda p: bitshuffle_ops.decode_planes(p, cfg.nbins, impl=impl),
            (planes,))

    return timers


def _bench_field(name: str, arr: np.ndarray, cfg: C.CompressorConfig,
                 impls: List[str], records: list) -> None:
    f = jnp.asarray(arr)
    nbytes = f.size * 4
    eb = C.resolve_eb(cfg, f)

    def rec(stage, impl, t, gbps=None):
        tag = f"T7_{name}_{stage}" + ("" if impl == "jax" else f"_{impl}")
        derived = (f"GBps={gbps:.3f}" if gbps is not None
                   else f"ms={t * 1e3:.2f}")
        emit(tag, t, derived)
        records.append({"stage": stage, "field": name, "impl": impl,
                        "seconds": t,
                        "GBps": gbps if gbps is not None else 0.0})

    # the stage axis comes from the pipeline's own stage declarations
    pipe = C.StagedPipeline.from_cfg(cfg)
    stage_kernels = pipe.predictor.kernels + pipe.encoder.kernels
    timers = _stage_timers(f, cfg, eb, stage_kernels)

    # unfused baseline (jax only — it IS the old reference path): two
    # dispatches with the delta tree materialized in between
    block = cfg.block_for(f.ndim)
    pre = jax.jit(lambda x: dq.blocked_delta(x, eb, block))
    post = jax.jit(lambda d: dq.postquant_codes(d, cfg.nbins)[0])

    def unfused(x):
        return post(pre(x))

    t = timeit(unfused, f)
    rec("dualquant_unfused", "jax", t, nbytes / t / 1e9)

    # lorenzo+huffman keeps its historical blob-path rows (codebook,
    # sequential-inflate cliff, jitted compress/decompress totals)
    hist = hist_ops.histogram(
        pipe.predictor.predict(f, cfg, eb,
                               dispatch.pipeline_policy("jax"))[0],
        cfg.nbins, impl="jax")
    t = timeit(jax.jit(lambda h: hf.canonical_codebook(
        hf.codeword_lengths(h)).codes), hist)
    rec("codebook", "jax", t)

    blob, _ = C.compress(f, dataclasses.replace(cfg, kernel_impl="jax"))
    ml = hf.bucket_max_len(max(1, int(blob.max_len)))
    table = hf.decode_table(blob.lengths, ml)

    # legacy sequential decode (the format-v1 path): one jax-only row —
    # the cliff the gap-array decode exists to kill
    t = timeit(lambda w, bu, nv: inflate_ops.inflate(
        w, bu, nv, table, ml, impl="jax"),
        blob.words, blob.bits_used, blob.n_valid)
    rec("inflate_seq", "jax", t, nbytes / t / 1e9)

    for impl in impls:
        for kname in stage_kernels:
            fn, fargs = timers[kname](impl)
            t = timeit(fn, *fargs)
            rec(_SHORT.get(kname, kname), impl, t, nbytes / t / 1e9)

        icfg = dataclasses.replace(cfg, kernel_impl=impl)
        pp = dispatch.pipeline_policy(impl)
        t = timeit(lambda x: C._compress_impl(x, icfg, eb, pp).words, f)
        rec("compress_total", impl, t, nbytes / t / 1e9)

        dec = jax.jit(lambda b: C._decompress_impl(
            b, table, icfg, eb, tuple(f.shape), ml, pp))
        t = timeit(dec, blob)
        rec("decompress_total", impl, t, nbytes / t / 1e9)


def _bench_staged(name: str, arr: np.ndarray, label: str,
                  cfg: C.CompressorConfig, impls: List[str],
                  records: list) -> None:
    """Stage rows + end-to-end staged-pipeline rows for a non-default
    predictor x encoder composition (cusz-i, fz)."""
    f = jnp.asarray(arr)
    nbytes = f.size * 4
    eb = C.resolve_eb(cfg, f)
    field = f"{name}[{label}]"

    def rec(stage, impl, t, gbps=None):
        tag = f"T7_{field}_{stage}" + ("" if impl == "jax" else f"_{impl}")
        emit(tag, t, f"GBps={gbps:.3f}" if gbps is not None
             else f"ms={t * 1e3:.2f}")
        records.append({"stage": stage, "field": field, "impl": impl,
                        "seconds": t,
                        "GBps": gbps if gbps is not None else 0.0})

    pipe = C.StagedPipeline.from_cfg(cfg)
    stage_kernels = pipe.predictor.kernels + pipe.encoder.kernels
    timers = _stage_timers(f, cfg, eb, stage_kernels)
    payload, _ = C.staged_compress(f, cfg)

    for impl in impls:
        for kname in stage_kernels:
            fn, fargs = timers[kname](impl)
            t = timeit(fn, *fargs)
            rec(_SHORT.get(kname, kname), impl, t, nbytes / t / 1e9)

        icfg = dataclasses.replace(cfg, kernel_impl=impl)
        t = timeit(lambda x: C.staged_compress(x, icfg)[0], f)
        rec("pipeline_compress", impl, t, nbytes / t / 1e9)
        t = timeit(lambda p: C.staged_decompress(p, icfg, eb,
                                                 tuple(f.shape)), payload)
        rec("pipeline_decompress", impl, t, nbytes / t / 1e9)


def main(small: bool = False, json_dir: str = ".",
         impls: Optional[List[str]] = None) -> list:
    impls = impls or _impl_axis()
    records: list = []
    cfg = C.CompressorConfig(eb=1e-4, eb_mode="valrel",
                             chunk_size=512 if small else 4096)
    for name, arr in _fields(small).items():
        _bench_field(name, arr, cfg, impls, records)
    # the non-default stage compositions, one representative field each
    staged_field = "cesm"
    arr = _fields(small)[staged_field]
    _bench_staged(staged_field, arr, "cusz-i",
                  dataclasses.replace(cfg, predictor="interp"),
                  impls, records)
    _bench_staged(staged_field, arr, "fz",
                  dataclasses.replace(cfg, encoder="bitshuffle",
                                      outlier_frac=1.0),
                  impls, records)
    write_json(os.path.join(json_dir, JSON_NAME), records)
    return records


if __name__ == "__main__":
    import argparse

    p = argparse.ArgumentParser()
    p.add_argument("--small", action="store_true")
    p.add_argument("--json-dir", default=".")
    args = p.parse_args()
    main(small=args.small, json_dir=args.json_dir)
