"""Every pipeline-stage Pallas kernel compiles for a TPU v5e.

Interpret mode cannot show what the chip's compiler (Mosaic) refuses:
block shapes off the (8, 128) tiling, primitives it has no lowering for
(`cumsum`, int32 matrix products), scoped-VMEM overruns.  These tests
compile each kernel with ``interpret=False`` for a described ``v5e:2x2``
topology, at the widths `chip_smoke.py` drives — Hurricane 100x500x500
(paper Table 2) under cusz / cusz-i / fz, HACC 280,953,867 under cusz —
and check that the kernel is in the executable.  Nothing runs; the
interpret-mode parity tests check the results.

The topology is described inside a module fixture (never at import), and
every compile runs with the persistent compilation cache off: a compile
for a described chip can be written to the cache but never read back.
"""
import math
import os

import jax
import jax.numpy as jnp
import pytest

from repro.core import dualquant as dq
from repro.core import huffman as hf
from repro.core import interp as IP
from repro.kernels import dispatch
from repro.kernels.bitshuffle import kernel as bitshuffle_k
from repro.kernels.bitshuffle.ref import nplanes
from repro.kernels.deflate import kernel as deflate_k
from repro.kernels.encode import kernel as encode_k
from repro.kernels.histogram import kernel as histogram_k
from repro.kernels.inflate import kernel as inflate_k
from repro.kernels.interp import kernel as interp_k
from repro.kernels.lorenzo import kernel as lorenzo_k

NBINS = 1024
HURRICANE = (100, 500, 500)
HACC = (280_953_867,)
CHUNK, SUB, FZ_CHUNK = 4096, 128, 512


def _blocked(shape, table=dq.DEFAULT_BLOCKS):
    block = table[len(shape)]
    nb = tuple(-(-s // b) for s, b in zip(shape, block))
    return nb + block


def _n_codes(shape):
    n = 1
    for s in _blocked(shape):
        n *= s
    return n


def _interp_rows(shape):
    """(rows, mo) of the first level step along each axis."""
    steps, _ = IP.interp_plan(shape)
    out, seen = [], set()
    for axis, shp in steps:
        if axis in seen:
            continue
        seen.add(axis)
        rows = 1
        for a, s in enumerate(shp):
            if a != axis:
                rows *= s
        out.append((rows, (shp[axis] + 1) // 2, shp[axis] // 2))
    return out


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:       # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return jax.sharding.SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def spec(one_chip, no_cache):
    def make(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)
    return make


@pytest.fixture(scope="module")
def on_chip(one_chip):
    def place(tree):
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                           sharding=one_chip), tree)
    return place


def _compiles(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def _codebook_shapes(on_chip):
    return on_chip(jax.eval_shape(hf.canonical_codebook,
                                  jax.ShapeDtypeStruct((NBINS,), jnp.int32)))


# one compile per (kernel, width); ids name the kernel as dispatch does
def _lorenzo_dualquant(spec, on_chip):
    # with the outlier store at the codec's default capacity (10% of n),
    # also at the lane-aligned blocks the checkpoint codecs use
    for shape, table in ((HURRICANE, dq.DEFAULT_BLOCKS),
                         (HACC, dq.DEFAULT_BLOCKS),
                         (HURRICANE, dq.TPU_BLOCKS), (HACC, dq.TPU_BLOCKS)):
        xb = _blocked(shape, table)
        _compiles(lambda x: lorenzo_k.dualquant_blocks_pallas(
            x, 1e-3, NBINS, math.prod(xb) // 10, interpret=False),
            spec(xb, jnp.float32))


def _lorenzo_reverse(spec, on_chip):
    for shape in (HURRICANE, HACC):
        _compiles(lambda d: lorenzo_k.reverse_blocks_pallas(
            d, 1e-3, interpret=False), spec(_blocked(shape)))


def _histogram(spec, on_chip):
    _compiles(lambda c: histogram_k.histogram_pallas(
        c, NBINS, interpret=False), spec((_n_codes(HACC),)))


def _encode(spec, on_chip):
    cb = _codebook_shapes(on_chip)
    _compiles(lambda c, b: encode_k.encode_pallas(c, b, interpret=False),
              spec((_n_codes(HACC),)), cb)


def _deflate(spec, on_chip):
    n = _n_codes(HACC)
    for chunk, sub in ((CHUNK, SUB), (FZ_CHUNK, SUB), (384, 192)):
        _compiles(lambda cw, bw: deflate_k.deflate_pallas(
            cw, bw, chunk, sub, interpret=False),
            spec((n,), jnp.uint32), spec((n,)))


def _inflate(spec, on_chip):
    # HACC's 68,593 chunks of 4096 (32 a grid step), and the KV cache's
    # chunks of 512 (256 a grid step), in both decode regimes; two
    # subchunks a chunk (a walk of 16 phases); and subchunks of 192 (a
    # last phase of 64 steps)
    n = _n_codes(HACC)
    for chunk, sub, ml in ((CHUNK, SUB, hf.LUT_BITS),
                           (CHUNK, SUB, hf.MAXLEN),
                           (FZ_CHUNK, SUB, hf.LUT_BITS),
                           (CHUNK, CHUNK // 2, hf.LUT_BITS),
                           (384, 192, hf.LUT_BITS)):
        nc = -(-n // chunk)
        table = on_chip(jax.eval_shape(
            lambda l, ml=ml: hf.build_decode_table(l, ml),
            jax.ShapeDtypeStruct((NBINS,), jnp.int32)))
        _compiles(lambda w, nv, g, t: inflate_k.inflate_pallas(
            w, nv, g, t, sub, max_len=ml, interpret=False),
            spec((nc, chunk), jnp.uint32), spec((nc,)),
            spec((nc, chunk // sub)), table)


def _interp(fn):
    def run(spec, on_chip):
        for rows, me, mo in _interp_rows(HURRICANE):
            _compiles(lambda pe, o: fn(pe, o, interpret=False),
                      spec((rows, me + 3)), spec((rows, mo)))
    return run


def _bitshuffle_encode(spec, on_chip):
    nc = -(-_n_codes(HURRICANE) // FZ_CHUNK)
    _compiles(lambda c: bitshuffle_k.encode_planes_pallas(
        c, NBINS, interpret=False), spec((nc, FZ_CHUNK)))


def _bitshuffle_decode(spec, on_chip):
    nc = -(-_n_codes(HURRICANE) // FZ_CHUNK)
    _compiles(lambda p: bitshuffle_k.decode_planes_pallas(
        p, NBINS, interpret=False),
        spec((nc, nplanes(NBINS), FZ_CHUNK // 32), jnp.uint32))


CASES = {
    "lorenzo.dualquant": _lorenzo_dualquant,
    "lorenzo.reverse": _lorenzo_reverse,
    "histogram": _histogram,
    "encode": _encode,
    "deflate": _deflate,
    "inflate": _inflate,
    "interp.predict": _interp(interp_k.residual_rows_pallas),
    "interp.reconstruct": _interp(interp_k.odd_rows_pallas),
    "bitshuffle.encode": _bitshuffle_encode,
    "bitshuffle.decode": _bitshuffle_decode,
}


def test_every_pipeline_stage_has_a_compile_case():
    assert set(CASES) == set(dispatch.PIPELINE_STAGES)


@pytest.mark.parametrize("kernel", dispatch.PIPELINE_STAGES)
def test_kernel_compiles_for_v5e(kernel, spec, on_chip):
    CASES[kernel](spec, on_chip)


def test_the_cusz_i_program_names_each_predict_step_for_its_roofline(
        spec):
    """Compiled for a v5e, the cusz-i compress program holds one Pallas
    call per split of the plan, each named as the benchmark's
    `interp_predict_roofline` reader finds it in a device trace (the bare
    instruction name, `_residual_jit.<n>`), and that reader claims no
    other kernel."""
    import re
    from bench import run
    from repro.core import compressor as CZ
    reader = run.load_reader(run.ROOT, "interp_predict_roofline")
    pp = dispatch.PipelinePolicy(entries=tuple(
        (k, dispatch.Resolved("pallas", False))
        for k in dispatch.PIPELINE_STAGES))
    cfg = CZ.CompressorConfig(eb=1e-3, eb_mode="abs", kernel_impl="pallas",
                              predictor="interp")
    shape = (20, 48, 80)
    text = CZ._compress_impl.lower(spec(shape, jnp.float32), cfg, 1e-3,
                                   pp).compile().as_text()
    calls = [line.split("=")[0].strip().lstrip("%")
             for line in text.splitlines() if "tpu_custom_call" in line]
    mine = [c for c in calls if reader.EVENTS.search(c)]
    assert len(mine) == len(IP.interp_plan(shape)[0]) == 12
    assert all(re.match(r"_residual_jit\.\d+$", c) for c in mine)
    assert sorted(set(calls) - set(mine)) == [
        "_deflate_jit.1", "_encode_jit.1", "_histogram_jit.1"]
