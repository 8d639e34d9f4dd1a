"""The codec path's spans, counters and stage scopes (repro.debug.spans).

The recorder on its own (nesting, the ring's bound, counter events),
then what one compress and one decompress call at test size record:
the documented ``codec.*`` spans in order, the ``host_syncs`` counter
against the syncs the host-sync guard attributes, the decode-table
cache's hits and builds, and the ``stage.*`` scopes in the lowered
pipeline.
"""
from __future__ import annotations

import collections
import re
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import codecs
from repro.core import compressor as CZ
from repro.core import huffman as hf
from repro.debug import host_sync_guard, spans
from repro.kernels import dispatch


def _since(t0: float):
    """(spans, counts) the recorder holds that ended after `t0`."""
    snap = spans.snapshot()
    return ([s for s in snap["spans"] if s.t1 >= t0],
            [c for c in snap["counts"] if c.t >= t0])


# -- the recorder --------------------------------------------------------------

def test_spans_nest_with_parent_and_root_id():
    t0 = time.perf_counter()
    with spans.span("t.outer"):
        with spans.span("t.inner"):
            spans.count("t.events", 3)
        with spans.span("t.second"):
            pass
    with spans.span("t.next"):
        pass
    got, counts = _since(t0)
    by = {s.name: s for s in got}
    # a span is recorded when it ends: children before their parent
    assert [s.name for s in got] == ["t.inner", "t.second", "t.outer",
                                     "t.next"]
    assert by["t.outer"].parent is None
    assert by["t.inner"].parent == by["t.second"].parent == "t.outer"
    assert by["t.inner"].root_id == by["t.outer"].root_id
    assert by["t.next"].root_id != by["t.outer"].root_id
    assert by["t.outer"].t0 <= by["t.inner"].t0 <= by["t.inner"].t1 \
        <= by["t.second"].t0 <= by["t.outer"].t1
    (c,) = counts
    assert (c.name, c.n, c.root_id) == ("t.events", 3, by["t.outer"].root_id)


def test_a_span_is_recorded_when_its_block_raises():
    t0 = time.perf_counter()
    with pytest.raises(ValueError):
        with spans.span("t.raises"):
            raise ValueError("x")
    with spans.span("t.after"):
        pass
    got, _ = _since(t0)
    assert [s.name for s in got] == ["t.raises", "t.after"]
    assert got[1].parent is None          # the stack was unwound


def test_the_ring_is_bounded_and_counts_what_it_dropped(monkeypatch):
    monkeypatch.setattr(spans, "_ring", collections.deque(maxlen=4))
    monkeypatch.setattr(spans, "_dropped", 0)
    for i in range(3):
        spans.count(f"t.c{i}")
    assert spans.snapshot()["dropped"] == 0
    for i in range(3, 7):
        with spans.span(f"t.s{i}"):
            pass
    snap = spans.snapshot()
    assert snap["dropped"] == 3           # the oldest three went first
    assert [c.name for c in snap["counts"]] == []
    assert [s.name for s in snap["spans"]] == ["t.s3", "t.s4", "t.s5",
                                               "t.s6"]


def test_threads_lose_no_record_and_no_drop(monkeypatch):
    """Many threads appending at once: every record is either kept or
    counted as dropped, and each thread's spans nest on its own stack."""
    monkeypatch.setattr(spans, "_ring", collections.deque(maxlen=1000))
    monkeypatch.setattr(spans, "_dropped", 0)
    n_threads, n_each = 16, 400
    errors = []

    def work(i):
        try:
            for _ in range(n_each // 2):
                with spans.span(f"t.thread{i}"):
                    spans.count("t.n")
        except Exception as e:  # noqa: BLE001 - reported below
            errors.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work, args=(i,))
                   for i in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads) and errors == []
    snap = spans.snapshot()
    kept = len(snap["spans"]) + len(snap["counts"])
    assert kept == 1000
    assert kept + snap["dropped"] == n_threads * n_each
    # each thread's spans are top-level on its own stack, and a count in
    # one carries its root id
    assert all(s.parent is None for s in snap["spans"])
    assert all(c.root_id is not None for c in snap["counts"])
    assert len({s.root_id for s in snap["spans"]}) == len(snap["spans"])


def test_count_sync_counts_device_data_only():
    t0 = time.perf_counter()
    spans.count_sync(np.ones(3))
    spans.count_sync({"a": np.ones(3), "b": 1})
    spans.count_sync({"a": np.ones(3), "b": jnp.ones(3)})
    _, counts = _since(t0)
    assert [(c.name, c.n) for c in counts] == [("host_syncs", 1)]


# -- one call of each kind at test size -----------------------------------------

@pytest.fixture(scope="module")
def codec_and_field():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((16, 24, 24)).cumsum(0),
                    jnp.float32)
    codec = codecs.get("cusz", eb=1e-3, eb_mode="valrel")
    packed = codecs.to_arrays(codec.pack(codec.encode(x)))   # warm
    codecs.decode(codec.unpack(codecs.from_arrays(*packed)))
    return codec, x, packed


def _compress(codec, x):
    """What the benchmark's compress call does."""
    c = codec.encode(x)
    jax.block_until_ready(c.payload)
    return codecs.to_arrays(codec.pack(c))


def _decompress(codec, packed):
    """What the benchmark's decompress call does."""
    c = codec.unpack(codecs.from_arrays(*packed))
    return codecs.decode(c).block_until_ready()


def test_a_compress_call_records_its_spans_in_order(codec_and_field):
    codec, x, _ = codec_and_field
    t0 = time.perf_counter()
    with spans.span("t.call"):
        _compress(codec, x)
    got, counts = _since(t0)
    assert [s.name for s in got] == [
        "codec.resolve_eb", "codec.dispatch", "codec.pack.d2h",
        "codec.pack.words", "codec.pack.crc32", "t.call"]
    root = got[-1].root_id
    assert all(s.parent == "t.call" and s.root_id == root
               for s in got[:-1])
    assert all(a.t1 <= b.t0 for a, b in zip(got[:-2], got[1:-1]))
    # one sync in resolve_eb, one in pack_blob; the outlier store's
    # values counted from pack_blob's host copy
    assert [(c.name, c.root_id) for c in counts] == [
        ("host_syncs", root), ("host_syncs", root), ("outliers.values", root)]


def test_a_compress_call_counts_its_outlier_tiles_without_a_sync(
        codec_and_field, host_sync_sanitizer):
    from repro.core import dualquant as dq
    _, x, _ = codec_and_field
    # at an absolute 1e-3 the blocks' corners (predicted from 0) are
    # outliers; the Pallas dual-quant kernel is the one that walks tiles
    eb = 1e-3
    codec = codecs.get("cusz", eb=eb, eb_mode="abs")
    with dispatch.kernel_policy(
            overrides={"lorenzo.dualquant": "pallas-interpret"}):
        _compress(codec, x)                               # warm
        t0 = time.perf_counter()
        with host_sync_sanitizer() as log:
            _compress(codec, x)
    got, counts = _since(t0)
    n = {c.name: c.n for c in counts}
    # 16x24x24 in 8x8x8 blocks: 18 blocks, 9216 values, 3 tiles of 4096
    xb = dq.block_split(dq.pad_to_blocks(x, (8, 8, 8)), (8, 8, 8))
    _, in_cap = dq.postquant_codes(
        dq.lorenzo_delta(dq.prequant(xb, eb), axes=(3, 4, 5)), 1024)
    out = np.pad(~np.asarray(in_cap).reshape(-1), (0, 3 * 4096 - 9216))
    hit = int(out.reshape(3, 4096).any(axis=1).sum())
    assert (n["outliers.tiles_hit"], n["outliers.tiles"]) == (hit, 3)
    assert hit > 0
    # counted from the host copy pack_blob already fetched
    assert [c.name for c in counts].count("host_syncs") == 2
    assert len(log.allowed_hits) == 2 and log.violations == []
    words = next(s for s in got if s.name == "codec.pack.words")
    assert all(words.t0 <= c.t <= words.t1 for c in counts
               if c.name.startswith("outliers."))


def test_a_cusz_i_compress_call_counts_tiles_and_outliers_without_a_sync(
        host_sync_sanitizer):
    from repro.core import interp
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((10, 24, 40)).cumsum(0).cumsum(1),
                    jnp.float32)
    codec = codecs.get("cusz-i", eb=1e-4, eb_mode="valrel",
                       kernel_impl="pallas-interpret")
    _compress(codec, x)                                   # warm
    t0 = time.perf_counter()
    with spans.span("t.call"):
        with host_sync_sanitizer() as log:
            _, arrays = _compress(codec, x)
    got, counts = _since(t0)
    n = collections.Counter(c.name for c in counts)
    assert n == {"host_syncs": 2, "interp.tiles": 1, "outliers.values": 1}
    assert len(log.allowed_hits) == 2 and log.violations == []
    by = {c.name: c.n for c in counts}
    # 9 splits of 10x24x40 in 8-row tiles: 202 grid steps
    assert by["interp.tiles"] == interp.predict_tiles((10, 24, 40)) == 202
    assert interp.predict_tiles((100, 500, 500)) == 47936
    assert by["outliers.values"] == arrays["out_idx"].shape[0] > 0
    # tiles once the dispatch is enqueued, outliers inside the pack
    dispatch_, words = (next(s for s in got if s.name == name)
                        for name in ("codec.dispatch", "codec.pack.words"))
    tiles_t = next(c.t for c in counts if c.name == "interp.tiles")
    outl_t = next(c.t for c in counts if c.name == "outliers.values")
    assert dispatch_.t1 <= tiles_t <= words.t0 <= outl_t <= words.t1
    # the reference kernel runs no Pallas grid and counts no tiles
    t0 = time.perf_counter()
    _compress(codecs.get("cusz-i", eb=1e-4, eb_mode="valrel",
                         kernel_impl="jax"), x)
    _, counts = _since(t0)
    assert "interp.tiles" not in {c.name for c in counts}


def test_a_decompress_call_records_its_spans_in_order(codec_and_field):
    codec, _, packed = codec_and_field
    t0 = time.perf_counter()
    with spans.span("t.call"):
        _decompress(codec, packed)
    got, counts = _since(t0)
    assert [s.name for s in got] == [
        "codec.unpack.words", "codec.unpack.h2d", "codec.decode_meta",
        "codec.dispatch", "t.call"]
    assert all(s.parent == "t.call" for s in got[:-1])
    assert sorted(c.name for c in counts) == ["decode_table.builds",
                                              "host_syncs"]


def test_a_decompress_call_counts_its_walk_steps_once(codec_and_field,
                                                      host_sync_sanitizer):
    from repro.kernels.inflate import kernel as inflate_kernel
    codec, _, packed = codec_and_field
    _, arrays = packed
    nc, n_sub = arrays["gap_bits"].shape
    chunk = int(arrays["chunk_words"])
    with dispatch.kernel_policy(overrides={"inflate": "pallas-interpret"}):
        _decompress(codec, packed)                        # warm
        t0 = time.perf_counter()
        with host_sync_sanitizer() as log:
            _decompress(codec, packed)
    got, counts = _since(t0)
    walk = [c for c in counts if c.name == "inflate.walk_steps"]
    # 16x24x24 in 8x8x8 blocks: 9216 codes, 3 chunks of 4096 and
    # 32 subchunks each, so one grid step of 4 chunks: 128 walk steps
    assert (nc, chunk, n_sub) == (3, 4096, 32)
    assert [c.n for c in walk] == [128]
    assert inflate_kernel.walk_steps(nc, chunk, chunk // n_sub) == 128
    # from the static plan once the dispatch is enqueued, no device read
    dispatch_ = next(s for s in got if s.name == "codec.dispatch")
    assert dispatch_.t1 <= walk[0].t
    assert len(log.allowed_hits) == 1 and log.violations == []
    # the benchmark's fields: 32 chunks a grid step
    assert inflate_kernel.walk_steps(6450, 4096, 128) == 202 * 128
    assert inflate_kernel.walk_steps(68593, 4096, 128) == 2144 * 128


@pytest.mark.parametrize("op,want", [("compress", 2), ("decompress", 1)])
def test_host_syncs_equal_the_syncs_the_guard_attributes(
        codec_and_field, host_sync_sanitizer, op, want):
    codec, x, packed = codec_and_field
    t0 = time.perf_counter()
    with host_sync_sanitizer() as log:
        if op == "compress":
            _compress(codec, x)
        else:
            _decompress(codec, packed)
    _, counts = _since(t0)
    n = sum(c.n for c in counts if c.name == "host_syncs")
    assert log.violations == []
    assert n == len(log.allowed_hits) == want, log.allowed_hits


def test_the_guard_ignores_reads_of_host_data():
    with host_sync_guard({}) as log:       # empty allowlist: a sync trips
        codecs.container.payload_crc32({"a": np.arange(4)})
    assert log.violations == []


def test_repeated_decodes_of_one_device_container_hit_the_table_cache(
        codec_and_field):
    codec, _, packed = codec_and_field
    c = codec.unpack(codecs.from_arrays(*packed))
    t0 = time.perf_counter()
    for _ in range(3):
        codecs.decode(c).block_until_ready()
    _, counts = _since(t0)
    assert [c.name for c in counts if c.name.startswith("decode_table")] \
        == ["decode_table.builds", "decode_table.hits", "decode_table.hits"]
    # the benchmark unpacks afresh every call: a new codebook array, so a
    # build every time
    t0 = time.perf_counter()
    for _ in range(2):
        _decompress(codec, packed)
    _, counts = _since(t0)
    assert [c.name for c in counts if c.name.startswith("decode_table")] \
        == ["decode_table.builds"] * 2


def test_container_nbytes_reads_no_device_data(codec_and_field):
    codec, x, _ = codec_and_field
    c = codec.encode(x)
    with host_sync_guard({}) as log:       # any sync would be a violation
        n = c.nbytes
    assert log.violations == []
    assert n == sum(np.asarray(v).nbytes for v in c.payload.values())


# -- stage scopes in the lowered pipeline ---------------------------------------

TRIVIAL = {"parameter", "constant", "get-tuple-element", "tuple",
           "broadcast"}


def _pipeline_ops(body: str = "_staged_compress_impl", cfg=None,
                  shape=(16, 32, 32)):
    """(opcode, op_name) of every instruction of the compress pipeline's
    body (or of the jitted function `body` it calls; `_decompress_impl`:
    the decompress pipeline's) in the lowered HLO, with its metadata."""
    from jax._src.lib import xla_client as xc
    cfg = cfg or CZ.CompressorConfig(eb=1e-3, eb_mode="abs")
    pp = dispatch.pipeline_policy(cfg.kernel_impl)
    x = jax.ShapeDtypeStruct(shape, jnp.float32)
    if body == "_decompress_impl":
        blob = jax.eval_shape(lambda v: CZ._compress_impl(v, cfg, 1e-3, pp),
                              x)._replace(outlier_tiles=None)
        table = jax.eval_shape(lambda ln: hf.build_decode_table(ln, 16),
                               jax.ShapeDtypeStruct((1024,), jnp.int32))
        lowered = CZ._decompress_impl.lower(blob, table, cfg, 1e-3, shape,
                                            16, pp)
        body = "main"                      # the entry computation
    else:
        lowered = CZ._compress_impl.lower(x, cfg, 1e-3, pp)
    module = lowered.compiler_ir("hlo").get_hlo_module()
    opts = xc._xla.HloPrintOptions()
    opts.print_metadata = True
    text = module.to_string(opts)
    body = next(c for c in re.split(r"\n(?=\S)", text)
                if re.match(rf"(ENTRY )?%{body}\b", c))
    ops = []
    for line in body.splitlines()[1:]:
        m = re.search(r"=\s*(?:\S+|\(.*?\))\s+([\w-]+)\(", line)
        if m:
            name = re.search(r'op_name="([^"]*)"', line)
            ops.append((m.group(1), name.group(1) if name else ""))
    return ops


def test_every_pipeline_op_carries_a_stage_scope():
    ops = _pipeline_ops()
    assert len(ops) > 50
    bare = [(o, n) for o, n in ops if o not in TRIVIAL
            and not re.match(r"stage\.\w+/", n)]
    assert bare == []
    scopes = {re.match(r"stage\.\w+", n).group(0) for o, n in ops
              if o not in TRIVIAL}
    # the outlier store is an output of the fused dual-quant op
    assert scopes == {"stage.blocks", "stage.dualquant", "stage.histogram",
                      "stage.codebook", "stage.encode", "stage.deflate"}


def test_the_nonzero_compaction_is_in_the_outlier_scope():
    # the reference dual-quant op (the CPU's), whose body the pipeline
    # calls under stage.dualquant
    ops = _pipeline_ops("_dualquant_jit")
    # jnp.nonzero(size=...) is a cumsum of the mask, a scatter-add
    # (bincount) of it and a cumsum of that; the value gather follows
    outl = [(o, n) for o, n in ops if n.startswith("stage.outliers/")]
    assert ("scatter", "stage.outliers/scatter-add") in outl
    assert ("call", "stage.outliers/jit(cumsum)") in outl
    assert ("gather", "stage.outliers/gather") in outl
    assert not any(n.startswith("stage.outliers/") for o, n in ops
                   if o == "custom-call")
    assert ("call", "stage.dualquant/jit(_dualquant_jit)") in _pipeline_ops()


def test_the_interp_predictor_ops_carry_its_stage_scopes():
    """cusz-i's compress and decompress pipelines: every op in a stage
    scope, the predictor's named as documented in `core/interp.py`."""
    cfg = CZ.CompressorConfig(eb=1e-3, eb_mode="abs", predictor="interp")
    ops = _pipeline_ops(cfg=cfg, shape=(10, 24, 40))
    bare = [(o, n) for o, n in ops if o not in TRIVIAL
            and not re.match(r"stage\.\w+/", n)]
    assert bare == []
    scopes = {re.match(r"stage\.\w+", n).group(0) for o, n in ops
              if o not in TRIVIAL}
    assert scopes == {"stage.prequant", "stage.interp", "stage.outliers",
                      "stage.histogram", "stage.codebook", "stage.encode",
                      "stage.deflate"}
    # the nonzero compaction of the outlier store is in its own scope
    assert ("scatter", "stage.outliers/scatter-add") in ops
    assert ("call", "stage.interp/jit(_residual_jit)") in ops
    dec = _pipeline_ops("_decompress_impl", cfg=cfg, shape=(10, 24, 40))
    names = {n for _, n in dec}
    assert "jit(_decompress_impl)/stage.interleave/jit(_odd_jit)" in names
    assert any(n.startswith("jit(_decompress_impl)/stage.scatter/")
               for n in names)
