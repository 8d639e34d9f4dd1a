"""The per-layer metrics that read the program's own spans and counters,
on hand-made contexts and on the live recorder.

Each reader reads nothing (None) where the program has no record: a
program without the recorder, or a ring that dropped records from
inside the window.
"""
from __future__ import annotations

import sys
import time

import pytest

from bench import loadgen, program, run, trace_reduce
from bench_cells import tiny
from repro import codecs

W = (10.0, 20.0)


def _rec(spans=(), counts=(), dropped=0):
    return {"spans": list(spans), "counts": list(counts), "dropped": dropped}


def _ctx(rec, calls=4, window=W):
    return {"program": rec, "calls": calls, "window": window}


def _read(name, ctx):
    return run.load_reader(run.ROOT, name).read(ctx)


def test_a_gap_is_named_by_the_codec_span_inside_the_bench_span():
    dev = {0: [("k", 1.0, 2.0), ("k", 4.0, 5.0)]}
    host = [("bench.window", 0.0, 6.0), ("bench.call", 0.0, 6.0),
            ("bench.pack", 2.0, 4.0), ("codec.pack.d2h", 2.1, 2.9),
            ("codec.pack.words", 2.9, 3.8)]
    red = trace_reduce.reduce(dev, host)
    # the gap [2, 4] has its midpoint, 3.0, in pack.words, the innermost
    assert red.gaps[0] == (pytest.approx(2.0), "codec.pack.words")
    assert red.window_s == pytest.approx(6.0)
    assert red.busy_s == pytest.approx(2.0)
    assert red.ops == pytest.approx({"k": 2.0})


@pytest.mark.parametrize("name,span", [
    ("transfer_share.compress", "codec.pack.d2h"),
    ("transfer_share.decompress", "codec.unpack.h2d")])
def test_transfer_share_is_the_window_share_of_its_spans(name, span):
    rec = _rec(spans=[(span, 11.0, 12.0, None, 1),
                      (span, 13.0, 13.5, None, 2),
                      (span, 5.0, 6.0, None, 3),         # before the window
                      ("codec.pack.words", 12.0, 13.0, None, 1)])
    assert _read(name, _ctx(rec)) == pytest.approx(15.0)
    assert _read(name, _ctx(_rec(spans=[("codec.dispatch", 11.0, 12.0,
                                         None, 1)]))) is None


@pytest.mark.parametrize("name", ["host_syncs_per_call.compress",
                                  "host_syncs_per_call.decompress"])
def test_host_syncs_per_call_counts_inside_the_window(name):
    rec = _rec(counts=[("host_syncs", 11.0, 1, 1), ("host_syncs", 12.0, 1, 1),
                       ("host_syncs", 15.0, 1, 2), ("host_syncs", 9.0, 1, 0),
                       ("decode_table.builds", 15.0, 1, 2)])
    assert _read(name, _ctx(rec, calls=2)) == pytest.approx(1.5)
    assert _read(name, _ctx(_rec(), calls=2)) == 0.0


def test_decode_table_hit_share():
    name = "decode_table_hit_share.decompress"
    rec = _rec(counts=[("decode_table.hits", 11.0, 1, 1),
                       ("decode_table.builds", 12.0, 1, 2),
                       ("decode_table.hits", 13.0, 1, 3),
                       ("decode_table.hits", 14.0, 1, 4),
                       ("decode_table.builds", 25.0, 1, 5)])
    assert _read(name, _ctx(rec)) == pytest.approx(75.0)
    builds = _rec(counts=[("decode_table.builds", 11.0, 1, 1)])
    assert _read(name, _ctx(builds)) == 0.0
    assert _read(name, _ctx(_rec())) is None       # no lookup in the window


ALL = ["transfer_share.compress", "transfer_share.decompress",
       "host_syncs_per_call.compress", "host_syncs_per_call.decompress",
       "decode_table_hit_share.decompress"]


@pytest.mark.parametrize("name", ALL)
def test_a_ring_that_dropped_part_of_the_window_reads_nothing(name):
    full = _rec(spans=[("codec.pack.d2h", 11.0, 12.0, None, 1),
                       ("codec.unpack.h2d", 11.0, 12.0, None, 1)],
                counts=[("host_syncs", 11.5, 1, 1),
                        ("decode_table.hits", 11.5, 1, 1)])
    assert _read(name, _ctx(full)) is not None
    full["dropped"] = 7                    # the oldest kept is in the window
    assert _read(name, _ctx(full)) is None
    full["counts"].append(("host_syncs", 9.0, 1, 0))   # one before it
    assert _read(name, _ctx(full)) is not None


@pytest.mark.parametrize("name", ALL)
def test_a_program_without_the_recorder_reads_nothing(monkeypatch, name):
    import repro.debug
    monkeypatch.delattr(repro.debug, "spans")
    monkeypatch.setitem(sys.modules, "repro.debug.spans", None)
    assert program.record({"window": W}) is None
    assert _read(name, {"calls": 2, "window": W}) is None


def test_the_readers_read_the_live_recorder():
    """Without a snapshot in the context, the readers read the ring of
    the process they run in, as in the harness: two syncs a compress
    call, one a decompress call, every decode table built afresh."""
    cell = tiny("hurricane-cusz.compress")
    codec = codecs.get(cell.config["codec"], **cell.config["compressor"])
    x = run.make_fields(cell, 2 ** 31 + 9)[0]
    comp = loadgen.compress_call(codec, loadgen.Spans())
    dec = loadgen.decompress_call(cell.config["compressor"]["kernel_impl"],
                                  loadgen.Spans())
    packed, _ = comp(x)
    dec(packed)                                            # warm
    t0 = time.perf_counter()
    for _ in range(3):
        comp(x)
    ctx = {"calls": 3, "window": (t0, time.perf_counter())}
    assert _read("host_syncs_per_call.compress", ctx) == 2.0
    assert 0.0 < _read("transfer_share.compress", ctx) < 100.0
    t0 = time.perf_counter()
    for _ in range(2):
        dec(packed)
    ctx = {"calls": 2, "window": (t0, time.perf_counter())}
    assert _read("host_syncs_per_call.decompress", ctx) == 1.0
    assert _read("decode_table_hit_share.decompress", ctx) == 0.0
    assert 0.0 < _read("transfer_share.decompress", ctx) < 100.0
