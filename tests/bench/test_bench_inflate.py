"""The reader of `inflate_walk_steps_per_call.decompress`: the program's
``inflate.walk_steps`` counter over the window's calls, from hand-made
records and from the live recorder of a tiny decompress mix run with
interpret-mode kernels."""
from __future__ import annotations

import time

from bench import loadgen, program, run

from bench_cells import ROOT, cell_for, tiny

NAME = "inflate_walk_steps_per_call.decompress"


def _ctx(counts, calls=2):
    return {"program": {"spans": [], "counts": counts, "dropped": 0},
            "calls": calls, "window": (10.0, 20.0)}


def test_the_walk_steps_reader_reads_its_counter_inside_the_window():
    reader = run.load_reader(ROOT, NAME)
    assert reader.MOVES == "decompress_gbps"
    counts = [("inflate.walk_steps", 11.0, 25_856, None),
              ("inflate.walk_steps", 12.0, 25_856, None),
              ("inflate.walk_steps", 9.0, 25_856, None),       # before it
              ("inflate.walk_steps", 21.0, 25_856, None),      # after it
              ("host_syncs", 11.5, 1, 1)]
    assert reader.read(_ctx(counts)) == 25_856.0
    # a program without the counter (or a jax-reference decode)
    assert reader.read(_ctx([("host_syncs", 11.0, 1, 1)])) is None


def test_the_walk_steps_reader_reads_the_live_recorder():
    from repro import codecs
    from repro.kernels.inflate import kernel as inflate_kernel
    cell = tiny(cell_for("decompress", "hurricane"))
    comp = dict(cell.config["compressor"], kernel_impl="pallas-interpret")
    codec = codecs.get(cell.config["codec"], **comp)
    x = run.make_fields(cell, 2 ** 31 + 11)[0]
    packed = codecs.to_arrays(codec.pack(codec.encode(x)))
    call = loadgen.decompress_call("pallas-interpret", loadgen.Spans())
    call(packed)                                                 # warm
    t0 = time.perf_counter()
    for _ in range(2):
        call(packed)
    ctx = {"calls": 2, "window": (t0, time.perf_counter())}
    assert program.record(ctx) is not None
    nc, n_sub = packed[1]["gap_bits"].shape
    chunk = int(packed[1]["chunk_words"])
    # 10x24x40 in 8x8x8 blocks: 15,360 codes in 4 chunks, one grid step
    assert (nc, chunk, n_sub) == (4, 4096, 32)
    assert run.load_reader(ROOT, NAME).read(ctx) == \
        inflate_kernel.walk_steps(nc, chunk, chunk // n_sub) == 128.0
