"""The harness on the CPU at tiny shapes, with interpret-mode kernels.

    JAX_PLATFORMS=cpu python -m pytest -q tests/bench

It finds every configuration, traffic mix and metric by name, drives one
run of each mix, holds the plain reference decoder to the program's
containers bit for bit, sees `correct` come out false when the program
underneath is broken, and keeps BENCHMARK.json inside the contract's
character sets.
"""
from __future__ import annotations

import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

from bench import check, fields, run
from bench.reference import cusz as ref

from bench_cells import ROOT, TINY, bench_json, cell_for, tiny

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


# -- discovery and the contract's character sets ------------------------------

def test_every_name_resolves_to_its_files():
    b = bench_json()
    for w in b["workloads"]:
        cell = run.Cell.load(ROOT, w["name"])
        f = cell.config["field"]
        assert os.path.exists(os.path.join(
            ROOT, "bench", "fields", f"{f['generator']}.py"))
        assert os.path.exists(os.path.join(
            ROOT, "bench", "reference", f"{cell.config['reference']}.py"))
        assert cell.config["reduced"] == []
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        # every end-to-end metric of the cell is a quantity its mix reports
        reported = set(cell.traffic["metrics"].values()) | {"setup_s"}
        assert {m["name"] for m in cell.end_to_end} <= reported
    for m in b["per_layer"]:
        mod = run.load_reader(ROOT, m["name"])
        assert getattr(mod, "MOVES", m["moves"]) == m["moves"]
        assert callable(mod.read)
        e2e = {e["name"]: e for e in b["end_to_end"]}[m["moves"]]
        assert set(m["workloads"]) <= set(e2e.get("workloads",
                                                  m["workloads"]))


def test_benchmark_json_keeps_to_the_contract():
    b = bench_json()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    names = [c["name"] for c in b["configs"]] + \
        [w["name"] for w in b["workloads"]] + \
        [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    assert len(names) == len(set(names))
    for n in names + [w["traffic"] for w in b["workloads"]]:
        assert NAME.match(n), n
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/") and 0 < len(c["source"]) <= 200
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] == 1 and 0 < len(w["why"]) <= 200
    for m in b["end_to_end"] + b["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in b["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in b["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    assert len(json.dumps(b)) < 64 * 1024


def test_unknown_device_kind_is_an_error():
    assert run.device_peaks(ROOT, "TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="not in bench/peaks.json"):
        run.device_peaks(ROOT, "TPU v9 imaginary")


def test_no_tpu_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"),
         "--workload", bench_json()["workloads"][0]["name"], "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "not a TPU" in p.stderr


ALLOCATOR_PROBE = """
import ctypes, sys
import numpy as np
from bench import run


class MallInfo2(ctypes.Structure):
    _fields_ = [(k, ctypes.c_size_t) for k in (
        "arena", "ordblks", "smblks", "hblks", "hblkhd", "usmblks",
        "fsmblks", "uordblks", "fordblks", "keepcost")]


libc = ctypes.CDLL(None)
libc.mallinfo2.restype = MallInfo2
if sys.argv[1] == "steady":
    assert run.steady_allocator()
before = libc.mallinfo2().hblkhd
a = np.ones(16 << 20, np.int32)
mapped = libc.mallinfo2().hblkhd - before
del a
print(mapped, libc.mallinfo2().fordblks)
"""


@pytest.mark.parametrize("mode", ["steady", "default"])
def test_the_steady_allocator_keeps_big_buffers_in_the_heap(mode):
    """In a process of its own (it changes the process's allocator): after
    `steady_allocator` a 64 MB buffer comes from the heap, not from a
    mapping of its own, and stays there once freed, for the next call's
    buffer to reuse; glibc's default maps it and unmaps it."""
    p = subprocess.run([sys.executable, "-c", ALLOCATOR_PROBE, mode],
                       cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr
    mapped, free_in_heap = (int(v) for v in p.stdout.split())
    if mode == "steady":
        assert mapped == 0 and free_in_heap >= 64 << 20
    else:
        assert mapped >= 64 << 20 and free_in_heap < 64 << 20


@pytest.mark.parametrize("gen", sorted(TINY))
def test_field_comes_from_the_seed_mapped_onto_its_range(gen):
    f = tiny(cell_for("compress", gen)).config["field"]

    def make(seed, value_range=f["range"], snapshot=0):
        return np.asarray(fields.make(f["generator"], f["shape"], seed,
                                      f["params"], value_range, snapshot))
    a, b, c = make(2 ** 33 + 1), make(2 ** 33 + 1), make(1)
    assert a.dtype == np.float32 and list(a.shape) == f["shape"]
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert not np.array_equal(a, make(2 ** 33 + 1, snapshot=1))
    for x in (a, c):
        assert (float(x.min()), float(x.max())) == tuple(
            np.float32(v) for v in f["range"])
    # the map is affine, nothing clipped: the unframed field has the same
    # order and the same relative spacing
    own = make(2 ** 33 + 1, value_range=None)
    assert (own.min(), own.max()) != (a.min(), a.max())
    t_own = (own.astype(np.float64) - own.min()) / (own.max() - own.min())
    t_a = (a.astype(np.float64) - a.min()) / (a.max() - a.min())
    assert np.max(np.abs(t_own - t_a)) < 1e-5
    assert np.sum(a == a.max()) == np.sum(own == own.max())


def test_hurricane_seeds_lay_out_one_storm_in_another_order():
    f = tiny(cell_for("compress", "hurricane")).config["field"]
    xs = [np.asarray(fields.make(f["generator"], f["shape"], s,
                                 f["params"], f["range"]))
          for s in (3, 2 ** 32 + 5, 2 ** 40 + 1)]
    assert len({x.tobytes() for x in xs}) == 3
    assert all(np.array_equal(np.sort(x, None), np.sort(xs[0], None))
               for x in xs)


# -- the reference ------------------------------------------------------------

@pytest.mark.parametrize("gen", sorted(TINY))
def test_reference_decodes_the_program_bit_for_bit(gen):
    from repro import codecs
    cell = tiny(cell_for("compress", gen))
    f = cell.config["field"]
    x = fields.make(f["generator"], f["shape"], 2 ** 31 + 3, f["params"],
                    f["range"])
    codec = codecs.get(cell.config["codec"], **cell.config["compressor"])
    header, arrays = codecs.to_arrays(codec.pack(codec.encode(x)))
    want = np.asarray(codecs.decode(codecs.from_arrays(header, arrays)))
    got, faults = ref.decode(header, arrays)
    assert sum(faults.values()) == 0, faults
    assert got.dtype == want.dtype and np.array_equal(got, want)


# -- whole runs, sound and broken ---------------------------------------------

@pytest.mark.parametrize("op,gen", [("compress", "hurricane"),
                                    ("decompress", "hacc")])
def test_one_run_of_each_traffic(op, gen):
    cell = tiny(cell_for(op, gen))
    r = run.run_cell(cell, 2 ** 31 + 7, 0.2, False, on_chip=False)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] >= 1
    assert set(r["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert list(r)[-1] == "checks"
    assert all(v["value"] <= v["limit"] for v in r["checks"].values())


@pytest.mark.parametrize("op", ["compress", "decompress"])
def test_a_mix_of_distinct_snapshots_is_data_only(op):
    """Several fields, each in its own value range (so each with its own
    absolute error bound), cycled round-robin: a mix that only its data
    file describes."""
    cell = tiny(cell_for(op, "hurricane"))
    cell.traffic = dict(cell.traffic, snapshots=3, frame=False,
                        check_sample=3)
    xs = run.make_fields(cell, 2 ** 31 + 9)
    assert len({(float(x.min()), float(x.max())) for x in xs}) == 3
    r = run.run_cell(cell, 2 ** 31 + 9, 0.3, False, on_chip=False)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] >= 3
    assert set(r["metrics"]) == {m["name"] for m in cell.end_to_end}


def _flip_a_word(monkeypatch):
    from repro.core import compressor as CZ
    real = CZ.pack_blob

    def pack_blob(blob):
        d = real(blob)
        d["words_packed"] = d["words_packed"].copy()
        d["words_packed"][d["words_packed"].shape[0] // 2] ^= np.uint32(1 << 7)
        return d
    monkeypatch.setattr(CZ, "pack_blob", pack_blob)


def _drop_half_the_field(monkeypatch):
    from repro.codecs import cusz
    real = cusz.CuszCodec.encode

    def encode(self, x, **kw):
        flat = x.reshape(-1)
        return real(self, flat.at[flat.shape[0] // 2:].set(0.0)
                    .reshape(x.shape), **kw)
    monkeypatch.setattr(cusz.CuszCodec, "encode", encode)


def _alter_a_value(monkeypatch):
    from repro import codecs
    real = codecs.decode

    def decode(c, **kw):
        y = real(c, **kw)
        return y.reshape(-1).at[y.size // 3].add(1.0).reshape(y.shape)
    monkeypatch.setattr(codecs, "decode", decode)


def _drop_half_the_output(monkeypatch):
    from repro import codecs
    real = codecs.decode

    def decode(c, **kw):
        y = real(c, **kw)
        flat = y.reshape(-1)
        return flat.at[flat.shape[0] // 2:].set(0.0).reshape(y.shape)
    monkeypatch.setattr(codecs, "decode", decode)


@pytest.mark.parametrize("op,fault", [
    ("compress", _flip_a_word),             # a token altered where made
    ("compress", _drop_half_the_field),     # half of the batch left out
    ("decompress", _alter_a_value),         # an answer altered where made
    ("decompress", _drop_half_the_output),  # half of the batch left out
])
def test_a_broken_program_is_not_correct(monkeypatch, op, fault):
    fault(monkeypatch)
    cell = tiny(cell_for(op, "hurricane"))
    r = run.run_cell(cell, 2 ** 31 + 11, 0.05, False, on_chip=False)
    assert r["correct"] is False and r["failed"] >= 1
    assert any(v["value"] > v["limit"] for v in r["checks"].values())


def test_check_limits_cover_every_number():
    assert set(check.LIMITS) == {"eb_gap", "err_over_bound",
                                 "format_faults", "repeat_mismatch",
                                 "recon_mismatch"}
