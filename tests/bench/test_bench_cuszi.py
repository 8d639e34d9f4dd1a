"""The cuSZ-i configuration and the `hurricane-cusz.variables` mix on the
CPU at tiny shapes, with interpret-mode kernels.

The plain reference `bench/reference/cuszi.py` decodes the program's
containers bit for bit, at the tiny Hurricane cell and at odd, 1-D and
2-D shapes; the program decodes the reference's; a tiny run of each new
cell is correct, the bfloat16 control and a program with one residual
altered are not; the new readers give their hand counts.
"""
from __future__ import annotations

import numpy as np
import pytest

from bench import check, control, fields, loadgen, program, run, trace_reduce
from bench.reference import cuszi as ref

from bench_cells import ROOT, tiny

CELL = "hurricane-cuszi.compress"
# the harness's tiny Hurricane, 10x24x40, samples the storm so coarsely
# that 15% of its values miss the interpolation by half the bins or more,
# past the 10% outlier store; at 20x48x80 2% do
SHAPE = [20, 48, 80]


def tiny_cuszi(shape=SHAPE) -> run.Cell:
    cell = tiny(CELL)
    cell.config["field"]["shape"] = list(shape)
    return cell


def _codec(comp, **kw):
    from repro import codecs
    return codecs.get("cusz-i", **dict(comp, kernel_impl="pallas-interpret",
                                       **kw))


def _random_walk(shape, seed):
    x = np.random.default_rng(seed).standard_normal(shape)
    for a in range(len(shape)):
        x = x.cumsum(a)
    return x.astype(np.float32)


def _field(shape):
    """The tiny Hurricane cell's field, or a random walk of `shape`."""
    if shape is None:
        f = tiny_cuszi().config["field"]
        return fields.make(f["generator"], f["shape"], 2 ** 31 + 3,
                           f["params"], f["range"])
    return _random_walk(shape, 2 ** 31 + 1)


@pytest.mark.parametrize("shape", [None, (10, 24, 40), (9, 23, 37),
                                   (4000,), (37, 53)],
                         ids=["hurricane", "3d", "odd-3d", "1d", "2d"])
def test_reference_decodes_the_program_bit_for_bit(shape):
    x = _field(shape)
    from repro import codecs
    comp = tiny(CELL).config["compressor"]
    codec = _codec(comp)
    header, arrays = codecs.to_arrays(codec.pack(codec.encode(x)))
    want = np.asarray(codecs.decode(codecs.from_arrays(header, arrays)))
    got, faults = ref.decode(header, arrays)
    assert sum(faults.values()) == 0, faults
    assert got.dtype == want.dtype and np.array_equal(got, want)
    # the outlier store is exercised, within its capacity
    assert 0 < arrays["out_idx"].shape[0] <= int(arrays["out_capacity"])
    # the reference's own container: the same residuals, anchor and
    # outliers as the program's, and the program decodes it as the
    # reference does, within the bound
    xh = np.asarray(x)
    h2, a2 = ref.encode(xh, eb_rel=comp["eb"], nbins=comp["nbins"],
                        chunk_size=comp["chunk_size"],
                        sub_size=comp["sub_size"], block=(8, 8, 8),
                        outlier_frac=comp["outlier_frac"])
    for k in ("out_idx", "out_val", "out_capacity", "anchor", "n_valid"):
        assert np.array_equal(a2[k], arrays[k]), k
    y2, faults2 = ref.decode(h2, a2)
    assert sum(faults2.values()) == 0, faults2
    assert np.array_equal(
        np.asarray(codecs.decode(codecs.from_arrays(h2, a2))), y2)
    eb = comp["eb"] * (float(xh.max()) - float(xh.min()))
    assert check.max_abs_err(xh, y2) <= check.stated_bound(xh, eb)


def test_reference_plan_is_the_programs():
    from repro.core import interp
    mod = run.load_reader(ROOT, "interp_predict_roofline")
    for shape in [(100, 500, 500), (10, 24, 40), (9, 23, 37), (4000,),
                  (37, 53), (3, 2), (4, 4, 4), (2, 1, 7)]:
        steps, anchor = interp.interp_plan(shape)
        assert ref.plan(shape) == (list(steps), anchor)
        assert mod.plan(shape) == list(steps)


def test_reference_counts_what_breaks_a_container():
    from repro import codecs
    comp = tiny(CELL).config["compressor"]
    x = _random_walk((9, 23, 37), 2 ** 31 + 1)
    codec = _codec(comp)
    header, arrays = codecs.to_arrays(codec.pack(codec.encode(x)))
    bad = dict(arrays, out_idx=arrays["out_idx"][1:],
               out_val=arrays["out_val"][1:])
    _, faults = ref.decode(header, bad)
    assert faults["checksum"] == 1 and faults["outliers"] >= 1
    _, faults = ref.decode(header, dict(arrays, anchor=arrays["anchor"][1:]))
    assert faults["anchor"] == 1
    _, faults = ref.decode(dict(header, codec="cusz"), arrays)
    assert faults["header"] == 1


# -- whole runs ---------------------------------------------------------------

@pytest.mark.parametrize("name", [CELL, "hurricane-cusz.variables"])
def test_one_run_of_each_new_cell(name):
    cell = tiny_cuszi() if name == CELL else tiny(name)
    r = run.run_cell(cell, 2 ** 31 + 7, 0.2, False, on_chip=False)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] >= 1
    assert set(r["metrics"]) == {"setup_s", "compress_gbps", "ratio"}
    assert all(v["value"] <= v["limit"] for v in r["checks"].values())


def test_the_variables_mix_gives_each_storm_its_own_bound():
    cell = tiny("hurricane-cusz.variables")
    xs = run.make_fields(cell, 2 ** 31 + 9)
    assert len(xs) == 4
    assert len({(float(x.min()), float(x.max())) for x in xs}) == 4


def test_control_fails_the_comparison():
    nums = control.control_numbers(tiny_cuszi(), 2 ** 31 + 5)
    assert not all(v["ok"] for v in check.verdict(nums).values())
    assert nums["err_over_bound"] > 3 * check.LIMITS["err_over_bound"]


def test_one_residual_altered_is_not_correct(monkeypatch):
    """The first split's first residual is off by 100 where the program
    makes it (a shape no other test compiles, so the program is traced
    afresh with the fault)."""
    from repro.core import interp
    real = interp.interp_ops.residual_rows
    done = []

    def residual_rows(pe, odd, **kw):
        r = real(pe, odd, **kw)
        if not done:
            done.append(1)
            r = r.at[0, 0].add(100)
        return r
    monkeypatch.setattr(interp.interp_ops, "residual_rows", residual_rows)
    cell = tiny_cuszi([21, 48, 80])
    r = run.run_cell(cell, 2 ** 31 + 11, 0.05, False, on_chip=False)
    assert done
    assert r["correct"] is False and r["failed"] >= 1
    assert r["checks"]["err_over_bound"]["value"] > 1


# -- the new readers ----------------------------------------------------------

def test_interp_predict_roofline_bytes_and_share():
    mod = run.load_reader(ROOT, "interp_predict_roofline")
    assert mod.work_bytes((10, 24, 40)) == 115_872
    assert mod.work_bytes((100, 500, 500)) == 300_094_140
    assert len(mod.plan((100, 500, 500))) == 19
    red = trace_reduce.Reduction(1.0, 0.5, {"other": 1.0}, [])
    ctx = {"trace": red, "calls": 2, "window": (0.0, 1.0), "work": {},
           "peaks": {"hbm_bytes_per_s": 819e9},
           "config": {"field": {"shape": [10, 24, 40]}}}
    assert mod.read(ctx) is None          # absent from the trace: nothing
    t = 2 * 115_872 / 819e9               # two calls' bytes at the peak
    red.ops = {"_residual_jit.3": 2 * t, "_residual_jit.12": 2 * t,
               "_histogram_jit.1": 1.0}
    assert mod.read(ctx) == pytest.approx(25.0)
    assert mod.EVENTS.search("fusion.7 | jit(_residual_jit)/pallas_call")
    assert not mod.EVENTS.search("_odd_jit.2")


def _ctx(counts, calls=2, shape=(10, 24, 40)):
    return {"program": {"spans": [], "counts": counts, "dropped": 0},
            "calls": calls, "window": (10.0, 20.0),
            "config": {"field": {"shape": list(shape)}}}


def test_interp_tiles_and_outlier_share_read_their_counters():
    tiles = run.load_reader(ROOT, "interp_tiles_per_call.compress")
    share = run.load_reader(ROOT, "outlier_share.compress")
    counts = [("interp.tiles", 11.0, 202, None),
              ("interp.tiles", 12.0, 202, None),
              ("interp.tiles", 9.0, 202, None),            # before it
              ("outliers.values", 11.5, 96, 1),
              ("outliers.values", 12.5, 192, 2),
              ("outliers.values", 25.0, 960, 3)]           # after it
    assert tiles.read(_ctx(counts)) == 202.0
    assert share.read(_ctx(counts)) == pytest.approx(
        100.0 * 288 / (2 * 9600))
    zero = [("outliers.values", 11.5, 0, 1)]
    assert share.read(_ctx(zero)) == 0.0
    for mod in (tiles, share):           # a program without the counters
        assert mod.read(_ctx([("host_syncs", 11.0, 1, 1)])) is None


def test_the_new_readers_read_the_live_recorder():
    import time
    cell = tiny_cuszi()
    comp = cell.config["compressor"]
    codec = _codec(comp)
    x = run.make_fields(cell, 2 ** 31 + 9)[0]
    call = loadgen.compress_call(codec, loadgen.Spans())
    header, arrays = call(x)[0]                              # warm
    t0 = time.perf_counter()
    for _ in range(2):
        call(x)
    ctx = {"calls": 2, "window": (t0, time.perf_counter()),
           "config": cell.config}
    assert program.record(ctx) is not None
    assert run.load_reader(ROOT, "interp_tiles_per_call.compress").read(
        ctx) == 812.0                                    # at 20x48x80
    n_out = arrays["out_idx"].shape[0]
    assert n_out > 0
    assert run.load_reader(ROOT, "outlier_share.compress").read(ctx) == \
        pytest.approx(100.0 * n_out / np.prod(SHAPE))
    assert run.load_reader(ROOT, "host_syncs_per_call.compress").read(
        ctx) == 2.0
