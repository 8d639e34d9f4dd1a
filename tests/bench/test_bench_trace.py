"""The trace reduction and the roofline byte counts, on the CPU in seconds.

Hand-made events check the reduction's arithmetic, and a trace recorded
on a TPU v5 lite (``fixtures/trace_hurricane_<op>.json.gz``: each mix at
16x64x64) has to reduce to what its run reported.  The kernel name map
of each roofline reader is also checked against the programs compiled
for a described v5e (no chip needed): every reader has to find its
Pallas call there, and no other reader may claim it.
"""
from __future__ import annotations

import os
import re

import pytest

from bench import run, trace_reduce

os.environ.setdefault("TPU_LOG_DIR", "disabled")

def test_busy_idle_and_gap_attribution():
    dev = {0: [("k1", 1.0, 2.0), ("k2", 1.5, 3.0), ("fusion", 4.0, 4.5),
               ("k1", 5.5, 6.0)]}                  # the last one is outside
    host = [("bench.window", 0.5, 5.0), ("bench.call", 0.5, 5.0),
            ("bench.pack", 3.0, 4.0)]
    red = trace_reduce.reduce(dev, host)
    assert red.window_s == pytest.approx(4.5)
    assert red.busy_s == pytest.approx(2.5)         # [1, 3] and [4, 4.5]
    assert red.idle_share == pytest.approx(2.0 / 4.5)
    assert red.ops == pytest.approx({"k1": 1.0, "k2": 1.5, "fusion": 0.5})
    assert red.gaps[0] == (pytest.approx(1.0), "bench.pack")
    assert sorted(n for _, n in red.gaps) == ["bench.call", "bench.call",
                                              "bench.pack"]
    assert red.seconds(re.compile("^k")) == pytest.approx(2.5)
    assert red.seconds(re.compile("absent")) is None
    assert red.top_gaps(1) == [["bench.pack", pytest.approx(1.0)]]


def test_ops_clip_to_the_window_and_average_over_devices():
    dev = {0: [("a", 0.0, 2.0)], 1: [("a", 1.0, 2.0)]}
    red = trace_reduce.reduce(dev, [("bench.window", 1.0, 3.0)])
    assert red.ops["a"] == pytest.approx(1.0)
    assert red.busy_s == pytest.approx(1.0)


def test_glue_is_busy_time_outside_the_kernels():
    # two overlapping lines of one op would count twice in a sum of ops;
    # the union of busy time counts them once
    dev = {0: [("_deflate_jit.1", 1.0, 2.0), ("fusion.3", 2.0, 3.0),
               ("copy.1", 2.5, 3.0)]}
    red = trace_reduce.reduce(dev, [("bench.window", 0.0, 10.0)])
    glue = run.load_reader(run.ROOT, "glue_share.decompress")
    assert glue.read({"trace": red}) == pytest.approx(10.0)
    idle = run.load_reader(run.ROOT, "idle_share.compress")
    assert idle.read({"trace": red}) == pytest.approx(80.0)


def test_a_trace_without_device_ops_is_an_error(tmp_path):
    """A CPU trace has no TPU plane: the reduction refuses it rather than
    read some other line as the device's operations."""
    import jax
    import jax.numpy as jnp
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
        jnp.arange(8.0).sum().block_until_ready()
    jax.profiler.stop_trace()
    with pytest.raises(ValueError, match="no 'XLA Ops' line for TPU 0"):
        trace_reduce.reduce_dir(str(tmp_path), n_devices=1)


def _ctx(red, calls=2):
    work = {"n_values": 9600, "n_sym": 15360, "nbins": 1024,
            "n_outliers": 10, "stream_bytes": 4000, "gap_bytes": 600}
    return {"trace": red, "calls": calls,
            "window": (0.0, red.window_s), "spans": [], "work": work,
            "peaks": {"hbm_bytes_per_s": 819e9}, "config": {}}


# hand counts for Hurricane cut to 10x24x40: 8x8x8 blocks pad it to
# 16x24x40 = 15360 codes; 10 outliers; 4000 B of stream, 600 B of gaps
# one Pallas call of each kernel as a v5e trace names it
KERNEL_OP = {
    "lorenzo_dualquant_roofline": "_dualquant_jit.1",
    "histogram_roofline": "_histogram_jit.1",
    "encode_roofline": "_encode_jit.1",
    "deflate_roofline": "_deflate_jit.1",
    "inflate_roofline": "_inflate_jit.1",
    "lorenzo_reverse_roofline": "_reverse_jit.1",
}
HAND = {
    "lorenzo_dualquant_roofline": 4 * 9600 + 2 * 15360 + 8 * 10,
    "histogram_roofline": 2 * 15360 + 4 * 1024,
    "encode_roofline": 2 * 15360 + 4 * 1024 + 4 * 15360,
    "deflate_roofline": 4 * 15360 + 4000 + 600,
    "inflate_roofline": 4000 + 600 + 2 * 15360,
    "lorenzo_reverse_roofline": 2 * 15360 + 8 * 10 + 4 * 9600,
}


@pytest.mark.parametrize("name", sorted(HAND))
def test_roofline_bytes_and_share(name):
    mod = run.load_reader(run.ROOT, name)
    ctx = _ctx(trace_reduce.Reduction(1.0, 0.5, {}, []))
    assert mod.work_bytes(ctx["work"]) == HAND[name]
    ctx["trace"].ops = {"other": 1.0}
    assert mod.read(ctx) is None          # absent from the trace: nothing
    t = 2 * HAND[name] / 819e9            # two calls' bytes at the peak
    ctx["trace"].ops = {KERNEL_OP[name]: 4 * t, "other": 1.0}
    assert mod.read(ctx) == pytest.approx(25.0)


@pytest.fixture(scope="module")
def v5e_ops():
    """(HLO instruction name | op_name) of every Pallas call in the cusz
    compress and decompress programs compiled for one described v5e."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from repro.core import compressor as CZ
    from repro.core import huffman as hf
    from repro.kernels import dispatch
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    one = SingleDeviceSharding(topo.devices[0])
    pp = dispatch.PipelinePolicy(entries=tuple(
        (k, dispatch.Resolved("pallas", False))
        for k in dispatch.PIPELINE_STAGES))
    cfg = CZ.CompressorConfig(eb=1e-3, eb_mode="abs", kernel_impl="pallas")
    shape = (16, 64, 64)

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one), tree)
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        x = on_chip(jax.ShapeDtypeStruct(shape, jnp.float32))
        blob = jax.eval_shape(lambda v: CZ._compress_impl(v, cfg, 1e-3, pp),
                              x)
        table = jax.eval_shape(lambda ln: hf.build_decode_table(ln, 16),
                               jax.ShapeDtypeStruct((1024,), jnp.int32))
        texts = [CZ._compress_impl.lower(x, cfg, 1e-3, pp).compile()
                 .as_text(),
                 CZ._decompress_impl.lower(on_chip(blob), on_chip(table),
                                           cfg, 1e-3, shape, 16, pp)
                 .compile().as_text()]
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)
    ops = []
    for text in texts:
        for line in text.splitlines():
            if "tpu_custom_call" in line:
                name = line.split("=")[0].strip().lstrip("%")
                m = re.search(r'op_name="([^"]*)"', line)
                ops.append(f"{name} | {m.group(1) if m else ''}")
    return ops


def test_each_kernel_names_one_pallas_call_of_the_v5e_programs(v5e_ops):
    assert len(v5e_ops) == len(HAND)
    readers = {n: run.load_reader(run.ROOT, n) for n in HAND}
    for op in v5e_ops:
        owners = [n for n, m in readers.items() if m.EVENTS.search(op)]
        assert len(owners) == 1, (op, owners)
        # the bare instruction name, as a trace without op_name stats has it
        assert readers[owners[0]].EVENTS.search(op.split(" | ")[0])
    glue = run.load_reader(run.ROOT, "glue_share.compress").KERNELS
    assert all(glue.search(op) for op in v5e_ops)
    assert not glue.search("fusion.7 | jit(_compress_impl)/reshape")


# -- a trace recorded on the chip ---------------------------------------------

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")
COMPRESS_KERNELS = ("lorenzo_dualquant_roofline", "histogram_roofline",
                    "encode_roofline", "deflate_roofline")
DECOMPRESS_KERNELS = ("inflate_roofline", "lorenzo_reverse_roofline")


def _recorded(op):
    """A traced run of the Hurricane mix `op` on a TPU v5 lite at
    16x64x64: its device ops and harness spans, the readers' context
    and what the run reported."""
    import gzip
    import json
    with gzip.open(os.path.join(FIXTURES, f"trace_hurricane_{op}.json.gz"),
                   "rt") as f:
        fx = json.load(f)
    dev = {int(d): [tuple(e) for e in evs] for d, evs in fx["dev"].items()}
    host = [tuple(h) for h in fx["host"]]
    ctx = dict(fx["ctx"])
    ctx["spans"] = [tuple(s) for s in ctx["spans"]]
    ctx["trace"] = trace_reduce.reduce(dev, host)
    return fx, ctx


@pytest.mark.parametrize("op,mine,others", [
    ("compress", COMPRESS_KERNELS, DECOMPRESS_KERNELS),
    ("decompress", DECOMPRESS_KERNELS, COMPRESS_KERNELS)])
def test_a_chip_trace_reduces_to_what_its_run_reported(op, mine, others):
    fx, ctx = _recorded(op)
    red = ctx["trace"]
    assert red.window_s == pytest.approx(fx["device"]["window_s"], rel=1e-6)
    assert red.busy_s == pytest.approx(fx["device"]["busy_s"], rel=1e-4)
    assert 0.0 < red.busy_s < red.window_s
    for name, value in fx["metrics"].items():
        got = run.load_reader(run.ROOT, name).read(ctx)
        assert got == pytest.approx(value, rel=1e-3), name
    # each kernel of this mix ran and is found by its bare instruction
    # name; the other mix's kernels are absent and read nothing
    for name in mine:
        assert red.seconds(run.load_reader(run.ROOT, name).EVENTS)
    for name in others:
        assert run.load_reader(run.ROOT, name).read(ctx) is None
    # every idle gap of the window lies inside a harness span
    assert red.gaps and all(n.startswith("bench.") for _, n in red.gaps)
