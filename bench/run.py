"""Benchmark harness: one cell, one run.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

(or ``python3 -m bench.run ...``), from the root of a checkout.  The
cell is an entry of ``workloads`` in ``BENCHMARK.json``; everything it
names is found by name under ``bench/``:

  bench/configs/<config>.json    field, codec and its CompressorConfig,
                                 the guarantee, the plain reference
  bench/traffic/<mix>.json       the mix the one generator drives
                                 (`bench/loadgen.py`), and the names
                                 its quantities are reported under
  bench/fields/<generator>.py    on-device field generator
  bench/metrics/<metric>.py      one reader per per-layer metric (or
                                 per quantity that metrics split by
                                 the end-to-end metric they move)
  bench/reference/<codec>.py     plain decoder the comparison uses
  bench/peaks.json               device peaks keyed by device_kind

A run makes the field on the device from the seed, warms every program
the mix uses (served from JAX's persistent cache under
``<checkout>/.jax_cache`` unless ``JAX_COMPILATION_CACHE_DIR`` names
another), then runs calls back to back until ``--seconds`` have passed
and the call in flight has completed.  ``--trace 0`` reports the cell's
end-to-end metrics, ``--trace 1`` traces the same window with the JAX
profiler and reports its per-layer metrics.  After the window the
outputs are compared with the plain reference; the last line of
standard output is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``[, ``breakdown``], ``checks``), and
the numbers compared close standard error, each beside its limit.

Without a TPU, or with fewer chips than the cell asks for, it exits 3
and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import jax  # noqa: E402
import numpy as np  # noqa: E402

from bench import check, fields, loadgen, trace_reduce  # noqa: E402


class NoChip(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Cell:
    """One entry of ``workloads`` with everything it names loaded."""
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]

    @staticmethod
    def load(root: str, workload: str) -> "Cell":
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            bench = json.load(f)
        cells = {w["name"]: w for w in bench["workloads"]}
        if workload not in cells:
            raise KeyError(f"no workload {workload!r}; BENCHMARK.json has "
                           f"{sorted(cells)}")
        w = cells[workload]
        cfg = {c["name"]: c for c in bench["configs"]}[w["config"]]
        with open(os.path.join(root, cfg["file"])) as f:
            config = json.load(f)

        def mine(m):
            return workload in m.get("workloads", [workload])
        return Cell(workload, int(w["chips"]), config,
                    loadgen.load(root, w["traffic"]),
                    [m for m in bench["end_to_end"] if mine(m)],
                    [m for m in bench["per_layer"] if mine(m)])


# -- the device ---------------------------------------------------------------

def chip_devices(chips: int):
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX's first device is {devs[0].platform!r}, not a "
                     f"TPU")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX finds "
                     f"{len(devs)}")
    return devs[:chips]


def enable_compile_cache(root: str) -> str:
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(root, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    # cache every program, the small eager ones too, so that a warm
    # run's set-up compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


M_TRIM_THRESHOLD, M_MMAP_MAX = -1, -4          # glibc's mallopt(3) params


def steady_allocator() -> bool:
    """Serve every host allocation from the process's heap and never give
    freed memory back to the system (glibc `mallopt`), so that a call's
    buffers (the blob pulled to the host and the packing's index arrays,
    a few hundred MB a call) reuse pages that are already mapped.  By
    default glibc maps each such buffer afresh and faults its pages in
    on first touch, a cost that depended on the machine's state: the same
    word gather took 0.019 s a call in one process and 0.07 s in the
    next on one v5e host.  False where the C library has no `mallopt`."""
    import ctypes
    try:
        libc = ctypes.CDLL(None)
        return bool(libc.mallopt(M_MMAP_MAX, 0)
                    and libc.mallopt(M_TRIM_THRESHOLD, -1))
    except (OSError, AttributeError):
        return False


def device_peaks(root: str, kind: str) -> dict:
    with open(os.path.join(root, "bench", "peaks.json")) as f:
        table = json.load(f)
    if kind not in table["devices"]:
        raise KeyError(f"device_kind {kind!r} is not in bench/peaks.json "
                       f"({sorted(table['devices'])}); add its peaks with "
                       f"their source")
    return table["devices"][kind]


def check_dispatch(codec) -> None:
    """Every stage of the codec's pipeline resolves to compiled Pallas."""
    from repro.core import stages
    from repro.kernels import dispatch
    cfg = codec.cfg
    pp = dispatch.pipeline_policy(cfg.kernel_impl)
    want = dispatch.Resolved("pallas", interpret=False)
    for k in (stages.get_predictor(cfg.predictor).kernels
              + stages.get_encoder(cfg.encoder).kernels):
        got = pp.for_kernel(k)
        if got != want:
            raise RuntimeError(f"stage {k} resolves to {got}, not {want}")


COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class CompileCounter:
    """Counts programs compiled or loaded from the persistent cache while
    `active` (jax.monitoring events).  One per process: `get()`."""

    _one: Optional["CompileCounter"] = None

    @classmethod
    def get(cls) -> "CompileCounter":
        if cls._one is None:
            cls._one = cls()
        cls._one.compiles = cls._one.cache_loads = 0
        return cls._one

    def __init__(self):
        self.active = False
        self.compiles = 0
        self.cache_loads = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **kw) -> None:
        if self.active and event == COMPILE_EVENT:
            self.compiles += 1

    def _event(self, event: str, **kw) -> None:
        if self.active and event == CACHE_HIT_EVENT:
            self.cache_loads += 1


# -- the run ------------------------------------------------------------------

def load_reader(root: str, name: str):
    """The reader of per-layer metric `name`: ``bench/metrics/<name>.py``,
    else the reader of the quantity it splits, ``<name up to its first
    dot>.py`` (``idle_share.compress`` and ``idle_share.decompress`` are
    one quantity, split by the end-to-end metric each moves)."""
    base = os.path.join(root, "bench", "metrics")
    path = os.path.join(base, f"{name}.py")
    if not os.path.exists(path):
        path = os.path.join(base, f"{name.split('.')[0]}.py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + os.path.basename(path)[:-3].replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def work_sizes(packs) -> Dict[str, int]:
    """Sizes the roofline readers count bytes from, per call (the mean
    over the snapshots' containers): the field's, the code stream's
    (blocked, so padded to whole blocks) and the packed container's."""
    tot: Dict[str, int] = {}
    for header, arrays in packs:
        block = header["params"]["block"]
        w = {"n_values": int(np.prod(header["shape"])),
             "n_sym": int(np.prod([-(-s // b) * b for s, b in
                                   zip(header["shape"], block)])),
             "nbins": int(header["params"]["nbins"]),
             "n_outliers": int(np.asarray(arrays["out_idx"]).shape[0]),
             "stream_bytes": int(np.asarray(arrays["words_packed"]).nbytes),
             "gap_bytes": int(np.asarray(arrays["gap_bits"]).nbytes
                              + np.asarray(arrays["gap_syms"]).nbytes)}
        for k, v in w.items():
            tot[k] = tot.get(k, 0) + v
    return {k: v // len(packs) for k, v in tot.items()}


def log(msg: str) -> None:
    print(f"[bench {time.perf_counter() - T_START:8.2f}s] {msg}",
          file=sys.stderr, flush=True)


def make_fields(cell: Cell, seed: int) -> List[jax.Array]:
    """The mix's fields, made on the device from the seed: `snapshots`
    of them, each mapped onto the configuration's range unless the mix
    sets `frame` false."""
    f, mix = cell.config["field"], cell.traffic
    lohi = f.get("range") if mix.get("frame", True) else None
    return [fields.make(f["generator"], f["shape"], seed, f["params"], lohi,
                        snapshot=i).block_until_ready()
            for i in range(int(mix.get("snapshots", 1)))]


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             root: str = ROOT, on_chip: bool = True,
             t_start: Optional[float] = None) -> dict:
    """Set up, measure, compare.  `on_chip=False` skips the look for a
    chip and the compiled-Pallas check (tests on the CPU)."""
    from repro import codecs
    t_start = time.perf_counter() if t_start is None else t_start
    devs = chip_devices(cell.chips) if on_chip else jax.devices()[:1]
    dev = devs[0]
    peaks = device_peaks(root, dev.device_kind) if on_chip else None
    cfg = cell.config
    codec = codecs.get(cfg["codec"], **cfg["compressor"])
    if on_chip:
        check_dispatch(codec)
    counter = CompileCounter.get()
    spans = loadgen.Spans()
    rng = np.random.default_rng(seed)

    xs = make_fields(cell, seed)
    log(f"{len(xs)} field(s) made: {tuple(cfg['field']['shape'])} "
        f"{cfg['field']['dtype']}")
    mix = loadgen.build(cell.traffic, codec, cfg["compressor"]["kernel_impl"],
                        xs, spans)
    del xs
    for i in range(mix.n + 1):          # the first call of each snapshot
        t0 = time.perf_counter()        # loads its programs, the last
        mix.call(i)                     # runs warm
        t_call = time.perf_counter() - t0
    expect = max(1, int(seconds / t_call) + 1)
    mix.plan(expect, rng)
    log(f"warm call {t_call:.4f} s, ~{expect} calls expected")
    spans.items.clear()

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    counter.active = True
    calls = []
    t_open = time.perf_counter()
    setup_s = t_open - t_start
    with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
        while True:
            t0 = time.perf_counter()
            with spans("bench.call"):
                out, nbytes = mix.call(len(calls))
            t1 = time.perf_counter()
            mix.keep(len(calls), out)
            calls.append((t0, t1, nbytes))
            if t1 - t_open >= seconds:
                break
    counter.active = False
    mix.close(len(calls) - 1, out)
    del out
    t_close = t1
    window_s = t_close - t_open
    mem_peak = int((dev.memory_stats() or {}).get("peak_bytes_in_use", 0))
    durs = sorted(b - a for a, b, _ in calls)
    log(f"window {window_s:.4f} s, {len(calls)} calls (min {durs[0]:.4f}, "
        f"median {durs[len(durs) // 2]:.4f}, max {durs[-1]:.4f} s), "
        f"{counter.compiles} compiles and {counter.cache_loads} cache "
        f"loads inside it")
    for name in sorted({s[0] for s in spans.items}):
        d = sorted(b - a for n, a, b in spans.items if n == name)
        q = [d[int(p * (len(d) - 1))] for p in (0, .25, .5, .75, 1)]
        log(f"span {name}: n {len(d)}, total {sum(d):.4f} s, min/q1/median"
            f"/q3/max " + " ".join(f"{v:.4f}" for v in q))

    result = {"correct": False, "attempted": len(calls), "failed": 0,
              "metrics": {},
              "device": {"platform": dev.platform, "kind": dev.device_kind,
                         "count": len(jax.devices()),
                         "memory_peak_bytes": mem_peak}}
    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}

    if trace:
        jax.profiler.stop_trace()
        red = trace_reduce.reduce_dir(trace_dir, n_devices=len(devs))
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx = {"trace": red, "calls": len(calls),
               "window": (t_open, t_close), "spans": list(spans.items),
               "work": work_sizes(mix.containers()), "peaks": peaks,
               "config": cfg}
        for m in cell.per_layer:
            v = load_reader(root, m["name"]).read(ctx)
            if v is not None:
                result["metrics"][m["name"]] = {"value": v,
                                                "unit": units[m["name"]]}
        result["device"]["busy_s"] = red.busy_s
        result["device"]["window_s"] = red.window_s
        result["breakdown"] = {"device_ops": red.top_ops(10),
                               "idle_gaps": red.top_gaps(10)}
    else:
        got = mix.quantities(sum(c[2] for c in calls), window_s)
        e2e = {"setup_s": setup_s}
        e2e.update({name: got[q] for q, name in
                    cell.traffic.get("metrics", {}).items()})
        for m in cell.end_to_end:
            result["metrics"][m["name"]] = {"value": e2e[m["name"]],
                                            "unit": units[m["name"]]}

    # -- the comparison, with the program's device state freed -------------
    xhs = mix.host_fields()
    nums, faults, failed = mix.check(xhs, float(cfg["compressor"]["eb"]),
                                     cfg["reference"], rng)
    verdicts = check.verdict(nums)
    result["correct"] = all(v["ok"] for v in verdicts.values())
    result["failed"] = failed
    result["checks"] = {k: {"value": v["value"], "limit": v["limit"]}
                        for k, v in verdicts.items()}
    log(f"reference faults: {faults}")
    for k, v in verdicts.items():
        print(f"check {k} = {v['value']!r} limit {v['limit']!r} "
              f"{'ok' if v['ok'] else 'FAILED'}", file=sys.stderr,
              flush=True)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = Cell.load(ROOT, args.workload)
    except (OSError, KeyError, ValueError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    try:
        chip_devices(cell.chips)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    log(f"compile cache: {enable_compile_cache(ROOT)}; steady allocator: "
        f"{steady_allocator()}")
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      t_start=T_START)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
