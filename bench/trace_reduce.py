"""Reduce a JAX profiler trace to device time per kernel, busy and idle
time, and the longest idle gaps named by the harness's host spans.

`events(path)` reads the ``.xplane.pb`` the profiler wrote (with
nothing but `jax.profiler.ProfileData`) into plain tuples: the device
operations of each TPU, and the host spans the harness annotated
(``bench.*``).  `reduce` is pure arithmetic on those tuples:

* the window is the ``bench.window`` span;
* busy time is the union of the device operations' intervals inside
  it, averaged over the devices;
* the idle gaps are the rest of the window, each named by the innermost
  harness span that holds its midpoint;
* an operation's time is the sum of its events' durations inside it.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[str, float, float]          # (name, start_s, end_s)

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
# the profiler's device planes and, inside them, the line of operations
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"


def find_xplane(path: str) -> str:
    found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return found[-1]


def _label(e) -> str:
    """An event's name with its string stats (the HLO op's framework
    name and the like), so that a kernel can be matched by either."""
    extra = [str(v) for _, v in e.stats if isinstance(v, str)]
    return " | ".join([e.name] + extra)


def events(xplane: str):
    """(device ops per device index, harness host spans, the line names
    of device planes without an ``XLA Ops`` line) from a trace.  Only
    that line is read: the plane's other lines nest modules and steps
    over the ops, and would count the same time twice."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(xplane)
    dev: Dict[int, List[Interval]] = {}
    missing: Dict[int, List[str]] = {}
    host: List[Interval] = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            lines = [ln for ln in plane.lines if ln.name == OPS_LINE]
            if not lines:
                missing[int(m.group(1))] = [ln.name for ln in plane.lines]
                continue
            dev.setdefault(int(m.group(1)), []).extend(
                (_label(e), e.start_ns * 1e-9, e.end_ns * 1e-9)
                for ln in lines for e in ln.events)
            continue
        for line in plane.lines:
            if plane.name.startswith("/host"):
                host.extend((e.name, e.start_ns * 1e-9, e.end_ns * 1e-9)
                            for e in line.events
                            if e.name.startswith(SPAN_PREFIX))
    return dev, host, missing


def union(intervals: Iterable[Tuple[float, float]]) -> List[List[float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float                       # mean over devices
    ops: Dict[str, float]               # seconds per op name, mean/device
    gaps: List[Tuple[float, str]]       # (seconds, host span), longest first

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def seconds(self, pattern: "re.Pattern") -> Optional[float]:
        """Time of the operations whose name matches; None where none
        ran in the window."""
        hits = [t for n, t in self.ops.items() if pattern.search(n)]
        return sum(hits) if hits else None

    def top_ops(self, k: int) -> List[list]:
        return [[n[:160], t] for n, t in sorted(self.ops.items(),
                                                key=lambda kv: -kv[1])[:k]]

    def top_gaps(self, k: int) -> List[list]:
        return [[n, t] for t, n in self.gaps[:k]]


def reduce(dev: Dict[int, List[Interval]], host: Sequence[Interval]
           ) -> Reduction:
    wins = [(a, b) for n, a, b in host if n == WINDOW_SPAN]
    if not wins:
        raise ValueError(f"the trace holds no {WINDOW_SPAN} span")
    w0, w1 = wins[0]
    n_dev = max(1, len(dev))
    ops: Dict[str, float] = {}
    busy = 0.0
    merged_all: List[List[float]] = []
    for d, evs in sorted(dev.items()):
        clipped = [(n, max(a, w0), min(b, w1)) for n, a, b in evs
                   if b > w0 and a < w1]
        for n, a, b in clipped:
            ops[n] = ops.get(n, 0.0) + (b - a) / n_dev
        merged = union((a, b) for _, a, b in clipped)
        busy += sum(b - a for a, b in merged) / n_dev
        if not merged_all:
            merged_all = merged
    spans = sorted((b - a, a, b, n) for n, a, b in host
                   if n != WINDOW_SPAN)
    gaps = []
    edges = [w0] + [v for ab in merged_all for v in ab] + [w1]
    for a, b in zip(edges[0::2], edges[1::2]):
        if b > a:
            mid = 0.5 * (a + b)
            name = next((n for _, s, e, n in spans if s <= mid <= e),
                        "outside any span")
            gaps.append((b - a, name))
    gaps.sort(key=lambda g: -g[0])
    return Reduction(w1 - w0, busy, ops, gaps)


def reduce_dir(path: str, n_devices: int = 1) -> Reduction:
    dev, host, missing = events(find_xplane(path))
    for d in range(n_devices):
        if d not in dev:
            raise ValueError(
                f"the trace has no {OPS_LINE!r} line for TPU {d} (its "
                f"plane's lines: {missing.get(d, 'no plane')})")
    dev = {d: v for d, v in dev.items() if d < n_devices}
    return reduce(dev, host)
