"""The one traffic generator: reads a mix from ``bench/traffic/<mix>.json``.

A mix is closed-loop, one call in flight: one user compressing, or
reading back, field after field.  A codec has two entry points, and a
mix drives one of them on fields its data describes.  Its keys:

  op            "compress": device-resident field -> packed container on
                the host (`encode`, `pack`, `to_arrays`);
                "decompress": packed host arrays -> field on the device
                (`from_arrays`, `unpack`, `decode`, `block_until_ready`)
  snapshots     distinct fields made from the seed (default 1), called
                round-robin; a decompress mix compresses each once in
                set-up
  frame         true (default): each field is mapped onto the
                configuration's ``range``, where it gives one; false:
                each field keeps its own value range, so its own
                absolute error bound
  metrics       {quantity: end-to-end metric name}: what the run reports
                its measured quantities as.  Quantities: ``gbps`` (field
                bytes of the calls completed in the window over the
                window, in GB/s) and ``ratio`` (field bytes over packed
                container bytes, one container per snapshot)
  check_sample  outputs of the window that the reference checks (drawn
                from the seed; default 1); a decompress mix also checks
                its last
  loop, in_flight   "closed" and 1, the only loop this generator drives
"""
from __future__ import annotations

import contextlib
import dataclasses
import json
import os
import time
from typing import Any, Callable, Dict, List, Tuple

import jax
import numpy as np

from bench import check
from repro import codecs

QUANTITIES = ("gbps", "ratio")


def load(root: str, mix: str) -> dict:
    with open(os.path.join(root, "bench", "traffic", f"{mix}.json")) as f:
        spec = json.load(f)
    if spec.get("op") not in MIXES:
        raise ValueError(f"traffic {mix}: op {spec.get('op')!r} not in "
                         f"{sorted(MIXES)}")
    if (spec.get("loop", "closed"), spec.get("in_flight", 1)) != ("closed", 1):
        raise ValueError(f"traffic {mix}: only a closed loop with one call "
                         f"in flight is generated")
    bad = set(spec.get("metrics", {})) - set(QUANTITIES)
    if bad:
        raise ValueError(f"traffic {mix}: no quantity {sorted(bad)}; the "
                         f"harness measures {QUANTITIES}")
    if int(spec.get("snapshots", 1)) < 1:
        raise ValueError(f"traffic {mix}: snapshots must be 1 or more")
    return spec


@dataclasses.dataclass
class Spans:
    """Host spans of the harness, kept in memory (name, start, end on
    `time.perf_counter`), also written into the profiler's trace."""
    items: List[Tuple[str, float, float]] = dataclasses.field(
        default_factory=list)

    @contextlib.contextmanager
    def __call__(self, name: str):
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            yield
        self.items.append((name, t0, time.perf_counter()))


def packed_nbytes(header: dict, arrays) -> int:
    """Bytes of the packed container a user writes: its arrays' bytes and
    its header as JSON."""
    return (sum(int(np.asarray(a).nbytes) for a in arrays.values())
            + len(json.dumps(header, sort_keys=True).encode()))


def compress_call(codec, spans: Spans) -> Callable[[Any], Tuple[Any, int]]:
    def call(x):
        with spans("bench.encode"):
            c = codec.encode(x)
            jax.block_until_ready(c.payload)
        with spans("bench.pack"):
            out = codecs.to_arrays(codec.pack(c))
        return out, int(x.nbytes)
    return call


def decompress_call(impl: str, spans: Spans) -> Callable:
    def call(packed):
        header, arrays = packed
        with spans("bench.unpack"):
            c = codecs.from_arrays(header, arrays)
            c = codecs.get(c.header.codec, kernel_impl=impl).unpack(c)
        with spans("bench.decode"):
            y = codecs.decode(c, kernel_impl=impl)
            y.block_until_ready()
        return y, int(y.nbytes)
    return call


class Mix:
    """What one run drives.  `call(i)` makes the window's i-th call (on
    snapshot i mod n); `keep(i, out)` holds what the comparison needs;
    after the window `check` compares it with the plain reference."""

    def __init__(self, spec: dict, codec, impl: str, xs: List[jax.Array],
                 spans: Spans):
        self.spec, self.codec, self.impl, self.spans = spec, codec, impl, spans
        self.xs = xs
        self.kept: List[Tuple[int, Any]] = []
        self.sample: set = set()

    @property
    def n(self) -> int:
        return len(self.xs)

    def call(self, i: int) -> Tuple[Any, int]:
        raise NotImplementedError

    def plan(self, expect: int, rng: np.random.Generator) -> None:
        """Draw the window calls whose outputs the reference checks."""
        k = min(expect, int(self.spec.get("check_sample", 1)))
        self.sample = set(rng.choice(expect, size=k,
                                     replace=False).tolist())

    def keep(self, i: int, out) -> None:
        if i in self.sample:
            self.kept.append((i % self.n, out))

    def close(self, i: int, out) -> None:
        """After the window: `out` was call i's output, the last."""

    def containers(self) -> List[Tuple[dict, dict]]:
        """One packed container per snapshot, for the ratio and the
        roofline's byte counts."""
        raise NotImplementedError

    def quantities(self, done_bytes: int, window_s: float
                   ) -> Dict[str, float]:
        packs = self.containers()
        field = sum(int(np.prod(h["shape"])) * 4 for h, _ in packs)
        return {"gbps": done_bytes / window_s / 1e9,
                "ratio": field / sum(packed_nbytes(h, a) for h, a in packs)}

    def host_fields(self) -> List[np.ndarray]:
        """The fields, pulled to the host and freed on the device."""
        xhs = [np.asarray(jax.device_get(x)) for x in self.xs]
        self.xs = [None] * len(xhs)
        return xhs

    def check(self, xhs: List[np.ndarray], eb_rel: float, ref: str,
              rng: np.random.Generator):
        raise NotImplementedError


class Compress(Mix):
    """Every window container is kept on the host; the reference decodes
    one of each snapshot's, drawn from the seed, and every other
    container of that snapshot has to match it byte for byte
    (compressing is deterministic)."""

    def __init__(self, *a):
        super().__init__(*a)
        self._call = compress_call(self.codec, self.spans)
        self.first: Dict[int, Any] = {}

    def call(self, i):
        return self._call(self.xs[i % self.n])

    def keep(self, i, out):
        self.kept.append((i % self.n, out))
        self.first.setdefault(i % self.n, out)

    def containers(self):
        return [self.first[s] for s in sorted(self.first)]

    def check(self, xhs, eb_rel, ref, rng):
        picks = {}
        for s in sorted(self.first):
            mine = [j for j, (t, _) in enumerate(self.kept) if t == s]
            picks[s] = int(mine[rng.integers(len(mine))])
        return check.check_compress(xhs, self.kept, eb_rel, ref, picks)


class Decompress(Mix):
    """Each snapshot is compressed once in set-up (this loads the
    compress programs too); the window decodes the packed arrays.  The
    outputs drawn from the seed and the last stay on the device until
    the window has closed."""

    def __init__(self, *a):
        super().__init__(*a)
        enc = compress_call(self.codec, Spans())
        self.packed = [enc(x)[0] for x in self.xs]
        self._call = decompress_call(self.impl, self.spans)

    def call(self, i):
        return self._call(self.packed[i % self.n])

    def close(self, i, out):
        if i not in self.sample:
            self.kept.append((i % self.n, out))

    def containers(self):
        return list(self.packed)

    def check(self, xhs, eb_rel, ref, rng):
        ys = [(s, np.asarray(jax.device_get(y))) for s, y in self.kept]
        self.kept.clear()
        return check.check_decompress(xhs, self.packed, ys, eb_rel, ref)


MIXES = {"compress": Compress, "decompress": Decompress}


def build(spec: dict, codec, impl: str, xs: List[jax.Array],
          spans: Spans) -> Mix:
    return MIXES[spec["op"]](spec, codec, impl, xs, spans)
