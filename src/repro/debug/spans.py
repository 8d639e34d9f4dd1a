"""Host spans and counters of the codec path, in memory and in the trace.

One small recorder, always on:

* `span(name)` times a block on `time.perf_counter` and also opens a
  ``jax.profiler.TraceAnnotation(name)``, so the block shows in a JAX
  profile on the device trace's clock (a no-op when no profiler runs).
  Spans nest per thread: each records its enclosing span's name as
  ``parent`` and shares the ``root_id`` of the outermost span, one id
  per top-level call.
* `count(name, n)` records a timestamped counter event; `count_sync(x)`
  counts one ``host_syncs`` event when `x` holds device data (a blocking
  read of host NumPy data is not a sync).
* `snapshot()` returns what the bounded ring holds, and how many of the
  oldest records it dropped when it overflowed.

A span costs two clock reads and a locked append to a ``deque``, a few
microseconds of host time.  Readers match records to their window on
``time.perf_counter``, the clock the benchmark harness uses.
"""
from __future__ import annotations

import collections
import contextlib
import itertools
import threading
import time
from typing import Iterator, NamedTuple, Optional

import jax

RING_SIZE = 1 << 16          # records kept; the oldest are dropped first


class Span(NamedTuple):
    name: str
    t0: float
    t1: float
    parent: Optional[str]    # enclosing span's name, None at the top
    root_id: int             # one id per top-level span and its children


class Count(NamedTuple):
    name: str
    t: float
    n: int
    root_id: Optional[int]   # the enclosing top-level span's, if any


# one recorder per process: the codec is called from many places (and
# from the checkpoint writer's threads), and none of them passes a
# recorder along; the lock keeps the ring and its drop count in step
_ring: "collections.deque" = collections.deque(maxlen=RING_SIZE)
_dropped = 0
_lock = threading.Lock()
_ids = itertools.count(1)
_local = threading.local()


def _stack() -> list:
    s = getattr(_local, "stack", None)
    if s is None:
        s = _local.stack = []
    return s


def _record(rec) -> None:
    global _dropped
    with _lock:
        if len(_ring) == _ring.maxlen:
            _dropped += 1
        _ring.append(rec)


@contextlib.contextmanager
def span(name: str) -> Iterator[None]:
    stack = _stack()
    parent, root = stack[-1] if stack else (None, next(_ids))
    stack.append((name, root))
    t0 = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation(name):
            yield
    finally:
        t1 = time.perf_counter()
        stack.pop()
        _record(Span(name, t0, t1, parent, root))


def count(name: str, n: int = 1) -> None:
    stack = _stack()
    _record(Count(name, time.perf_counter(), int(n),
                  stack[-1][1] if stack else None))


def holds_device_data(x) -> bool:
    """True when any leaf of the pytree `x` is a `jax.Array`."""
    return any(isinstance(v, jax.Array) for v in jax.tree.leaves(x))


def count_sync(x) -> None:
    """Count one ``host_syncs`` event for a blocking read of `x`, when
    `x` holds device data."""
    if holds_device_data(x):
        count("host_syncs")


def snapshot() -> dict:
    """{"spans": [Span], "counts": [Count], "dropped": int}: the ring's
    records, oldest first, and how many older ones it dropped."""
    with _lock:
        recs, dropped = list(_ring), _dropped
    return {"spans": [r for r in recs if isinstance(r, Span)],
            "counts": [r for r in recs if isinstance(r, Count)],
            "dropped": dropped}
