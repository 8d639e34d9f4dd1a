"""The program's own record of the window: the spans and counters of
`repro.debug.spans`, read by the per-layer metrics that name a
``codec.*`` span or a counter.

The readers run in the harness's process after the window, so the
recorder's ring is there to read; a context may also carry a snapshot
under ``"program"`` (the tests' hand-made contexts).  Every function
returns None where there is nothing to read: a program without the
recorder, or a ring that dropped records from inside the window.
"""
from __future__ import annotations

from typing import List, Optional, Tuple


def record(ctx) -> Optional[dict]:
    """The recorder's snapshot (``spans``, ``counts``, ``dropped``), or
    None where the program has none or lost part of the window."""
    rec = ctx.get("program")
    if rec is None:
        try:
            from repro.debug import spans
        except ImportError:
            return None
        rec = spans.snapshot()
    if rec["dropped"]:
        # the ring drops its oldest records first: the window is whole
        # only if the oldest record kept ended before the window opened
        ends = [s[2] for s in rec["spans"]] + [c[1] for c in rec["counts"]]
        if not ends or min(ends) >= ctx["window"][0]:
            return None
    return rec


def span_seconds(ctx, names: Tuple[str, ...]) -> Optional[float]:
    """Seconds of the spans named `names` that lie inside the window;
    None where no such span does."""
    rec = record(ctx)
    if rec is None:
        return None
    t0, t1 = ctx["window"]
    inside = [b - a for n, a, b, *_ in rec["spans"]
              if n in names and a >= t0 and b <= t1]
    return sum(inside) if inside else None


def counts(ctx, name: str) -> Optional[List[int]]:
    """The increments of counter `name` recorded inside the window."""
    rec = record(ctx)
    if rec is None:
        return None
    t0, t1 = ctx["window"]
    return [n for c, t, n, *_ in rec["counts"]
            if c == name and t0 <= t <= t1]
