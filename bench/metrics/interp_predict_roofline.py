"""Roofline share of the interpolation predict kernel (kernels/interp,
`residual_rows`).

Moves `compress_gbps`.  Its bytes are the stage interface over the
level plan: at each split the whole int32 grid before the split is read
and each odd sample's int32 residual is written, 4 (values before the
split + odds written) bytes a split.  They are counted from the
configuration's field shape with a copy of the plan kept here, so that
the yardstick does not move with `core/interp.py`: 300,094,140 B over 19
splits at 100x500x500.
"""
import re

import numpy as np

from bench import roofline

MOVES = "compress_gbps"
# the kernel in a v5e trace: the Pallas custom call XLA names after the
# jitted wrapper (`_residual_jit.3`), whose op_name ends in pallas_call
EVENTS = re.compile(r"^%?_residual_jit(\.\d+)?(\s|$)|"
                    r"jit\(_residual_jit\)/pallas_call")
ANCHOR = 4


def plan(shape):
    """(axis, shape before the split) of each split: while an axis is
    longer than ANCHOR, each such axis in turn halves (evens rounded
    up); a field with none splits its longest axis once."""
    s = [int(v) for v in shape]
    steps = []
    while max(s) > ANCHOR:
        for a in range(len(s)):
            if s[a] > ANCHOR:
                steps.append((a, tuple(s)))
                s[a] = (s[a] + 1) // 2
    if not steps and max(s) > 1:
        a = int(np.argmax(s))
        steps.append((a, tuple(s)))
    return steps


def work_bytes(shape) -> int:
    total = 0
    for a, shp in plan(shape):
        n = int(np.prod(shp))
        total += 4 * (n + n // shp[a] * (shp[a] // 2))
    return total


def read(ctx):
    nbytes = work_bytes(ctx["config"]["field"]["shape"])
    return roofline.share(ctx, EVENTS, lambda _: nbytes)
