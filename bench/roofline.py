"""Roofline share of one kernel, for the readers in ``bench/metrics``.

The least time the chip could take for the kernel's work in the window,
over the kernel's device time in the trace.  The work is the algorithm's:
the bytes of the stage's own inputs and outputs at the stage interface,
counted from the field's shape, nbins and the measured container (see
`bench.run.work_sizes`), whatever the kernel pads, stages or contracts.
Every codec stage here does a handful of integer operations per byte,
so bytes over the HBM peak bound each of them, never operations.
"""
from __future__ import annotations

import re
from typing import Callable, Optional


def share(ctx: dict, pattern: "re.Pattern",
          work_bytes: Callable[[dict], int]) -> Optional[float]:
    """Percent of the HBM roofline; None where the kernel did not run in
    the traced window or the device has no peaks."""
    if ctx["peaks"] is None:
        return None
    t = ctx["trace"].seconds(pattern)
    if not t:
        return None
    least = ctx["calls"] * work_bytes(ctx["work"]) \
        / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / t

