"""Shared benchmark utilities: timing + CSV emission.

Wall-clock numbers are taken on whatever backend JAX runs on.  Every
`BENCH_*.json` in the repository so far was written on the CPU, so its
timings say nothing about the TPU; only a run on the chip (see
`chip_smoke.py`) gives device times.
"""
from __future__ import annotations

import json
import os
import time

import jax
import numpy as np


def timeit(fn, *args, warmup: int = 1, iters: int = 3):
    """Median wall time of fn(*args) with block_until_ready."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def emit(name: str, seconds: float, derived: str = ""):
    print(f"{name},{seconds * 1e6:.1f},{derived}")


def write_json(path: str, records: list):
    """Machine-readable benchmark output (one BENCH_*.json per module) so
    perf-trajectory tooling reads structured records instead of scraping
    the CSV stdout."""
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    with open(path, "w") as f:
        json.dump(records, f, indent=1)
    print(f"# wrote {path} ({len(records)} records)")
