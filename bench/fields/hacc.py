"""HACC-like 1-D particle coordinates (on-device port of
``repro.data.scidata.hacc_like``, copied here so that the yardstick does
not move with the program's data module).

Particles sorted by cell: ``n // per_cell`` cell positions, the order
statistics of U(0, span), each repeated for its particles, plus
N(0, jitter) noise, so the series is locally smooth with jumps.  The
run's seed (and the snapshot's index) draws the particles: the ratio
moves by 0.03% between seeds.  The
order statistics come from normalised running sums of Exp(1) draws
(they have the law of sorted uniforms); the running sum is taken in two
levels of rows.  Both spare the TPU compiler minutes: a million-element
`sort` took 123 s and a flat `cumsum` 34 s to compile for a v5e.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def field(key, snapshot, shape, *, per_cell: int, span: float,
          jitter: float):
    (n,) = shape
    ncell = max(1, n // per_cell)
    kc, kj = jax.random.split(jax.random.fold_in(key, snapshot))
    gaps = jax.random.exponential(kc, (ncell + 1,), jnp.float32)
    run = _running_sum(gaps)
    cell = run[:ncell] / run[ncell] * span
    rep = -(-n // ncell)
    pos = jnp.broadcast_to(cell[:, None], (ncell, rep)).reshape(-1)[:n]
    return pos + jax.random.normal(kj, (n,), jnp.float32) * jitter


def _running_sum(v, rows: int = 1024):
    """Inclusive running sum of `v` as a running sum inside each of
    `rows` rows plus the running total of the rows before."""
    m = v.shape[0]
    width = -(-m // rows)
    x = jnp.pad(v, (0, rows * width - m)).reshape(rows, width)
    inner = jnp.cumsum(x, axis=1)
    before = jnp.cumsum(inner[:, -1]) - inner[:, -1]
    return (inner + before[:, None]).reshape(-1)[:m]
