"""On-device field generators, one module per generator name.

`make(name, shape, seed, params, value_range, snapshot)` imports
``bench.fields.<name>`` and calls its ``field(key, snapshot, shape,
**params)``, jitted, so a field is built on the device in one call from
the seed's key; ``snapshot`` numbers the distinct fields of a mix that
cycles through several.

Where a ``value_range`` [lo, hi] is given, the field is mapped onto it
by the affine map that takes its own minimum to lo and its maximum to
hi: every value keeps its place in the field's own range, nothing is
clipped.  The error bound is relative to the value range (``valrel``),
so every seed then resolves the same absolute bound and runs the same
compiled programs.  With ``value_range`` None the field keeps its own
range.
"""
from __future__ import annotations

import importlib
from functools import partial
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of up to 64 bits (``jax.random.key`` keeps
    only the low 32 bits of a larger seed while x64 is off)."""
    seed = int(seed)
    if not 0 <= seed < 1 << 64:
        raise ValueError(f"seed {seed} is not a 64-bit unsigned integer")
    data = np.array([seed >> 32, seed & 0xFFFFFFFF], np.uint32)
    return jax.random.wrap_key_data(data)


def frame(f: jax.Array, lo: float, hi: float) -> jax.Array:
    """f mapped affinely onto [lo, hi]: its minimum to exactly lo, its
    maximum to exactly hi."""
    f = f.astype(jnp.float32)
    mn, mx = jnp.min(f), jnp.max(f)
    t = (f - mn) / (mx - mn)
    y = lo + t * jnp.float32(hi - lo)
    return jnp.where(f == mn, jnp.float32(lo),
                     jnp.where(f == mx, jnp.float32(hi), y))


@partial(jax.jit, static_argnames=("name", "shape", "params", "lohi"))
def _make(key, snapshot, name, shape, params, lohi):
    gen = importlib.import_module(f"bench.fields.{name}")
    f = gen.field(key, snapshot, shape, **dict(params))
    return f.astype(jnp.float32) if lohi is None else frame(f, *lohi)


def make(name: str, shape, seed: int, params: dict,
         value_range: Optional[Sequence[float]] = None,
         snapshot: int = 0) -> jax.Array:
    """The seed's field (its `snapshot`-th), as float32 on the device."""
    lohi = None if value_range is None else tuple(
        float(v) for v in value_range)
    return _make(seed_key(seed), jnp.uint32(snapshot), name,
                 tuple(int(s) for s in shape),
                 tuple(sorted(params.items())), lohi)
