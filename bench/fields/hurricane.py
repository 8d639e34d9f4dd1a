"""Hurricane-like 3-D storm field (on-device port of the random-Fourier-
feature model of ``repro.data.scidata.hurricane_like``, copied here so
that the yardstick does not move with the program's data module).

Each octave o adds ``nfeat`` plane waves a·sin(ph + w·g) over the unit
grid, with w ~ N(0, 1)·scale·2^o, a ~ N(0, 1)·2^-o and ph ~ U(0, 2π).
Like the paper's one Hurricane file, the storm is one realisation, drawn
from ``storm_seed`` (and the snapshot's index, for mixes that cycle
through several storms).  The run's seed picks the order its values are
laid out in: one of the grid's symmetries (a flip of each axis, and a
swap of the last two where they are equal).  Every seed thus compresses
the same values, so the same work and the same ratio; drawing the storm
itself from the seed moved the ratio by 2% (interquartile range over six
seeds) and the spectrum with it by 10%.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def field(key, snapshot, shape, *, octaves: int, scale: float, nfeat: int,
          storm_seed: int):
    nd = len(shape)
    storm = jax.random.fold_in(jax.random.key(storm_seed), snapshot)
    kw, ka, kp = jax.random.split(storm, 3)
    octave = jnp.repeat(jnp.arange(octaves, dtype=jnp.float32), nfeat)
    w = jax.random.normal(kw, (octaves * nfeat, nd), jnp.float32) \
        * (scale * 2.0 ** octave)[:, None]
    ph = jax.random.uniform(kp, (octaves * nfeat,), jnp.float32, 0.0,
                            2 * jnp.pi)
    a = jax.random.normal(ka, (octaves * nfeat,), jnp.float32) \
        * 2.0 ** -octave
    grids = [jnp.linspace(0.0, 1.0, s, dtype=jnp.float32).reshape(
        [s if d == e else 1 for e in range(nd)])
        for d, s in enumerate(shape)]

    def wave(i, out):
        arg = ph[i]
        for d, g in enumerate(grids):
            arg = arg + w[i, d] * g
        return out + a[i] * jnp.sin(arg)

    f = jax.lax.fori_loop(0, octaves * nfeat, wave,
                          jnp.zeros(shape, jnp.float32))
    return symmetry(f, jax.random.bits(key, (), jnp.uint32))


def symmetry(f, bits):
    """The grid symmetry that `bits` picks: bit d flips axis d, bit nd
    swaps the last two axes where they are equal."""
    nd = f.ndim
    for d in range(nd):
        f = jnp.where((bits >> d) & 1, jnp.flip(f, d), f)
    if nd >= 2 and f.shape[-1] == f.shape[-2]:
        f = jnp.where((bits >> nd) & 1, jnp.swapaxes(f, -1, -2), f)
    return f
