"""Production mesh construction.

A FUNCTION, not a module-level constant: importing this module never
touches jax device state (the dry-run pins the fake device count *before*
any jax initialization)."""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod.

    Axes: 'data' carries DP+FSDP; 'model' carries TP/EP; 'pod' carries
    cross-pod data parallelism (and the compressed gradient all-reduce)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_host_mesh():
    """1-device mesh with the production axis names (tests/examples)."""
    return jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)


def make_local_mesh():
    """Two pods over every local device: ``(2, n // 2, 1)`` on
    ('pod', 'data', 'model') for ``n = jax.device_count()`` — four chips
    of one host become two 2-chip pods, the smallest layout on which the
    compressed cross-pod gradient all-reduce runs."""
    n = jax.device_count()
    if n % 2:
        raise ValueError(f"{n} local devices do not split into 2 pods")
    return jax.make_mesh((2, n // 2, 1), ("pod", "data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 3)
