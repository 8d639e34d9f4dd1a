"""Jit'd public wrappers for the Lorenzo dual-quant kernels, registered
with the dispatch layer.

With `impl=None` the ambient `KernelPolicy` (context > $REPRO_KERNEL_IMPL
> auto) decides; an explicit `impl` always wins.  Resolution happens
outside the jit boundary so the concrete choice is part of the cache key.

impl='jax'    -> pure-jnp oracle (XLA; works on any backend, used in the
                 multi-pod dry-run where the TPU Pallas lowering is
                 unavailable on the CPU host platform)
impl='pallas' -> Pallas kernel (interpret=True on CPU for validation,
                 compiled on real TPUs)
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax

from .. import dispatch
from . import kernel, ref

DUALQUANT = dispatch.register("lorenzo.dualquant", impls=("jax", "pallas"))
REVERSE = dispatch.register("lorenzo.reverse", impls=("jax", "pallas"))


@partial(jax.jit, static_argnames=("eb", "nbins", "capacity", "impl",
                                   "interpret"))
def _dualquant_jit(xb, eb: float, nbins: int, capacity: int, impl: str,
                   interpret: bool):
    # the kernel lays a block out in 128-lane rows; a block of another
    # size (no default or TPU block is one) runs the reference
    if impl == "pallas" and math.prod(xb.shape[xb.ndim // 2:]) % 128 == 0:
        return kernel.dualquant_blocks_pallas(xb, eb, nbins, capacity,
                                              interpret=interpret)
    return ref.dualquant_blocks_ref(xb, eb, nbins, capacity)


def dualquant_blocks(xb, eb: float, nbins: int, capacity: int,
                     impl: Optional[str] = None,
                     interpret: Optional[bool] = None):
    """Fused PREQUANT + ℓ-delta + POSTQUANT on blocked input, with the
    outlier store of `core.dualquant.extract_outliers`.

    Returns (codes int32 shaped like xb, out_idx [capacity], out_val
    [capacity], n_outliers, outlier_tiles): `outlier_tiles` is int32
    [tiles holding an outlier, tiles] of the Pallas kernel's walk over
    4096-value tiles, and None from the reference, which walks none."""
    r = dispatch.resolve(DUALQUANT, impl, interpret)
    return _dualquant_jit(xb, eb, nbins, capacity, r.impl, r.interpret)


@partial(jax.jit, static_argnames=("eb", "impl", "interpret"))
def _reverse_jit(delta, eb: float, impl: str, interpret: bool):
    if impl == "pallas":
        return kernel.reverse_blocks_pallas(delta, eb, interpret=interpret)
    return ref.reverse_blocks_ref(delta, eb)


def reverse_blocks(delta, eb: float, impl: Optional[str] = None,
                   interpret: Optional[bool] = None):
    """Per-block cumsum inverse + dequant.  Returns blocked float32."""
    r = dispatch.resolve(REVERSE, impl, interpret)
    return _reverse_jit(delta, eb, r.impl, r.interpret)
