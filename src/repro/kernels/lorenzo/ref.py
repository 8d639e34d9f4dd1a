"""Pure-jnp oracle for the fused dual-quant Lorenzo kernel."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import dualquant as dq


def dualquant_blocks_ref(xb: jax.Array, eb: float, nbins: int,
                         capacity: int):
    """xb: [..., b1(, b2(, b3))] float32 blocks (block axes last `nd`).

    Returns (codes int32, out_idx, out_val, n_outliers, None) with code 0
    reserved for outliers.  This is PREQUANT + ℓ-delta + POSTQUANT and
    the outlier store, exactly core/dualquant; it walks no outlier tiles,
    so it counts none.
    """
    nd = xb.ndim // 2
    dqv = dq.prequant(xb, eb)
    delta = dq.lorenzo_delta(dqv, axes=range(xb.ndim - nd, xb.ndim))
    codes, in_cap = dq.postquant_codes(delta, nbins)
    with jax.named_scope("stage.outliers"):
        oidx, oval, n_out = dq.extract_outliers(
            delta.reshape(-1), in_cap.reshape(-1), capacity)
    return codes, oidx, oval, n_out, None


def reverse_blocks_ref(delta: jax.Array, eb: float):
    """Inverse: per-block cumsum + dequant.  delta: blocked int32."""
    nd = delta.ndim // 2
    dqv = dq.lorenzo_reconstruct(delta, axes=range(delta.ndim - nd, delta.ndim))
    return dq.dequant(dqv, eb)
