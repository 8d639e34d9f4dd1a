"""Pallas TPU kernel: histogram of quantization bins (cuSZ §3.2.1).

GPU cuSZ uses shared-memory replicated histograms with atomics
(Gomez-Luna et al.).  TPUs have no fast atomics; the TPU-native
formulation is a ONE-HOT CONTRACTION: each VMEM tile of codes becomes a
[T, K] one-hot (compare against a K iota) and is summed over T on the
MXU via a [1,T]x[T,K] matmul.  Tiles accumulate into the single output
block across grid steps (standard Pallas reduction: every grid index maps
to output block 0; step 0 initializes).

Conflict-free by construction — the replication/atomics machinery of the
CUDA version is unnecessary here (DESIGN.md §2).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _hist_kernel(nbins, tile, codes_ref, out_ref):
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    codes = codes_ref[...].reshape(-1)                       # [T]
    onehot = (codes[:, None] ==
              jax.lax.broadcasted_iota(jnp.int32, (1, nbins), 1)
              ).astype(jnp.float32)                          # [T, K]
    ones = jnp.ones((1, codes.shape[0]), jnp.float32)
    part = jax.lax.dot_general(ones, onehot,
                               (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)  # [1, K]
    out_ref[...] += part.astype(jnp.int32)


def histogram_pallas(codes: jax.Array, nbins: int, tile: int = 2048, *,
                     interpret: bool) -> jax.Array:
    flat = codes.reshape(-1).astype(jnp.int32)
    n = flat.shape[0]
    npad = -(-n // tile) * tile - n
    # pad with an out-of-range bin id; one-hot rows become all-zero
    flat = jnp.pad(flat, (0, npad), constant_values=nbins)
    nt = flat.shape[0] // tile
    out = pl.pallas_call(
        functools.partial(_hist_kernel, nbins, tile),
        grid=(nt,),
        in_specs=[pl.BlockSpec((tile,), lambda i: (i,))],
        out_specs=pl.BlockSpec((1, nbins), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, nbins), jnp.int32),
        interpret=interpret,
    )(flat)
    return out[0]
