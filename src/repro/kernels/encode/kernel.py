"""Pallas TPU kernel: Huffman encode (codebook gather, cuSZ §3.2.4).

The paper calls this stage "basically memory copy": every symbol gathers
its (codeword, bitwidth) pair from the codebook.  TPUs have no fast
VMEM gather with per-lane dynamic indices; the TPU-native formulation is
the same ONE-HOT CONTRACTION as the histogram kernel, run the other way:
a [K, T] one-hot of the tile's codes against a K iota, contracted on the
MXU with an [8, K] table whose rows are the four codeword bytes and the
bitwidth.  Operands are int8 and the accumulator int32 (see
`kernels.common`): each output sums exactly one table byte, so the
gather is bit-exact.

Tiles are (rows, 128) blocks of the flattened code stream, so blocks
meet the (8, 128) rule and both outputs are lane-dense.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .. import common


def _encode_kernel(nbins, codes_ref, table_ref, cw_ref, bw_ref):
    rows = codes_ref.shape[0]
    t = rows * 128
    codes = codes_ref[...].reshape(1, t)
    onehot = (jax.lax.broadcasted_iota(jnp.int32, (nbins, t), 0)
              == codes).astype(jnp.int8)                     # [K, T]
    got = common.dot_i8(table_ref[...], onehot)              # [8, T]
    cw = common.from_bytes([got[k:k + 1] for k in range(4)])
    cw_ref[...] = cw.reshape(rows, 128)
    bw_ref[...] = got[4:5].reshape(rows, 128)


def encode_pallas(codes: jax.Array, cb, tile: int = 1024, *,
                  interpret: bool) -> Tuple[jax.Array, jax.Array]:
    """codes: int32 quant codes (any shape); cb: huffman.Codebook.
    Returns (codewords uint32 [n], bitwidths int32 [n]) flat, matching
    core/huffman.encode bit-for-bit."""
    flat = codes.reshape(-1).astype(jnp.int32)
    nbins = cb.codes.shape[0]
    n = flat.shape[0]
    npad = -(-n // tile) * tile - n
    # pad with an out-of-range symbol: its one-hot column is all-zero, so
    # the padded tail encodes to (0 bits, 0 width) and is cropped below
    x = jnp.pad(flat, (0, npad), constant_values=nbins).reshape(-1, 128)
    cwb = common.to_bytes(jax.lax.bitcast_convert_type(cb.codes, jnp.int32))
    zero = jnp.zeros((nbins,), jnp.int32)
    table = jnp.stack(cwb + (cb.lengths.astype(jnp.int32),) + (zero,) * 3
                      ).astype(jnp.int8)
    rows = tile // 128
    spec = pl.BlockSpec((rows, 128), lambda i: (i, 0))
    cw, bw = pl.pallas_call(
        functools.partial(_encode_kernel, nbins),
        grid=(x.shape[0] // rows,),
        in_specs=[spec, pl.BlockSpec((8, nbins), lambda i: (0, 0))],
        out_specs=[spec, spec],
        out_shape=[jax.ShapeDtypeStruct(x.shape, jnp.int32)] * 2,
        interpret=interpret,
    )(x, table)
    cw = jax.lax.bitcast_convert_type(cw.reshape(-1)[:n], jnp.uint32)
    return cw, bw.reshape(-1)[:n]
