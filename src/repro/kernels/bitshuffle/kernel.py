"""Pallas kernel: fused zigzag quantize-map + bit-plane shuffle.

Words are independent, so the kernel works on a WORD-MAJOR layout: the
wrapper views the [nc, chunk] codes as nc·W words of 32 symbols
(W = chunk/32) and transposes them to [32, rows, 128] — symbol l of word
j at [l, j // 128, j % 128].  The bit index l is then the leading,
untiled dim, so packing bit p of 32 symbols into one word is 32 masked
shifts OR-ed across whole (rows, 128) tiles on the VPU: no lane
reduction, no matrix unit, exact in int32.  Each grid step fuses the
zigzag map with the shuffle for `_ROWS` x 128 words (the FZ-GPU fusion:
no materialized intermediate between the quantize map and the shuffle).
The static plane count P ≤ 16 keeps the loops unrolled.

  encode  codes [32, R, 128] -> planes [P, R, 128]
          planes[p] bit l = bit p of zigzag(codes[l])
  decode  planes [P, R, 128] -> codes [32, R, 128], the exact inverse

The wrappers do the [nc, ...] <-> word-major transposes in XLA.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .ref import nplanes

_ROWS = 32          # 128-word rows per grid step (a multiple of 8)


def _encode_kernel(nbins, p_count, x_ref, out_ref):
    acc = [None] * p_count
    for l in range(32):
        d = x_ref[l] - nbins // 2                      # [rows, 128] int32
        v = (d << 1) ^ (d >> 31)                       # zigzag
        for p in range(p_count):
            b = ((v >> p) & 1) << l
            acc[p] = b if l == 0 else acc[p] | b
    for p in range(p_count):
        out_ref[p] = acc[p]


def _decode_kernel(nbins, p_count, planes_ref, out_ref):
    planes = [planes_ref[p] for p in range(p_count)]   # [rows, 128] int32
    for l in range(32):
        v = (planes[0] >> l) & 1
        for p in range(1, p_count):
            v = v | (((planes[p] >> l) & 1) << p)
        d = (v >> 1) ^ -(v & 1)                        # un-zigzag
        out_ref[l] = d + nbins // 2


def _word_major(x: jax.Array):
    """[n, nw] int32 -> ([n, R, 128] zero-padded, rows per step)."""
    n, nw = x.shape
    rows = -(-nw // 128)
    step = min(_ROWS, -(-rows // 8) * 8)
    rows = -(-rows // step) * step
    x = jnp.pad(x, ((0, 0), (0, rows * 128 - nw)))
    return x.reshape(n, rows, 128), step


def _call(kernel, x, n_out, step, interpret):
    n_in, rows, _ = x.shape
    return pl.pallas_call(
        kernel,
        grid=(rows // step,),
        in_specs=[pl.BlockSpec((n_in, step, 128), lambda i: (0, i, 0))],
        out_specs=pl.BlockSpec((n_out, step, 128), lambda i: (0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_out, rows, 128), jnp.int32),
        interpret=interpret,
    )(x)


def encode_planes_pallas(codes2: jax.Array, nbins: int, *,
                         interpret: bool) -> jax.Array:
    nc, chunk = codes2.shape
    p_count, w = nplanes(nbins), chunk // 32
    x, step = _word_major(codes2.astype(jnp.int32).reshape(nc * w, 32).T)
    planes = _call(functools.partial(_encode_kernel, nbins, p_count),
                   x, p_count, step, interpret)
    planes = planes.reshape(p_count, -1)[:, :nc * w].reshape(p_count, nc, w)
    return jax.lax.bitcast_convert_type(planes.transpose(1, 0, 2),
                                        jnp.uint32)


def decode_planes_pallas(planes: jax.Array, nbins: int, *,
                         interpret: bool) -> jax.Array:
    nc, p_count, w = planes.shape
    x = jax.lax.bitcast_convert_type(planes, jnp.int32)
    x, step = _word_major(x.transpose(1, 0, 2).reshape(p_count, nc * w))
    codes = _call(functools.partial(_decode_kernel, nbins, p_count),
                  x, 32, step, interpret)
    return codes.reshape(32, -1)[:, :nc * w].T.reshape(nc, 32 * w)
