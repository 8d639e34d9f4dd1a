"""Helpers shared by the Pallas kernels: exact integer contractions on the
MXU, zero-filled shifts, and the chunk tiling of the Huffman bitstream
kernels.

Mosaic refuses int32 matrix products, and gathers with per-lane dynamic
indices only within one vreg.  The kernels gather and pack 32-bit words
by splitting them into four bytes and contracting **int8 operands into
int32 accumulators**.  Every product the kernels form sums, per output,
either one nonzero byte (a one-hot gather) or bytes whose set bits are
disjoint (bit-field packing).  Bytes travel as signed values in
[-128, 128), so an accumulator holds the true byte sum minus a multiple
of 256; its low 8 bits are exact, and `from_bytes` keeps only those.

Byte planes stay int32 until `dot_i8` casts each whole operand to int8,
so no int8 value is sliced or stacked inside a kernel (int8 packs four
rows per 32-bit sublane).
"""
from __future__ import annotations

import math
from typing import Sequence

import jax
import jax.numpy as jnp


def signed_byte(b: jax.Array) -> jax.Array:
    """int32 values in [0, 256) -> the signed byte with the same low 8
    bits, in [-128, 128), still int32."""
    return b - ((b & 0x80) << 1)


def to_bytes(x: jax.Array) -> tuple:
    """int32 word bit patterns -> four signed byte planes (int32), low
    byte first."""
    return tuple(signed_byte(jax.lax.shift_right_logical(x, 8 * k) & 0xFF)
                 for k in range(4))


def from_bytes(acc: Sequence[jax.Array]) -> jax.Array:
    """Four int32 byte accumulators (low byte first) -> int32 words."""
    w = acc[0] & 0xFF
    for k in range(1, 4):
        w = w | ((acc[k] & 0xFF) << (8 * k))
    return w


def dot_i8(a: jax.Array, b: jax.Array, transpose_b: bool = False
           ) -> jax.Array:
    """``a @ b`` (or ``a @ b.T``) of operands holding values in
    [-128, 128), cast to int8, accumulated in int32."""
    dims = (((1,), (1 if transpose_b else 0,)), ((), ()))
    return jax.lax.dot_general(a.astype(jnp.int8), b.astype(jnp.int8), dims,
                               preferred_element_type=jnp.int32)


def shift(x: jax.Array, axis: int, k: int = 1) -> jax.Array:
    """`x` shifted by `k` along `axis` with zero fill (the padding layer)."""
    zshape = list(x.shape)
    zshape[axis] = k
    sl = [slice(None)] * x.ndim
    sl[axis] = slice(0, x.shape[axis] - k)
    return jnp.concatenate([jnp.zeros(zshape, x.dtype), x[tuple(sl)]],
                           axis=axis)


def chunks_per_tile(chunk: int, min_rows: int = 32) -> int:
    """Chunks per grid step for kernels that lay each `chunk`-symbol
    Huffman chunk out as `chunk // 128` rows of 128 lanes: the fewest
    chunks that make the row count a multiple of 8, doubled until a step
    holds at least `min_rows` rows."""
    if chunk % 128:
        raise ValueError(f"the Pallas Huffman kernels need chunk_size a "
                         f"multiple of 128, got {chunk}")
    rc = chunk // 128
    g = 8 // math.gcd(rc, 8)
    while g * rc < min_rows:
        g *= 2
    return g
