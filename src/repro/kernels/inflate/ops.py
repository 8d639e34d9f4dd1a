"""Jit'd public wrapper for the inflate stage; dispatch-registered.

Gap-array two-phase decode (Rivera et al., arXiv 2201.09118): when the
caller supplies the per-subchunk gap array that deflate now emits, decode
is parallel over subchunks and registers a real Pallas impl — the old
"inflate is RAW-bound, jax-only" era is over.  Gap-less streams (format
v1 containers) still decode through the sequential jax reference; an
explicit ``impl="pallas"`` request on such a stream raises, since the
Pallas kernel is the gap decoder.  A subchunk too long for one grid
step of the kernel to hold in VMEM (`kernel.fits`: 4096 symbols or
more) decodes through the jax gap reference, whatever the request.

The decode tables ride in a prebuilt `huffman.DecodeTable` (see
`huffman.decode_table` — built once per codebook, cached, never inside
the jitted decode).  A bare `Codebook` is accepted for convenience and
converted through the same cache.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax

from repro.core import huffman as hf

from .. import dispatch
from . import kernel, ref

KERNEL = dispatch.register("inflate", impls=("jax", "pallas"))


@partial(jax.jit, static_argnames=("max_len_static", "sub_size", "impl",
                                   "interpret"))
def _inflate_jit(words, bits_used, n_valid, table, gaps,
                 max_len_static: int, sub_size: int, impl: str,
                 interpret: bool):
    # repro-lint: allow[tracer-branch] `gaps` is a pytree-structure choice
    # (None on format-v1 streams), part of the jit cache key — not a tracer
    if gaps is None:
        del impl, interpret      # sequential path; uniform cache key
        return ref.inflate_seq_ref(words, bits_used, n_valid, table,
                                   max_len_static)
    if impl == "pallas":
        return kernel.inflate_pallas(words, n_valid, gaps, table, sub_size,
                                     max_len=max_len_static,
                                     interpret=interpret)
    return ref.inflate_gap_ref(words, n_valid, gaps, table, sub_size,
                               max_len_static)


def for_shape(r: dispatch.Resolved, chunk: int,
              sub_size: int) -> dispatch.Resolved:
    """`r` for a gap-array stream of `chunk`-symbol chunks, or the jax
    reference where the Pallas kernel cannot take the subchunk
    (`kernel.fits`)."""
    if r.impl == "pallas" and not kernel.fits(chunk, sub_size):
        return dispatch.Resolved("jax", False)
    return r


def inflate(words, bits_used, n_valid, table, max_len_static: int,
            gaps=None, sub_size: Optional[int] = None,
            impl: Optional[str] = None, interpret: Optional[bool] = None):
    r = dispatch.resolve(KERNEL, impl, interpret)
    if isinstance(table, hf.Codebook):
        table = hf.decode_table(table.lengths, max_len_static)
    if gaps is None:
        if r.impl == "pallas" and impl is not None:
            raise NotImplementedError(
                "inflate impl='pallas' needs the gap array: the Pallas "
                "kernel is the gap-array subchunk decoder; gap-less "
                "(format v1) streams decode via the sequential jax path")
        sub_size = 0                       # unused on the sequential path
    else:
        if sub_size is None:
            sub_size = words.shape[1] // gaps.shape[1]
        r = for_shape(r, words.shape[1], sub_size)
    return _inflate_jit(words, bits_used, n_valid, table, gaps,
                        max_len_static, sub_size, r.impl, r.interpret)
