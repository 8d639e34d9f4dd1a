"""Multi-level interpolation predictor (cuSZ-i, arXiv 2312.05492) behind
the `Predictor` stage protocol.

Scheme: prequantize ONCE to exact int32 (the pipeline's only lossy
step), then lift level by level — along each axis the samples split into
even/odd strides, every odd sample is predicted with an integer cubic
stencil over its four even neighbors, and only the residual is kept; the
even half recurses until every dim is at the anchor size.  The tiny
anchor grid rides in the payload uncompressed (int32), exactly like
cuSZ-i stores its anchor points every 2^L stride.

Because the lifting runs on prequantized integers with floor-division
arithmetic, encode and decode are exact inverses: the single prequant
rounding bounds the error by eb regardless of level count (unlike
per-level float requantization, which compounds).  On smooth fields the
cubic stencil leaves far smaller residuals than the blocked
first-difference Lorenzo predictor (no per-block boundary resets
either), which concentrates the quant-code histogram and directly buys
compression ratio from the downstream encoder at the same bound.

The level plan is static (a pure function of the field shape), so the
whole multi-level loop unrolls inside one jit trace — per-level shapes
change, which rules out `lax.scan`, but level count is log2(max dim).
The residual stream order (level-major, then row-major in working-axis-
moved layout) is likewise static and shared by predict/reconstruct.

Predict runs under the named scopes `stage.prequant`, `stage.interp`
(the lifting steps and the quant codes) and `stage.outliers`;
reconstruct under `stage.scatter` and `stage.interleave` (the inverse
lifting and the dequantisation).  Each compress call counts
`interp.tiles` (`repro.debug.spans`): the grid steps of the Pallas
predict kernel, from the static plan.
"""
from __future__ import annotations

import functools
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.debug import spans
from repro.kernels.interp import kernel as interp_kernel
from repro.kernels.interp import ops as interp_ops

from . import dualquant as dq
from . import stages

#: stop splitting once every dim is at most this (the anchor grid)
ANCHOR = 4


@functools.lru_cache(maxsize=512)
def interp_plan(shape: Tuple[int, ...]
                ) -> Tuple[Tuple[Tuple[int, Tuple[int, ...]], ...],
                           Tuple[int, ...]]:
    """Static level plan for `shape`.

    Returns (steps, anchor_shape): each step is (axis, shape-before-
    split); the split replaces size s with ceil(s/2) evens, emitting
    floor(s/2) odd residuals.  At least one step is forced for tiny
    fields (so the encoder always sees a nonempty code stream) unless
    every dim is 1.
    """
    s = list(shape)
    steps: List[Tuple[int, Tuple[int, ...]]] = []
    while max(s) > ANCHOR:
        for a in range(len(s)):
            if s[a] > ANCHOR:
                steps.append((a, tuple(s)))
                s[a] = (s[a] + 1) // 2
    if not steps and max(s) >= 2:
        a = int(np.argmax(s))
        steps.append((a, tuple(s)))
        s[a] = (s[a] + 1) // 2
    return tuple(steps), tuple(s)


def predict_tiles(shape: Tuple[int, ...]) -> int:
    """Grid steps the Pallas predict kernel runs over a field of `shape`:
    one level of R rows takes ceil(R / row tile) steps."""
    return sum(interp_kernel.grid_steps(int(np.prod(shp)) // shp[axis])
               for axis, shp in interp_plan(shape)[0])


def _n_residuals(shape: Tuple[int, ...]) -> Tuple[int, int]:
    steps, anchor_shape = interp_plan(shape)
    n_res = int(np.prod(shape)) - int(np.prod(anchor_shape))
    return n_res, int(np.prod(anchor_shape))


def _pad_even(e2: jax.Array) -> jax.Array:
    """[R, me] -> [R, me+3]: edge-replicate 1 left / 2 right so every odd
    position gathers four even neighbors at static offsets."""
    return jnp.concatenate([e2[:, :1], e2, e2[:, -1:], e2[:, -1:]], axis=1)


def _interleave(even: jax.Array, odd: jax.Array) -> jax.Array:
    """Merge even/odd strides back along the last axis (exact inverse of
    the [0::2]/[1::2] split)."""
    s = even.shape[-1] + odd.shape[-1]
    out = jnp.zeros(even.shape[:-1] + (s,), even.dtype)
    out = out.at[..., 0::2].set(even)
    return out.at[..., 1::2].set(odd)


class InterpPredictor(stages.Predictor):
    name = "interp"
    kernels = ("interp.predict", "interp.reconstruct")
    payload_keys = ("out_idx", "out_val", "n_outliers", "anchor")

    def n_codes(self, shape, cfg) -> int:
        n_res, _ = _n_residuals(shape)
        return max(1, n_res)

    def predict(self, data, cfg, eb, pp):
        steps, anchor_shape = interp_plan(data.shape)
        kw = pp.for_kernel("interp.predict").as_kwargs()
        with jax.named_scope("stage.prequant"):
            x = dq.prequant(data, eb)
        with jax.named_scope("stage.interp"):
            parts = []
            for axis, _ in steps:
                xm = jnp.moveaxis(x, axis, -1)
                even, odd = xm[..., 0::2], xm[..., 1::2]
                e2 = even.reshape(-1, even.shape[-1])
                o2 = odd.reshape(-1, odd.shape[-1])
                r2 = interp_ops.residual_rows(_pad_even(e2), o2, **kw)
                parts.append(r2.reshape(-1))
                x = jnp.moveaxis(even, -1, axis)
            resid = (jnp.concatenate(parts) if parts
                     else jnp.zeros((0,), jnp.int32))
            if resid.shape[0] < self.n_codes(data.shape, cfg):
                # degenerate all-ones shape: emit one in-cap dummy symbol
                # so the encoder never sees an empty stream
                resid = jnp.zeros((1,), jnp.int32)
            codes, in_cap = dq.postquant_codes(resid, cfg.nbins)
            anchor = x.reshape(-1).astype(jnp.int32)
        cap = stages.outlier_capacity(int(np.prod(data.shape)), cfg)
        with jax.named_scope("stage.outliers"):
            oidx, oval, n_out = dq.extract_outliers(
                resid, in_cap.reshape(-1), cap)
        return codes, {"out_idx": oidx, "out_val": oval,
                       "n_outliers": n_out, "anchor": anchor}

    def record_call(self, shape, cfg, pp):
        if pp.for_kernel("interp.predict").impl == "pallas":
            spans.count("interp.tiles", predict_tiles(tuple(shape)))

    def reconstruct(self, codes_flat, payload, cfg, eb, shape, pp):
        steps, anchor_shape = interp_plan(shape)
        kw = pp.for_kernel("interp.reconstruct").as_kwargs()
        nc = self.n_codes(shape, cfg)
        with jax.named_scope("stage.scatter"):
            delta = dq.codes_to_delta(codes_flat[:nc], cfg.nbins)
            delta = dq.scatter_outliers(delta, payload["out_idx"],
                                        payload["out_val"])
        # replay the plan to get each step's residual segment offset and
        # moved-layout odd shape (all static)
        segs = []
        off = 0
        for axis, shp in steps:
            moved = shp[:axis] + shp[axis + 1:] + (shp[axis],)
            mo = shp[axis] // 2
            odd_shape = moved[:-1] + (mo,)
            segs.append((axis, odd_shape, off))
            off += int(np.prod(odd_shape))
        with jax.named_scope("stage.interleave"):
            x = payload["anchor"].reshape(anchor_shape)
            for axis, odd_shape, off in reversed(segs):
                em = jnp.moveaxis(x, axis, -1)
                e2 = em.reshape(-1, em.shape[-1])
                mo = odd_shape[-1]
                r2 = delta[off:off + int(np.prod(odd_shape))].reshape(-1, mo)
                o2 = interp_ops.odd_rows(_pad_even(e2), r2, **kw)
                om = o2.reshape(odd_shape)
                x = jnp.moveaxis(_interleave(em, om), -1, axis)
            return dq.dequant(x, eb)

    def header_params(self, shape, cfg):
        return {"outlier_frac": float(cfg.outlier_frac)}

    def valid(self, payload):
        return stages._outlier_valid(payload)

    def pack_payload(self, payload):
        d = stages._pack_outliers(payload)
        d["anchor"] = np.asarray(payload["anchor"], np.int32)
        return d

    def unpack_payload(self, packed, cfg, shape):
        d = stages._unpack_outliers(packed)
        d["anchor"] = np.asarray(packed["anchor"], np.int32)
        return d

    def stored_nbytes(self, packed):
        return (len(packed["out_idx"]) * 8
                + np.asarray(packed["anchor"]).size * 4)


stages.register_predictor("interp", InterpPredictor)
