"""Identity codec: raw arrays behind the same `Codec` contract.

Exists so every checkpoint leaf — compressed or not — goes through one
container format, and so non-native dtypes survive storage: npz writes
bfloat16 but loads it back as raw void bytes, so `pack` bitcasts any
non-builtin dtype to a same-width unsigned view and `unpack` restores it
from the header's recorded dtype.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.debug import spans

from .base import Codec, register
from .container import Container, stamp_checksum

_UINT_OF = {1: np.uint8, 2: np.uint16, 4: np.uint32, 8: np.uint64}


@dataclasses.dataclass(frozen=True)
class LosslessCodec(Codec):
    name = "lossless"
    version = 1

    def encode(self, x, *, cfg=None) -> Container:
        return Container(self._header(x), {"data": jnp.asarray(x)})

    def decode(self, c: Container, *, like=None) -> jax.Array:
        c = self.unpack(c)
        return self._finish(jnp.asarray(c.payload["data"]), c.header, like)

    def pack(self, c: Container) -> Container:
        if c.header.param("packed"):
            return c
        spans.count_sync(c.payload["data"])
        # repro-lint: allow[host-sync] pack() IS the device->storage boundary
        arr = np.asarray(jax.device_get(c.payload["data"]))
        if arr.dtype.kind not in "biufc":          # e.g. ml_dtypes bfloat16
            arr = arr.view(_UINT_OF[arr.dtype.itemsize])
        return stamp_checksum(
            Container(c.header.with_params(packed=True), {"data": arr}))

    def unpack(self, c: Container) -> Container:
        if not c.header.param("packed"):
            return c
        arr = np.asarray(c.payload["data"])
        want = np.dtype(c.header.dtype)
        if arr.dtype != want and arr.dtype.itemsize == want.itemsize:
            arr = arr.view(want)                   # undo the storage bitcast
        return Container(
            c.header.with_params(packed=False).without_params("checksum"),
            {"data": jnp.asarray(arr)})

    # -- sharded encode: identity is trivially split-stable
    def shard_axis(self, shape, nshards: int):
        from repro.dist.sharding import even_shard_axis
        return even_shard_axis(shape, nshards)

    def payload_axes(self, axis: int):
        return {"data": axis}


register("lossless", lambda **kw: LosslessCodec(**kw))
