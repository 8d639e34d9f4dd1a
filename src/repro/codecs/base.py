"""The `Codec` protocol and the string-keyed codec registry.

Every compression surface in the repo implements one contract:

    encode(x, *, cfg=None)      -> Container        (device pytree + header)
    decode(container, *, like)  -> jax.Array        (header-honoring inverse)
    pack(container)             -> Container        (host/storage form)
    unpack(container)           -> Container        (back to device form)

`decode` needs nothing but the container — dtype, shape and every codec
parameter ride in the header.  `like` optionally overrides the output
dtype/shape (elastic restore).  `pack` defaults to pulling the payload to
host numpy; codecs with a denser storage form (cuSZ's per-chunk word
packing) override it, and `decode` transparently unpacks packed input.

Registry: `get("cusz")`, `get("int8")`, `get("int8-block", axis=2)`, ...
Construction kwargs configure the codec instance; encode/decode stay
config-free so a codec object is a static, hashable policy.
"""
from __future__ import annotations

from typing import Callable, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.debug import spans

from .container import (ChecksumError, Container, Header, check_container,
                        make_header, stamp_checksum, verify_container)


class Codec:
    """Base class: subclasses set `name`/`version`, implement encode/decode.

    Instances must be cheap, immutable and hashable (frozen dataclasses):
    they are used as static jit cache keys by consumers.
    """

    name: str = "?"
    version: int = 1
    #: Sharded-encode capability declaration, checked statically by
    #: repro-lint (R3): a codec either overrides `shard_axis` +
    #: `payload_axes` (split-stable along some axis) or sets
    #: ``shardable = False`` to opt out explicitly — the checkpoint
    #: planner then keeps each leaf whole on one owner shard.
    shardable: bool = True

    # -- required -----------------------------------------------------------
    def encode(self, x, *, cfg=None) -> Container:
        raise NotImplementedError

    def decode(self, c: Container, *, like=None) -> jax.Array:
        raise NotImplementedError

    # -- storage form (override when a denser packing exists) ---------------
    def pack(self, c: Container) -> Container:
        """Host/storage form: numpy payload, `packed=True` plus a payload
        crc32 (``checksum``) in the header."""
        if c.header.param("packed"):
            return c
        payload = {}
        for k, v in c.payload.items():
            spans.count_sync(v)
            # repro-lint: allow[host-sync] pack() IS the storage boundary
            payload[k] = np.asarray(jax.device_get(v))
        return stamp_checksum(
            Container(c.header.with_params(packed=True), payload))

    def unpack(self, c: Container) -> Container:
        """Inverse of `pack`: device arrays, storage-only params dropped
        (``checksum`` must not leak into device headers, which serve as
        static jit cache keys)."""
        if not c.header.param("packed"):
            return c
        payload = {k: jnp.asarray(v) for k, v in c.payload.items()}
        return Container(
            c.header.with_params(packed=False).without_params("checksum"),
            payload)

    # -- shared helpers -----------------------------------------------------
    def _header(self, x, **params) -> Header:
        return make_header(self.name, self.version, x, **params)

    def _finish(self, y: jax.Array, header: Header, like) -> jax.Array:
        """Cast/reshape decode output per the header (or `like` override)."""
        if like is not None:
            return y.reshape(tuple(like.shape)).astype(like.dtype)
        return y.reshape(header.shape).astype(np.dtype(header.dtype))

    def stored_nbytes(self, c: Container) -> int:
        """Bytes this container occupies in storage form."""
        return self.pack(c).nbytes

    def valid(self, c: Container) -> bool:
        """Whether this (device-form) container decodes faithfully.
        Codecs with capacity limits override (cuSZ: outlier overflow)."""
        return True

    # -- sharded encode (the per-host checkpoint write path) ----------------
    #
    # A codec is *split-stable* along an axis when encoding each slice
    # independently decodes to exactly what encoding the whole tensor
    # would — so a sharded save is bit-identical to a single-file save.
    # Elementwise codecs (lossless, int8 with a pinned global scale,
    # int8-block with block-aligned splits) qualify; chunked-transform
    # codecs (cusz, zfp: prediction/blocking crosses slice boundaries)
    # do not and return None, which makes the checkpoint planner assign
    # the whole leaf to one owner shard instead of splitting it.

    def shard_axis(self, shape, nshards: int):
        """Axis to split a `shape` tensor over `nshards` hosts, or None
        when this codec cannot split it without changing the decode."""
        return None

    def encode_parts(self, x, axis: int, nshards: int):
        """Encode `x` as `nshards` independent slice containers along
        `axis`.  Must be bit-equivalent to `encode(x)` on decode; codecs
        with cross-slice state (per-tensor scales) override to pin it."""
        step = x.shape[axis] // nshards
        idx = [slice(None)] * x.ndim
        parts = []
        for h in range(nshards):
            idx[axis] = slice(h * step, (h + 1) * step)
            parts.append(self.encode(x[tuple(idx)]))
        return parts

    def payload_axes(self, axis: int):
        """Per-field concat axis for reassembling slice containers along
        source `axis` in payload space (`container.concat_containers`),
        or None when payload-space merge is unsupported — the loader
        then decodes each part and concatenates values."""
        return None


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

_FACTORIES: Dict[str, Callable[..., Codec]] = {}
_DEFAULTS: Dict[str, Codec] = {}      # cache for kwarg-less lookups


def register(name: str, factory: Callable[..., Codec]) -> None:
    """Register a codec factory under a string key.  `factory(**kwargs)`
    must return a configured `Codec` instance."""
    _FACTORIES[name] = factory
    _DEFAULTS.pop(name, None)


def get(name: str, **kwargs) -> Codec:
    """Look up a configured codec: `get("cusz", eb=1e-4, eb_mode="valrel")`.
    Without kwargs the default-configured instance is cached and shared."""
    if name not in _FACTORIES:
        raise KeyError(f"unknown codec {name!r}; registered: {names()}")
    if not kwargs:
        if name not in _DEFAULTS:
            _DEFAULTS[name] = _FACTORIES[name]()
        return _DEFAULTS[name]
    return _FACTORIES[name](**kwargs)


def names() -> List[str]:
    return sorted(_FACTORIES)


def get_block_codec(name: str, *, axis: int, block: int) -> Codec:
    """Look up a codec that quantizes blockwise along one axis (the wire/
    cache format the KV cache and the a2a reshard need).  Raises a clear
    error for registry ids that don't take axis/block configuration."""
    try:
        return get(name, axis=axis, block=block)
    except TypeError:
        raise ValueError(
            f"codec {name!r} is not a blockwise wire codec: it must accept "
            f"axis=/block= configuration (e.g. 'int8-block')") from None


def decode(c: Container, *, like=None, verify: bool = False,
           **codec_kwargs) -> jax.Array:
    """Decode a container by its own header — the codec id, version, dtype
    and shape all come from the container; nothing else is required.
    `codec_kwargs` configure the decode-side codec (e.g. kernel_impl).

    ``verify=True`` checks the payload against the header's crc32 before
    decoding and raises `ChecksumError` on mismatch — the restore paths
    (checkpoint load, wire arrival) opt in; hot device-side paths skip
    the host-side hash."""
    if verify:
        check_container(c)
    codec = get(c.header.codec, **codec_kwargs)
    if c.header.version > codec.version:
        raise ValueError(
            f"container written by {c.header.codec} v{c.header.version}, "
            f"but installed codec is v{codec.version}")
    return codec.decode(c, like=like)
