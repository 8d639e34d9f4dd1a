"""Self-describing versioned container: the one wire/storage format every
codec produces and consumes.

A `Container` is a pytree of payload arrays plus a static `Header` that
records everything needed to decode — codec id, codec version, the source
array's dtype and shape, and the codec's static parameters (error bound,
bin count, block table, ...).  Nothing travels out-of-band: the historical
`(packed_dict, eb, shape)` caller-side plumbing (which silently dropped
the source dtype) is replaced by `codecs.decode(container)`.

The header is the pytree aux data, so containers cross `jax.jit`
boundaries with the header as a static cache key, and `jax.tree` utilities
treat the payload arrays as leaves.  `to_arrays`/`from_arrays` give the
host/storage view (npz-friendly field dict + JSON-able header).
"""
from __future__ import annotations

import dataclasses
import zlib
from typing import Any, Dict, Mapping, Tuple

import jax
import numpy as np

from repro.debug import spans

CONTAINER_FORMAT = 1


class ChecksumError(ValueError):
    """A container's payload does not match its header checksum — the
    bytes were corrupted somewhere between `pack` and now."""


def _freeze(v):
    """Make a params value hashable (lists -> tuples, recursively)."""
    if isinstance(v, (list, tuple)):
        return tuple(_freeze(x) for x in v)
    if isinstance(v, (np.integer,)):
        return int(v)
    if isinstance(v, (np.floating,)):
        return float(v)
    return v


def _jsonable(v):
    if isinstance(v, tuple):
        return [_jsonable(x) for x in v]
    return v


@dataclasses.dataclass(frozen=True)
class Header:
    """Static, hashable codec header (safe as a jit static argument)."""
    codec: str                                   # registry id, e.g. "cusz"
    version: int                                 # codec format version
    dtype: str                                   # source dtype name
    shape: Tuple[int, ...]                       # source shape
    params: Tuple[Tuple[str, Any], ...] = ()     # static codec params

    def param(self, key: str, default=None):
        for k, v in self.params:
            if k == key:
                return v
        return default

    def with_params(self, **kw) -> "Header":
        """Return a header with `kw` merged into params (replace on key).
        Params stay key-sorted — the canonical order `make_header` and
        `from_json` produce — so header equality (and the jit cache key)
        never depends on merge order."""
        items = [(k, v) for k, v in self.params if k not in kw]
        items += [(k, _freeze(v)) for k, v in kw.items()]
        return dataclasses.replace(self, params=tuple(sorted(items)))

    def without_params(self, *keys: str) -> "Header":
        """Return a header with `keys` removed from params.  `unpack`
        uses this to drop storage-only params (``checksum``) so device
        headers — and therefore jit cache keys — never vary with the
        stored bytes."""
        return dataclasses.replace(
            self, params=tuple((k, v) for k, v in self.params
                               if k not in keys))

    def to_json(self) -> Dict[str, Any]:
        return {"format": CONTAINER_FORMAT, "codec": self.codec,
                "version": self.version, "dtype": self.dtype,
                "shape": list(self.shape),
                "params": {k: _jsonable(v) for k, v in self.params}}

    @staticmethod
    def from_json(d: Mapping[str, Any]) -> "Header":
        fmt = d.get("format", CONTAINER_FORMAT)
        if fmt > CONTAINER_FORMAT:
            raise ValueError(f"container format {fmt} is newer than this "
                             f"reader ({CONTAINER_FORMAT})")
        params = tuple(sorted((k, _freeze(v))
                              for k, v in dict(d.get("params", {})).items()))
        return Header(codec=str(d["codec"]), version=int(d["version"]),
                      dtype=str(d["dtype"]), shape=tuple(d["shape"]),
                      params=params)


def make_header(codec: str, version: int, like, **params) -> Header:
    """Header for a source array `like` (anything with .dtype/.shape)."""
    items = tuple(sorted((k, _freeze(v)) for k, v in params.items()))
    return Header(codec=codec, version=int(version),
                  dtype=np.dtype(like.dtype).name,
                  shape=tuple(int(s) for s in like.shape), params=items)


@jax.tree_util.register_pytree_node_class
class Container:
    """header (static) + payload (dict of arrays; the pytree leaves)."""

    __slots__ = ("header", "payload")

    def __init__(self, header: Header, payload: Dict[str, Any]):
        self.header = header
        self.payload = dict(payload)

    # -- pytree protocol ----------------------------------------------------
    def tree_flatten(self):
        keys = tuple(sorted(self.payload))
        return tuple(self.payload[k] for k in keys), (self.header, keys)

    @classmethod
    def tree_unflatten(cls, aux, children):
        header, keys = aux
        return cls(header, dict(zip(keys, children)))

    # -- conveniences -------------------------------------------------------
    @property
    def nbytes(self) -> int:
        # each array's own size: no pull of device data to the host
        return sum(int(v.nbytes if hasattr(v, "nbytes")
                       else np.asarray(v).nbytes)
                   for v in self.payload.values())

    def replace(self, header: Header = None, payload=None) -> "Container":
        return Container(header if header is not None else self.header,
                         payload if payload is not None else self.payload)

    def __repr__(self):
        h = self.header
        return (f"Container(codec={h.codec!r}, v{h.version}, "
                f"dtype={h.dtype}, shape={h.shape}, "
                f"fields={sorted(self.payload)})")


# ---------------------------------------------------------------------------
# Payload integrity (crc32 checksums, stamped by `Codec.pack`)
# ---------------------------------------------------------------------------

def payload_crc32(payload: Mapping[str, Any]) -> int:
    """crc32 over the payload's canonical byte stream: sorted field names
    with each field's dtype, shape and raw bytes.  Covering the metadata
    too means a corrupted npz that swaps/reshapes a field — not just one
    that flips data bytes — also fails verification."""
    crc = 0
    for k in sorted(payload):
        spans.count_sync(payload[k])
        # repro-lint: allow[host-sync] checksumming is a host/storage op
        arr = np.ascontiguousarray(np.asarray(jax.device_get(payload[k])))
        meta = f"{k}:{arr.dtype.str}:{arr.shape};".encode()
        crc = zlib.crc32(arr.tobytes(), zlib.crc32(meta, crc))
    return crc & 0xFFFFFFFF


def stamp_checksum(c: "Container") -> "Container":
    """Record the payload crc32 in the header (storage-form containers;
    every `pack` implementation ends with this)."""
    with spans.span("codec.pack.crc32"):
        return c.replace(header=c.header.with_params(
            checksum=payload_crc32(c.payload)))


def verify_container(c: "Container") -> bool:
    """True when the payload matches the header checksum.  Containers
    without a checksum param (pre-checksum writers, device-form headers)
    verify trivially — absence of evidence is not corruption."""
    want = c.header.param("checksum")
    return want is None or payload_crc32(c.payload) == int(want)


def check_container(c: "Container") -> None:
    """`verify_container`, but raising `ChecksumError` with the mismatch
    detail — the restore-path spelling."""
    want = c.header.param("checksum")
    if want is None:
        return
    got = payload_crc32(c.payload)
    if got != int(want):
        raise ChecksumError(
            f"container payload checksum mismatch for codec "
            f"{c.header.codec!r} shape {c.header.shape}: header says "
            f"{int(want):#010x}, payload hashes to {got:#010x}")


# ---------------------------------------------------------------------------
# Shard reassembly (payload-space concatenation)
# ---------------------------------------------------------------------------

def concat_containers(parts, axis: int, field_axes: Mapping[str, Any]
                      ) -> Container:
    """Merge axis-sharded containers of one codec into a single container
    without decoding: each payload field is concatenated along the axis
    `field_axes` maps it to (None = shared/replicated field, taken from
    the first part).  Headers must agree except for ``shape[axis]``; the
    merged header sums that dim.  This is the elastic-restore wire path:
    what moves between hosts is the codec's compressed payload, never the
    decoded array."""
    h0 = parts[0].header
    # per-part checksums necessarily differ (different bytes) and do not
    # describe the merged payload — exclude them from the compatibility
    # check and drop them from the merged header
    def _cmp(h):
        return tuple((k, v) for k, v in h.params if k != "checksum")
    for p in parts[1:]:
        if p.header.codec != h0.codec or _cmp(p.header) != _cmp(h0):
            raise ValueError(f"cannot concat containers with differing "
                             f"codec/params: {p.header} vs {h0}")
    h0 = h0.without_params("checksum")
    shape = list(h0.shape)
    shape[axis] = sum(int(p.header.shape[axis]) for p in parts)
    payload: Dict[str, Any] = {}
    for field, fa in field_axes.items():
        vals = [p.payload[field] for p in parts]
        if fa is None:
            payload[field] = vals[0]
        elif all(isinstance(v, np.ndarray) for v in vals):
            payload[field] = np.concatenate(vals, axis=fa)
        else:
            payload[field] = jax.numpy.concatenate(
                [jax.numpy.asarray(v) for v in vals], axis=fa)
    return Container(dataclasses.replace(h0, shape=tuple(shape)), payload)


# ---------------------------------------------------------------------------
# Host / storage view
# ---------------------------------------------------------------------------

def to_arrays(c: Container) -> Tuple[Dict[str, Any], Dict[str, np.ndarray]]:
    """(header-json, {field: numpy array}) — the npz/storage form."""
    arrays = {}
    for k, v in c.payload.items():
        spans.count_sync(v)
        # repro-lint: allow[host-sync] to_arrays() is the storage boundary
        arrays[k] = np.asarray(jax.device_get(v))
    return c.header.to_json(), arrays


def from_arrays(header, arrays: Mapping[str, Any]) -> Container:
    """Rebuild a container from `to_arrays` output (header json or Header)."""
    h = header if isinstance(header, Header) else Header.from_json(header)
    return Container(h, dict(arrays))
