"""Seeded fault injection for the resilience layer.

A `ChaosConfig` describes which faults to inject; `use_chaos(cfg)` arms a
`ChaosMonkey` for a scope and `current()` returns it to the code paths
that consult it (the trainer's step loop, the checkpoint shard writer).
Every schedule is deterministic: the same config and seed inject the same
faults at the same steps, so the recovery paths are testable.

Fault classes:

* **straggler** — one host runs slow for steps in ``[start, stop)``.
  Simulation contract: with per-host work shares ``share`` (a simplex,
  uniform by default) over ``n`` hosts, host ``h`` takes
  ``dur[h] = compute·share[h]·n`` plus, on the straggler,
  ``delay·share[h]·n``.  Shrinking the straggler's share therefore
  genuinely shrinks its duration, and `inject_step` sleeps the modelled
  extra for real, so mitigation shows up in wall-clock time.
* **writer** — the next ``failures`` shard writes either raise
  `TransientWriteError` before writing (``kind=raise``, an `OSError`,
  so the async writer's retry loop absorbs it) or silently truncate the
  written file to 60% (``kind=partial``, caught by checksums at restore).
* **corrupt** — the next ``shards`` written files get one byte flipped
  in their second half.
* **nan** — the loss at the listed steps reads as NaN.

Spec mini-language (``launch/train.py --chaos``): groups separated by
``;``, each ``name:key=value,...``; ``nan:steps=7+8`` lists steps with
``+``.  See `from_spec`.
"""
from __future__ import annotations

import contextlib
import dataclasses
import os
import threading
import time
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

__all__ = ["ChaosConfig", "ChaosMonkey", "TransientWriteError", "use_chaos",
           "current", "from_spec", "corrupt_file", "corrupt_container"]

_PARTIAL_KEEP = 0.6          # fraction of a partial write that survives


class TransientWriteError(OSError):
    """An injected, retryable I/O failure (OSError-classed on purpose)."""


@dataclasses.dataclass(frozen=True)
class ChaosConfig:
    nhosts: int = 1
    seed: int = 0
    straggler_host: Optional[int] = None
    straggler_delay_s: float = 0.0
    straggler_start: int = 0
    straggler_stop: Optional[int] = None
    writer_failures: int = 0
    writer_fault: str = "raise"              # "raise" | "partial"
    nan_steps: Tuple[int, ...] = ()
    corrupt_shards: int = 0

    def __post_init__(self):
        if self.writer_fault not in ("raise", "partial"):
            raise ValueError(f"writer fault must be 'raise' or 'partial', "
                             f"got {self.writer_fault!r}")
        if (self.straggler_host is not None
                and not 0 <= self.straggler_host < self.nhosts):
            raise ValueError(f"straggler host {self.straggler_host} outside "
                             f"the {self.nhosts}-host cluster")


class ChaosMonkey:
    """The armed injector for one `ChaosConfig`; logs every fault it
    injects to ``events`` as ``{"kind": ..., ...}`` dicts."""

    def __init__(self, cfg: ChaosConfig):
        self.cfg = cfg
        self.events: List[Dict[str, Any]] = []
        self._rng = np.random.default_rng(cfg.seed)
        self._writer_left = cfg.writer_failures
        self._corrupt_left = cfg.corrupt_shards
        self._lock = threading.Lock()        # writes come from a worker

    # -- straggler --------------------------------------------------------

    def straggler_active(self, step: int) -> bool:
        c = self.cfg
        return (c.straggler_host is not None and step >= c.straggler_start
                and (c.straggler_stop is None or step < c.straggler_stop))

    def host_step_times(self, step: int, compute_s: float,
                        shares: Optional[Sequence[float]] = None
                        ) -> np.ndarray:
        """Modelled per-host durations of one step (the contract above)."""
        n = self.cfg.nhosts
        sh = (np.full(n, 1.0 / n) if shares is None
              else np.asarray(shares, np.float64))
        durs = compute_s * sh * n
        if self.straggler_active(step):
            h = self.cfg.straggler_host
            durs[h] += self.cfg.straggler_delay_s * sh[h] * n
        return durs

    def inject_step(self, step: int, compute_s: float,
                    shares: Optional[Sequence[float]] = None
                    ) -> Tuple[float, np.ndarray]:
        """Sleep the modelled extra over ``compute_s`` and return
        ``(cluster step time, per-host durations)``."""
        durs = self.host_step_times(step, compute_s, shares)
        total = float(durs.max())
        extra = total - compute_s
        if self.straggler_active(step):
            self.events.append({"kind": "straggler-delay", "step": int(step),
                                "host": self.cfg.straggler_host,
                                "extra_s": extra})
        if extra > 0:
            time.sleep(extra)
        return total, durs

    # -- loss -------------------------------------------------------------

    def nan_burst(self, step: int) -> bool:
        hit = step in self.cfg.nan_steps
        if hit:
            self.events.append({"kind": "nan-burst", "step": int(step)})
        return hit

    # -- writes -----------------------------------------------------------

    def pre_write(self, path: str) -> None:
        """Raise `TransientWriteError` while the ``raise`` budget lasts."""
        with self._lock:
            if self.cfg.writer_fault != "raise" or self._writer_left <= 0:
                return
            self._writer_left -= 1
            self.events.append({"kind": "write-fault", "path": path})
        raise TransientWriteError(f"chaos: injected write failure on {path}")

    def post_write(self, path: str) -> None:
        """Damage a just-written file while a ``partial`` or ``corrupt``
        budget lasts (partial writes first)."""
        with self._lock:
            if self.cfg.writer_fault == "partial" and self._writer_left > 0:
                self._writer_left -= 1
                size = os.path.getsize(path)
                with open(path, "r+b") as f:
                    f.truncate(int(size * _PARTIAL_KEEP))
                self.events.append({"kind": "partial-write", "path": path})
            elif self._corrupt_left > 0:
                self._corrupt_left -= 1
                corrupt_file(path, seed=int(self._rng.integers(1 << 31)))
                self.events.append({"kind": "corrupt-shard", "path": path})


# ---------------------------------------------------------------------------
# ambient monkey
# ---------------------------------------------------------------------------

_armed: Optional[ChaosMonkey] = None     # process-wide: writer threads see it


def current() -> Optional[ChaosMonkey]:
    """The monkey armed by the innermost `use_chaos`, or None."""
    return _armed


@contextlib.contextmanager
def use_chaos(cfg: Optional[ChaosConfig]) -> Iterator[Optional[ChaosMonkey]]:
    """Arm a `ChaosMonkey` for the scope; ``None`` arms nothing."""
    global _armed
    if cfg is None:
        yield None
        return
    prev = _armed
    _armed = monkey = ChaosMonkey(cfg)
    try:
        yield monkey
    finally:
        _armed = prev


# ---------------------------------------------------------------------------
# spec mini-language
# ---------------------------------------------------------------------------

_GROUPS = {
    "straggler": {"host": ("straggler_host", int),
                  "delay": ("straggler_delay_s", float),
                  "start": ("straggler_start", int),
                  "stop": ("straggler_stop", int)},
    "writer": {"failures": ("writer_failures", int),
               "kind": ("writer_fault", str)},
    "nan": {"steps": ("nan_steps",
                      lambda v: tuple(int(s) for s in v.split("+") if s))},
    "corrupt": {"shards": ("corrupt_shards", int)},
}
_GROUP_DEFAULTS = {"writer": {"writer_failures": 1},
                   "corrupt": {"corrupt_shards": 1}}


def from_spec(spec: str, seed: int = 0, nhosts: int = 1) -> ChaosConfig:
    """Parse e.g. ``"straggler:host=1,delay=0.05;writer:failures=2"``.
    ``nhosts`` widens to cover a named straggler host."""
    kw: Dict[str, Any] = {}
    for group in filter(None, (g.strip() for g in spec.split(";"))):
        name, _, body = group.partition(":")
        name = name.strip()
        if name not in _GROUPS:
            raise ValueError(f"unknown chaos group {name!r}; expected one "
                             f"of {sorted(_GROUPS)}")
        kw.update(_GROUP_DEFAULTS.get(name, {}))
        for item in filter(None, (i.strip() for i in body.split(","))):
            key, _, val = item.partition("=")
            if key not in _GROUPS[name]:
                raise ValueError(f"unknown key {key!r} in chaos group "
                                 f"{name!r}; expected {sorted(_GROUPS[name])}")
            field, conv = _GROUPS[name][key]
            kw[field] = conv(val)
    host = kw.get("straggler_host")
    if host is not None:
        nhosts = max(nhosts, host + 1)
    return ChaosConfig(nhosts=nhosts, seed=seed, **kw)


# ---------------------------------------------------------------------------
# corruption helpers
# ---------------------------------------------------------------------------

def corrupt_file(path: str, seed: int = 0) -> int:
    """Flip one byte in the second half of ``path`` (seeded position);
    returns the offset."""
    size = os.path.getsize(path)
    if size == 0:
        raise ValueError(f"cannot corrupt empty file {path}")
    rng = np.random.default_rng(seed)
    off = int(rng.integers(size // 2, size))
    with open(path, "r+b") as f:
        f.seek(off)
        b = f.read(1)[0]
        f.seek(off)
        f.write(bytes([b ^ 0xFF]))
    return off


def corrupt_container(c, seed: int = 0):
    """A copy of container ``c`` with one payload byte flipped (header,
    checksum included, unchanged), so verification must fail."""
    import jax
    rng = np.random.default_rng(seed)
    # repro-lint: allow[host-sync] corruption is a host/storage-side edit
    payload = {k: np.array(jax.device_get(v)) for k, v in c.payload.items()}
    keys = sorted(k for k, v in payload.items() if v.nbytes > 0)
    if not keys:
        raise ValueError("container has no payload bytes to corrupt")
    k = keys[int(rng.integers(len(keys)))]
    raw = np.ascontiguousarray(payload[k]).reshape(-1).view(np.uint8)
    raw[int(rng.integers(raw.size))] ^= 0xFF
    payload[k] = raw.view(payload[k].dtype).reshape(payload[k].shape)
    return c.replace(payload=payload)
