"""``warn_once``, the process-wide deprecation helper.

Python's own per-location warning dedup resets whenever the filter stack
changes (pytest installs ``always``), so shims that should warn exactly
once per process keep their own seen-set here.
"""
from __future__ import annotations

import warnings
from typing import Set

_WARNED: Set[str] = set()


def warn_once(key: str, message: str, *, category=DeprecationWarning,
              stacklevel: int = 3) -> None:
    """Emit `message` the first time `key` is seen in this process.

    Deliberately immune to warning-filter resets: deprecation shims on
    hot paths (per-gradient, per-KV-block) must not spam once per call
    under pytest's ``always`` filter.
    """
    if key in _WARNED:
        return
    _WARNED.add(key)
    warnings.warn(message, category, stacklevel=stacklevel)
