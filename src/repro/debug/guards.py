"""Runtime JAX sanitizers as context managers.

Three guards, each wrapping a jax debugging facility into a pass/fail
scope for tests (the static layer is ``tools/lint``; these catch what
static analysis cannot — actual compiles and actual syncs):

* `no_recompiles(max_compiles=N, match=...)` — counts XLA executable
  compilations via ``jax.log_compiles`` while the scope is active and
  raises `RecompileError` when the count exceeds the budget.  Eager ops
  compile tiny helper executables (``jit(convert_element_type)`` …), so
  pass ``match=`` with the jitted function's name to count only the
  executable under test.
* `no_implicit_transfers()` — arms ``jax.transfer_guard``.  On CPU the
  device→host direction is zero-copy and never fires, but implicit
  host→device transfers (e.g. a Python scalar fed to an eager op) DO
  fire even on CPU; on gpu/tpu both directions are guarded.  Prepare
  inputs (``device_put``/``jnp.asarray``) before entering the scope.
* `host_sync_guard(allowed)` — patches ``jax.device_get`` and
  ``jax.block_until_ready`` to attribute each blocking sync to the
  first `repro` source frame on the stack and raises `HostSyncError`
  at scope exit for any site not in `allowed` (the statically waived
  ``allow[host-sync]`` spans, see ``tools.lint.waived_spans``).  This
  is the CPU-meaningful complement to the transfer guard.  A call on
  host data alone (no `jax.Array` leaf) blocks on nothing and is not
  counted: the same rule as the ``host_syncs`` counter
  (`spans.count_sync`).  Limitation:
  ``float()``/``bool()`` on an array sync inside C code and cannot be
  intercepted here — the static layer covers those.
"""
from __future__ import annotations

import contextlib
import dataclasses
import logging
import os
import re
import traceback
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import jax

from .spans import holds_device_data

_COMPILE_RE = re.compile(
    r"Finished XLA compilation of (?:jit\()?([\w<>\-.]+)\)? in")
# loggers that carry compile/trace markers across jax versions
_COMPILE_LOGGERS = ("jax._src.dispatch", "jax._src.interpreters.pxla",
                    "jax.dispatch", "jax.interpreters.pxla")


class GuardError(RuntimeError):
    """Base class for sanitizer failures."""


class RecompileError(GuardError):
    pass


class HostSyncError(GuardError):
    pass


# ---------------------------------------------------------------------------
# no_recompiles
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class CompileLog:
    """Mutable scope state: names of executables compiled so far."""
    compiles: List[str] = dataclasses.field(default_factory=list)

    def count(self) -> int:
        return len(self.compiles)


class _CompileCounter(logging.Handler):
    def __init__(self, log: CompileLog, match: Optional[str]):
        super().__init__(level=logging.DEBUG)
        self._log = log
        self._match = re.compile(match) if match else None

    def emit(self, record: logging.LogRecord) -> None:
        m = _COMPILE_RE.search(record.getMessage())
        if not m:
            return
        name = m.group(1)
        if self._match is not None and not self._match.search(name):
            return
        self._log.compiles.append(name)


@contextlib.contextmanager
def no_recompiles(max_compiles: int = 1,
                  match: Optional[str] = None) -> Iterator[CompileLog]:
    """Fail if more than `max_compiles` XLA compilations happen in scope.

    The common shapes: warm up a function once, then assert steady state
    with ``no_recompiles(max_compiles=0)``; or cover first use with the
    default budget of 1 (compile once, never again).  `match` restricts
    counting to executables whose name matches the regex — e.g.
    ``match=r"^step$"`` for the serve decode step.
    """
    log = CompileLog()
    handler = _CompileCounter(log, match)
    loggers = [logging.getLogger(n) for n in _COMPILE_LOGGERS]
    old = [(lg.level, lg.propagate) for lg in loggers]
    for lg in loggers:
        lg.addHandler(handler)
        if lg.level > logging.WARNING:
            lg.setLevel(logging.WARNING)
        lg.propagate = False      # count, don't spam test output
    try:
        with jax.log_compiles(True):
            yield log
    finally:
        for lg, (lv, prop) in zip(loggers, old):
            lg.removeHandler(handler)
            lg.setLevel(lv)
            lg.propagate = prop
    if log.count() > max_compiles:
        raise RecompileError(
            f"{log.count()} XLA compilation(s) inside a "
            f"no_recompiles(max_compiles={max_compiles}) scope"
            + (f" (match={match!r})" if match else "")
            + f": {log.compiles}")


# ---------------------------------------------------------------------------
# no_implicit_transfers
# ---------------------------------------------------------------------------

@contextlib.contextmanager
def no_implicit_transfers(level: str = "disallow") -> Iterator[None]:
    """Arm ``jax.transfer_guard(level)`` for the scope.

    Levels: "log", "disallow", "log_explicit", "disallow_explicit".
    NOTE: on CPU-only backends host/device transfers are zero-copy and
    jax never classifies them as guarded transfers, so this is a no-op
    there — pair it with `host_sync_guard` for CPU-meaningful coverage.
    """
    with jax.transfer_guard(level):
        yield


# ---------------------------------------------------------------------------
# host_sync_guard
# ---------------------------------------------------------------------------

#: {absolute file path: [(start_line, end_line, reason), ...]}
AllowedSites = Dict[str, Sequence[Tuple[int, int, str]]]


@dataclasses.dataclass
class SyncLog:
    """Syncs attributed to repro source lines during the scope."""
    violations: List[str] = dataclasses.field(default_factory=list)
    allowed_hits: List[str] = dataclasses.field(default_factory=list)


def _attribute_frame(skip_file: str) -> Optional[Tuple[str, int]]:
    """(abs file, line) of the innermost repro-source frame below us."""
    sep = os.sep
    marker = f"{sep}repro{sep}"
    for frame in reversed(traceback.extract_stack()):
        fn = frame.filename
        if fn == skip_file or f"{sep}debug{sep}guards" in fn:
            continue
        if marker in fn and f"{sep}tests{sep}" not in fn:
            return os.path.abspath(fn), frame.lineno
    return None


@contextlib.contextmanager
def host_sync_guard(allowed: Optional[AllowedSites] = None,
                    *, strict: bool = True) -> Iterator[SyncLog]:
    """Intercept blocking syncs (`jax.device_get`, `jax.block_until_ready`)
    issued from `repro` library code during the scope.

    Syncs from statement spans in `allowed` are recorded as hits; any
    other repro-attributed sync is a violation — raised as
    `HostSyncError` at scope exit when `strict`.  Syncs issued directly
    by test/driver code (no repro frame on the stack) are ignored: the
    guard polices the library, not the harness.
    """
    allowed = allowed or {}
    log = SyncLog()
    real_get, real_block = jax.device_get, jax.block_until_ready
    here = __file__

    def _check(kind: str, x) -> None:
        if not holds_device_data(x):
            return
        site = _attribute_frame(here)
        if site is None:
            return
        path, line = site
        for (lo, hi, reason) in allowed.get(path, ()):
            if lo <= line <= hi:
                log.allowed_hits.append(
                    f"{path}:{line} {kind} [waived: {reason}]")
                return
        log.violations.append(f"{path}:{line} {kind}")

    def guarded_get(x):
        _check("jax.device_get", x)
        return real_get(x)

    def guarded_block(x):
        _check("jax.block_until_ready", x)
        return real_block(x)

    jax.device_get, jax.block_until_ready = guarded_get, guarded_block
    try:
        yield log
    finally:
        jax.device_get, jax.block_until_ready = real_get, real_block
    if strict and log.violations:
        raise HostSyncError(
            "unwaived host sync(s) from repro code inside a "
            f"host_sync_guard scope: {log.violations}")
