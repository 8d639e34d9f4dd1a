"""Runtime sanitizers (recompile / transfer / host-sync guards) and the
codec path's spans and counters (`spans`)."""
from . import spans  # noqa: F401
from .guards import (GuardError, HostSyncError,  # noqa: F401
                     RecompileError, host_sync_guard, no_implicit_transfers,
                     no_recompiles)

__all__ = ["GuardError", "HostSyncError", "RecompileError",
           "host_sync_guard", "no_implicit_transfers", "no_recompiles",
           "spans"]
