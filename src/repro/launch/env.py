"""One hardened runtime setup for every entrypoint and CI job.

Both related launch stacks ship this module in some form (HomebrewNLP's
``run.sh`` exports ``--xla_force_host_platform_device_count`` + allocator
tuning; bayespec's ``config.py`` wraps platform/XLA-flag/NaN-debug
setup); here it is one importable, testable function instead of N copies
of environment-variable strings across scripts and CI YAML:

    from repro.launch import env
    env.setup_runtime(env.RuntimeConfig(host_device_count=8,
                                        nan_debug=True))

`env_overrides` is the pure core (config -> environment dict, merging
and deduplicating ``XLA_FLAGS`` against whatever is already set), so
tests assert on it without touching the process environment.
`setup_runtime` applies it to ``os.environ`` — call it **before the
first JAX backend touch** (importing jax is fine; creating arrays is
not), since XLA reads these at backend initialization — and turns on
JAX's persistent compilation cache (`compile_cache_dir`).  Importing
this module never mutates the environment.
"""
from __future__ import annotations

import dataclasses
import os
import sys
import warnings
from typing import Dict, Optional, Tuple

#: flag names this module owns inside XLA_FLAGS: a RuntimeConfig value
#: replaces any pre-set copy of these (last writer wins), while every
#: unmanaged flag already in the environment is preserved verbatim.
#: The per-op ``--xla_gpu_enable_async_*`` switches were removed from
#: XLA (async collectives are on by default under the latency-hiding
#: scheduler) and XLA *aborts* on unknown flags, so they are listed here
#: only to scrub stale copies out of inherited environments.
_MANAGED = (
    "--xla_force_host_platform_device_count",
    "--xla_gpu_enable_latency_hiding_scheduler",
    "--xla_gpu_enable_async_all_gather",
    "--xla_gpu_enable_async_reduce_scatter",
    "--xla_gpu_enable_async_collective_permute",
)


@dataclasses.dataclass(frozen=True)
class RuntimeConfig:
    """One runtime environment policy.

    ``host_device_count`` forces N fake CPU devices (the 8-fake-device
    SPMD tests and big local mesh sims).  ``async_collectives`` turns on
    XLA:GPU's latency-hiding scheduler + async collective ops (harmless
    no-ops on CPU).  ``nan_debug`` arms ``jax_debug_nans`` — jitted
    functions re-run op-by-op on a NaN and raise at the producing op.
    ``preallocate=False`` disables the GPU client's 75% up-front arena
    (the multi-process-per-host setting)."""
    host_device_count: Optional[int] = None
    async_collectives: bool = True
    nan_debug: bool = False
    preallocate: bool = True
    extra_xla_flags: Tuple[str, ...] = ()


def env_overrides(cfg: RuntimeConfig,
                  base_env: Optional[Dict[str, str]] = None
                  ) -> Dict[str, str]:
    """The environment-variable dict `cfg` resolves to, merged over
    ``base_env`` (default: the live ``os.environ``).  Pure — nothing is
    applied; returns only the keys that need setting."""
    base_env = dict(os.environ) if base_env is None else base_env
    flags = [f for f in base_env.get("XLA_FLAGS", "").split()
             if f and not f.startswith(_MANAGED)]
    if cfg.host_device_count is not None:
        assert cfg.host_device_count >= 1, cfg.host_device_count
        flags.append(f"--xla_force_host_platform_device_count="
                     f"{int(cfg.host_device_count)}")
    if cfg.async_collectives:
        # one flag, not the removed per-op --xla_gpu_enable_async_*
        # family: the scheduler overlaps collectives with compute, and
        # current XLA runs collectives async by default underneath it
        flags.append("--xla_gpu_enable_latency_hiding_scheduler=true")
    flags += list(cfg.extra_xla_flags)
    out: Dict[str, str] = {}
    joined = " ".join(flags)
    if joined != base_env.get("XLA_FLAGS", ""):
        out["XLA_FLAGS"] = joined
    if not cfg.preallocate:
        out["XLA_PYTHON_CLIENT_PREALLOCATE"] = "false"
    if cfg.nan_debug:
        out["JAX_DEBUG_NANS"] = "1"
    return out


#: JAX's own environment variable for its persistent compilation cache
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
#: the cache directory when CACHE_ENV is unset: fixed, inside the
#: checkout (git-ignored), so every run of this checkout hits it
CHECKOUT_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def compile_cache_dir(base_env: Optional[Dict[str, str]] = None) -> str:
    """Where compiled executables persist: ``$JAX_COMPILATION_CACHE_DIR``
    when set (JAX reads it itself), else `CHECKOUT_CACHE`."""
    base_env = os.environ if base_env is None else base_env
    return base_env.get(CACHE_ENV) or CHECKOUT_CACHE


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.
    Sets a directory in code only when the environment names none."""
    import jax
    path = compile_cache_dir()
    if not os.environ.get(CACHE_ENV):
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def _backends_initialized() -> bool:
    xb = sys.modules.get("jax._src.xla_bridge")
    return bool(getattr(xb, "_backends", None))


def setup_runtime(cfg: Optional[RuntimeConfig] = None, **kw) -> RuntimeConfig:
    """Apply `cfg` (or ``RuntimeConfig(**kw)``) to ``os.environ`` and the
    live jax config.  Safe to call after ``import jax`` but before the
    first backend touch; warns (rather than silently misconfiguring) if
    backends already initialized — XLA flags set now won't take effect.
    Returns the config it applied, so entrypoints can log it."""
    if cfg is None:
        cfg = RuntimeConfig(**kw)
    overrides = env_overrides(cfg)
    if "XLA_FLAGS" in overrides and _backends_initialized():
        warnings.warn(
            "launch.env.setup_runtime: JAX backends are already "
            "initialized; XLA_FLAGS changes will not apply to this "
            "process. Call setup_runtime() before the first jax "
            "device/array operation.", RuntimeWarning, stacklevel=2)
    os.environ.update(overrides)
    if "jax" in sys.modules:
        # env var alone is too late once jax.config snapshotted it
        sys.modules["jax"].config.update("jax_debug_nans",
                                         bool(cfg.nan_debug))
    enable_compile_cache()
    return cfg


def add_arguments(ap) -> None:
    """Attach the shared runtime flags to an entrypoint's argparser."""
    ap.add_argument("--host-devices", type=int, default=None,
                    help="force N fake CPU devices "
                         "(--xla_force_host_platform_device_count)")
    ap.add_argument("--nan-debug", action="store_true",
                    help="arm jax_debug_nans (raise at the producing op)")
    ap.add_argument("--no-async-collectives", action="store_true",
                    help="disable XLA:GPU async collectives + "
                         "latency-hiding scheduler")


def from_args(args) -> RuntimeConfig:
    """Build the `RuntimeConfig` an `add_arguments`-extended namespace
    selects."""
    return RuntimeConfig(
        host_device_count=args.host_devices,
        nan_debug=bool(args.nan_debug),
        async_collectives=not args.no_async_collectives)
