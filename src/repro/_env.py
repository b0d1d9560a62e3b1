"""Process-environment knobs that must be set BEFORE jax initializes.

Deliberately jax-free: importing this module must not trigger backend
initialization, or the knobs it sets would be ignored.
"""
from __future__ import annotations

import os
from pathlib import Path

#: Forced host-device count shared by tests/conftest.py, the --shard
#: benchmarks and scripts/ci.sh (which re-states it in shell).  The perf
#: gate (scripts/check_bench.py) hard-fails on device_count mismatches, so
#: every entry point must agree on this number.
FORCED_HOST_DEVICES = 8

_FLAG = "--xla_force_host_platform_device_count"


def force_host_devices(n: int = FORCED_HOST_DEVICES) -> None:
    """Inject ``--xla_force_host_platform_device_count=n`` into XLA_FLAGS.

    No-op when the flag is already present (an explicit topology pin wins).
    Only affects the CPU platform; must run before jax touches a backend.

    Parameters
    ----------
    n : int, optional
        Device count to force (default :data:`FORCED_HOST_DEVICES`).
    """
    if _FLAG not in os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                                   + f" {_FLAG}={n}").strip()


#: Where JAX's persistent compilation cache lives when
#: ``JAX_COMPILATION_CACHE_DIR`` is unset: one fixed, gitignored path inside
#: the checkout.  The path is part of every cache key, so a directory that
#: moved between runs (a temporary name, a pid, a timestamp) would never hit.
COMPILE_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for this process.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is used as it is; otherwise it
    is set to :data:`COMPILE_CACHE_DIR`.  JAX reads the variable when it is
    imported, so call this first.  The entry points that compile at full
    size call it (``chip_smoke.py``, ``repro.launch.allocd``,
    ``repro.launch.plan``); the test suite does not.

    Returns
    -------
    str
        The cache directory in use.
    """
    return os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                                 str(COMPILE_CACHE_DIR))
