"""CPU backend arming for tests, dry runs and CPU-only bench modes.

Pins jax to the host CPU backend (optionally with N virtual devices) before
any backend is instantiated, so a process that must not take the chip — a
test, a chaos child, a multi-device dry run — never does. Used by
tests/conftest.py, bench.py's CPU-only modes and the driver dryrun.
"""
from __future__ import annotations

import os
import re


def force_device_count_flags(flags: str, device_count: int | None) -> str:
    """XLA_FLAGS with the virtual-host-device count set to exactly
    ``device_count`` (any pre-existing count is REPLACED, never kept —
    the one home of this flag dance for in-process arming and for child
    environments alike; ``None`` just strips a stale flag)."""
    flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                   flags or "").strip()
    if device_count is not None:
        flags = (flags + f" --xla_force_host_platform_device_count"
                         f"={device_count}").strip()
    return flags


def force_cpu_backend(device_count: int | None = None) -> None:
    """Pin jax to the CPU backend, optionally with N virtual devices.

    Must run before the first backend access (imports are fine — backends
    initialize lazily). Safe to call repeatedly.
    """
    if device_count is not None:
        os.environ["XLA_FLAGS"] = force_device_count_flags(
            os.environ.get("XLA_FLAGS", ""), device_count)
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    jax.config.update("jax_platforms", "cpu")
