"""Chip entry-point helpers: refuse to run off the TPU, and keep JAX's
persistent compile cache where a deployment can place it.

Both are for scripts that measure or smoke-test on the chip (``chip_smoke.py``
and the benchmark entry point); the test suite runs on the CPU and uses
neither."""

from __future__ import annotations

import os
from pathlib import Path

import jax


def use_compile_cache(default_dir: Path) -> str:
    """Turn on the persistent compile cache before the first compile.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and is
    left alone; otherwise the cache goes to ``default_dir``, a fixed path
    (the directory is part of the cache key, so a moving one never hits).
    Returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(default_dir))
    return str(default_dir)


def require_tpu() -> dict:
    """The device JAX reports, as ``{"platform", "kind", "count"}``; raises
    SystemExit naming the platform found when it is not a TPU (there is no
    CPU fallback for a chip run)."""
    devices = jax.devices()
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices)}
    if dev["platform"] != "tpu":
        raise SystemExit(
            f"no TPU: JAX found platform {dev['platform']!r} "
            f"({dev['kind']}, {dev['count']} device(s)); this run needs a TPU"
        )
    return dev
