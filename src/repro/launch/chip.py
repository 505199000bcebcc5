"""Accelerator set-up shared by the entry points that may run on a chip
(``chip_smoke.py``, ``benchmarks/run.py``, the examples).

``enable_compile_cache`` keeps JAX's persistent compilation cache where
``JAX_COMPILATION_CACHE_DIR`` says, or else at the fixed ``<repo>/.jax_cache``
(the path is part of the cache key, so it never moves).
``require_accelerator`` refuses to go on without the expected platform, and
``device_record`` names the device every result ran on.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO = Path(__file__).resolve().parents[3]
CACHE_DIR = REPO / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.
    A set ``JAX_COMPILATION_CACHE_DIR`` is left alone (JAX reads it)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)


def require_accelerator(platform: str = "tpu") -> list:
    """``jax.devices()``, or ``RuntimeError`` when they are not ``platform``
    devices: a measurement path never falls back to the CPU."""
    devices = jax.devices()
    if devices[0].platform != platform:
        raise RuntimeError(
            f"no {platform} device: jax.devices() are "
            f"{devices[0].platform} ({devices[0].device_kind}) x "
            f"{len(devices)}")
    return devices


def device_record() -> dict:
    """The device a result ran on, as JAX reports it."""
    devices = jax.devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices)}
