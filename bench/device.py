"""The device a run measures: the platform guard, the peak table, the
compilation cache, and the memory and compile readings.

A measurement never falls back to another platform: without the
accelerator, or with fewer chips than the cell asks for, ``require``
raises and the run prints no result.  A device kind that ``peaks.json``
does not list is an error too, never a default.
"""
from __future__ import annotations

import json
import os
from pathlib import Path

import jax

PEAKS_FILE = Path(__file__).resolve().with_name("peaks.json")


class NoDevice(RuntimeError):
    """The run cannot measure here; it exits non-zero with no result."""


def peaks(device_kind: str, table: Path = PEAKS_FILE) -> dict:
    """Published peaks of ``device_kind``; ``KeyError`` for an unknown kind."""
    known = json.loads(Path(table).read_text())
    if device_kind not in known:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"peaks.json lists {sorted(known)}")
    return known[device_kind]


def require(chips: int, platform: str = "tpu") -> list:
    """The first ``chips`` devices, or ``NoDevice`` when JAX finds fewer
    than that on ``platform``."""
    devices = jax.devices()
    if devices[0].platform != platform:
        raise NoDevice(f"no {platform} device: JAX finds "
                       f"{devices[0].platform} ({devices[0].device_kind})")
    if len(devices) < chips:
        raise NoDevice(f"the cell needs {chips} {platform} chips, JAX finds "
                       f"{len(devices)}")
    return devices[:chips]


def enable_compile_cache(root: Path) -> str:
    """Persistent compilation cache at ``JAX_COMPILATION_CACHE_DIR`` when
    set, else at the fixed ``<root>/.jax_cache`` (the path is part of the
    cache key, so it never moves).  Every program is cached, however fast
    it compiled, so that only a checkout's first run compiles."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        Path(root) / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def record(devices: list) -> dict:
    """The device block of the result line, as JAX reports it.
    ``memory_peak_bytes`` is the peak of the fullest chip in use."""
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(jax.devices()),
            "memory_peak_bytes": int(peak)}


class CompileCounter:
    """Counts the programs JAX traces and brings up, so a run can show that
    its measured window compiles nothing.  ``loaded`` names every program
    compiled or fetched from the persistent cache; ``cache_misses`` counts
    those that the cache did not hold and XLA compiled."""

    _TRACE = "/jax/core/compile/jaxpr_trace_duration"
    _LOAD = "/jax/core/compile/backend_compile_duration"
    _MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        self.traced: list = []
        self.loaded: list = []
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, secs: float, **kw) -> None:
        if event == self._TRACE:
            self.traced.append(kw.get("fun_name"))
        elif event == self._LOAD:
            self.loaded.append(kw.get("fun_name"))

    def _on_event(self, event: str, **kw) -> None:
        if event == self._MISS:
            self.cache_misses += 1

    def reset(self) -> None:
        self.traced.clear()
        self.loaded.clear()
        self.cache_misses = 0

    def close(self) -> None:
        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)
