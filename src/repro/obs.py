"""The program's spans and counters.

Spans: ``span(name, **meta)`` is ``jax.profiler.TraceAnnotation`` named
``repro/<name>``.  It writes an event into a running profiler trace, on
the host plane beside the runtime's own events and on the same clock, so
that a reduction can set each span against the device operations it
launched or waited for; ``meta`` lands as the event's stats.  A running
profiler is the only switch: with no trace active a span costs what a
``TraceAnnotation`` costs.

Names in use (see PERF.md §3 for the metric that reads each):

  gk_select, gk_select_multi  the eager entries; children ``nan_check``
                              and ``dispatch`` (the jitted program's call)
  distributed_quantile,       the sharded eager entries, with the same
  distributed_quantile_multi  children
  nan_check                   ``local_ops.reject_nans`` (``where=``)
  service.ingest_batch        a tick; children ``service.pack``,
                              ``nan_check``, ``service.rotate``,
                              ``service.update``, ``service.retire``
  service.windowed            a windowed query (``request=``); children
                              ``service.slices``, ``service.pivot``,
                              ``service.count_extract``, ``service.resolve``
  read                        a blocking device-to-host read

Counters: one registry of named integer counts under one lock, safe
across the service's ingest and query threads.

  sketch.sorts                sketch-construction sorts dispatched
  kernels.hbm_passes          full-array HBM streams the kernel wrappers
                              dispatched
  service.ingest_dispatches   device dispatches on the ingest path
  service.cap_programs        query programs built for a new candidate cap
  distributed.collectives     collectives a sharded GK Select job issues:
                              the sketch's 2 all_gathers, the counts'
                              psum, phase_reduce's ppermutes (or
                              all_gather)
  distributed.reduce_bytes    bytes of the candidate buffers those
                              ppermutes carry, a chip's share
"""
from __future__ import annotations

import threading
from typing import Dict

import jax

PREFIX = "repro/"

_COUNTS: Dict[str, int] = {}
_LOCK = threading.Lock()


def span(name: str, **meta) -> jax.profiler.TraceAnnotation:
    """A context manager that records ``repro/<name>`` in a running
    profiler trace, with ``meta`` as its stats."""
    return jax.profiler.TraceAnnotation(PREFIX + name, **meta)


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to the counter ``name``."""
    with _LOCK:
        _COUNTS[name] = _COUNTS.get(name, 0) + n


def counters() -> Dict[str, int]:
    """A snapshot of every counter that has been counted since its reset."""
    with _LOCK:
        return dict(_COUNTS)


def reset(*names: str) -> None:
    """Zero the counters ``names``, or every counter when none is named."""
    with _LOCK:
        for name in names or list(_COUNTS):
            _COUNTS.pop(name, None)
