"""The program a sharded job cell runs, and its jobs' device time by the
engine's phase scopes.

``program_trace.job_hlo`` compiles the one-chip cell's ``gk_select``; a
cell whose driver is ``sharded_job_loop`` runs
``distributed_quantile(x, q, mesh)`` instead, one program over the cell's
chips (``engine.gk_select_sharded`` under ``shard_map``), with its own
instruction names and scopes.  ``sharded_hlo`` compiles that program as
the driver runs it, and ``phase_shares`` reads the trace against it with
``program_trace``'s reduction: each chip's device time inside the job's
launches, given to the innermost operation running, by the innermost
``phase_*`` scope, averaged over the chips.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

from bench import program_trace
from bench import trace as tracemod


@functools.lru_cache(maxsize=None)
def _sharded_hlo(chips: int, shape: Tuple[int, int], dtype: str,
                 q: float) -> str:
    import jax
    import numpy as np
    import repro.core
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    from repro.core import lowering
    mesh = Mesh(np.array(jax.devices()[:chips]), ("data",))
    x = jax.ShapeDtypeStruct((shape[0] * shape[1],), dtype,
                             sharding=NamedSharding(mesh, PartitionSpec("data")))
    enabled = jax.config.jax_enable_compilation_cache
    jax.clear_caches()
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return lowering.lower(repro.core.distributed_quantile, x, q,
                              mesh).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
        compilation_cache.reset_cache()


def sharded_hlo(cell) -> str:
    """The compiled text of the program a sharded job cell's
    ``distributed_quantile`` call runs: the same entry, arguments, mesh and
    sharding as ``sharded_job_loop``.  Compiled afresh, past the in-memory
    and persistent caches, for the reason ``program_trace.job_hlo`` gives:
    a program loaded from the persistent cache may carry another
    checkout's scopes."""
    cfg = cell.config
    return _sharded_hlo(cell.chips, (int(cfg["partitions"]),
                                     int(cfg["values_per_partition"])),
                        cfg["dtype"], float(cell.traffic["q"]))


def phase_shares(r) -> Optional[Dict[str, float]]:
    """Percent of the jobs' device time (the union of operations inside
    ``job`` spans, averaged over the chips) whose innermost operation ran
    under each phase scope of the sharded program, logged with what no
    phase holds; ``None`` when nothing was traced or the program names no
    phase.  Computed once per reading, which keeps it for the other
    readers."""
    if r.trace is None:
        return None
    if not hasattr(r, "_sharded_phase_shares"):
        r._sharded_phase_shares = _phase_shares(r)
    return r._sharded_phase_shares


def _phase_shares(r) -> Optional[Dict[str, float]]:
    jobs = r.trace.spans_named("job")
    busy = tracemod.busy_ns(r.trace, jobs)
    if busy <= 0:
        return None
    hlo = sharded_hlo(r.cell)
    op_scopes = program_trace.scopes(hlo)
    phases = sorted({program_trace.PHASE.findall(p)[-1]
                     for p in op_scopes.values()
                     if program_trace.PHASE.search(p)})
    if not phases:
        return None
    within = program_trace.program_launches(
        r.trace, program_trace.module_name(hlo), jobs)
    shares = {phase: 100.0 * program_trace.scope_time_ns(
        r.trace, op_scopes, phase, within) / busy for phase in phases}
    program = 100.0 * tracemod.busy_ns(r.trace, within) / busy
    r.log("phase shares of the sharded job's device time (%): "
          + " ".join(f"{p} {v:.4f}" for p, v in shares.items())
          + f"; no phase {program - sum(shares.values()):.4f}"
          + f"; other programs {100.0 - program:.4f}")
    return shares


def phase_share(r, scope: str) -> Optional[float]:
    """``phase_shares(r)[scope]``; ``None`` where that is missing."""
    shares = phase_shares(r)
    return None if shares is None else shares.get(scope)
