"""Public entry points for distributed quantiles — thin plans over the
phase-based engine (``repro.core.engine``; DESIGN.md §6).

Spark roles map to SPMD collectives (DESIGN.md §2):

  collect sketches       -> lax.all_gather   (replicated merge, no driver)
  TorrentBroadcast pivot -> free (pivot computed replicated post-gather)
  collect counts         -> lax.psum
  treeReduce candidates  -> <= log2(P)+2 lax.ppermute butterfly generalized
                            to ANY shard count (fold/butterfly/broadcast,
                            DESIGN.md §5), re-selecting the cap best at each
                            step (paper's reduceSlices), or a single capped
                            all_gather (strategy="all_gather")

The engine bodies (``gk_select_sharded``, ``gk_select_multi_sharded``,
``count_discard_sharded``, ``full_sort_sharded``, ...) are composed from the
shared phase functions ``phase_sketch / phase_pivot / phase_count_extract /
phase_reduce / phase_resolve`` in ``engine.py`` and are re-exported here
unchanged for compatibility.  This module only owns the mesh-facing
wrappers: validate, pick a plan, shard_map it.
"""
from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from .. import obs
from . import local_ops, lowering
from .engine import butterfly_steps

# Engine bodies + collective helpers re-exported for compatibility: every
# pre-refactor import path (benchmarks, tests, downstream code) keeps
# working against the phase-based engine.
from .engine import (shard_map_compat, tree_reduce_candidates,
                     gather_candidates, _pmax_pair, _axis_size,
                     phase_sketch, phase_pivot, phase_count,
                     phase_count_extract, phase_reduce, phase_resolve,
                     gk_select_sharded, gk_select_multi_sharded,
                     approx_quantile_sharded, count_discard_sharded,
                     full_sort_sharded)


# ---------------------------------------------------------------------------
# Public API: run over a mesh
# ---------------------------------------------------------------------------


def _count_collectives(x, num_shards: int, cap: int, *, levels: int = 1,
                       two_sided: bool = False, sketch: bool = True,
                       reduce_strategy: str = "tree") -> None:
    """Count, once per eager GK Select job over ``num_shards``, what its
    plan hands to collectives (``repro.obs``):

      distributed.collectives   the sketch's 2 ``all_gather`` (cold jobs),
                                the counts' ``psum``, and per candidate
                                buffer reduced (1 one-sided, 2 two-sided)
                                one ``ppermute`` a ``butterfly_steps``
                                step, or one ``all_gather``
      distributed.reduce_bytes  the bytes of those buffers, (levels, cap)
                                of ``x``'s dtype, one for each ppermute
                                step, or for each of the P - 1 chips an
                                all_gather reaches

    Nothing is counted while ``x`` is traced or the entry is lowered: no
    job runs then."""
    if isinstance(x, jax.core.Tracer) or lowering.active():
        return
    buffers = 2 if two_sided else 1
    if reduce_strategy == "tree":
        reduce_ops = sends = buffers * len(butterfly_steps(num_shards))
    else:
        reduce_ops, sends = buffers, buffers * (num_shards - 1)
    obs.count("distributed.collectives", 2 * sketch + 1 + reduce_ops)
    obs.count("distributed.reduce_bytes",
              sends * levels * cap * jnp.dtype(x.dtype).itemsize)


@functools.lru_cache(maxsize=128)
def _quantile_program(mesh: Mesh, axis: str, method: str, q: float,
                      eps: float, speculative: bool, reduce_strategy: str,
                      fused: bool, backend):
    """The jitted shard_map program of one ``distributed_quantile`` plan,
    built once per (mesh, static parameters) and replayed after."""
    num_shards = mesh.shape[axis]
    fused_fn = None
    if fused:
        from ..kernels.ops import make_fused_fn   # lazy: kernels optional
        fused_fn = make_fused_fn(backend=backend)
    bodies = {
        "gk_select": functools.partial(gk_select_sharded, q=q, eps=eps,
                                       axis=axis, num_shards=num_shards,
                                       speculative=speculative,
                                       reduce_strategy=reduce_strategy,
                                       fused_fn=fused_fn),
        "approx": functools.partial(approx_quantile_sharded, q=q, eps=eps,
                                    axis=axis, num_shards=num_shards),
        "afs": functools.partial(count_discard_sharded, q=q, axis=axis,
                                 num_shards=num_shards, collect_counts=False),
        "jeffers": functools.partial(count_discard_sharded, q=q, axis=axis,
                                     num_shards=num_shards, collect_counts=True),
        "full_sort": functools.partial(full_sort_sharded, q=q, axis=axis,
                                       num_shards=num_shards),
    }
    return jax.jit(shard_map_compat(bodies[method], mesh=mesh,
                                    in_specs=(P(axis),), out_specs=P()))


def distributed_quantile(x: jax.Array, q: float, mesh: Mesh, *,
                         axis: str = "data", eps: float = 0.01,
                         method: str = "gk_select", speculative: bool = False,
                         reduce_strategy: str = "tree",
                         fused: bool = False, backend=None,
                         check_nans: bool = True) -> jax.Array:
    """Exact (or approximate, method='approx') quantile of a 1-D array sharded
    over ``axis`` of ``mesh``.  The entry point used by optimizer/serving
    integrations.  The job runs as one jitted program per (mesh, static
    parameters); ``x`` is best placed already sharded
    (``NamedSharding(mesh, P(axis))``), else the program reshards it.

    Exactness guarantee: for every exact method ('gk_select', 'afs',
    'jeffers', 'full_sort') the answer is bit-identical to the global sort
    oracle; eps and the flags below only steer data movement.

    ``fused=True`` injects the fused count+extract seam into the gk_select
    body; ``backend`` is the kernel-dispatch handle the seam closes over
    (None = per-platform default — compiled Pallas on TPU, jitted jnp
    fallback on CPU; "pallas"/"pallas_interpret"/"jnp" or a
    ``kernels.dispatch.Backend`` pin it).  Ignored without ``fused``.

    NaN policy: reject (DESIGN.md §7).  The check is one extra data pass +
    a host sync before the job; ``check_nans=False`` opts out and transfers
    the NaN-free contract to the caller (hot-loop querying)."""
    num_shards = mesh.shape[axis]
    if x.ndim != 1:
        raise ValueError("distributed_quantile expects a flat array")
    if x.size % num_shards:
        raise ValueError(f"size {x.size} % shards {num_shards} != 0 — pad first")
    if fused and method != "gk_select":
        raise ValueError(f"fused=True only applies to method='gk_select', "
                         f"got method={method!r}")
    with obs.span("distributed_quantile"):
        if check_nans:
            local_ops.reject_nans(x, "distributed_quantile")
        program = _quantile_program(mesh, axis, method, float(q), eps,
                                    speculative, reduce_strategy, fused,
                                    backend if fused else None)
        if method == "gk_select":
            _count_collectives(
                x, num_shards,
                local_ops.candidate_cap(x.size, eps, x.size // num_shards),
                two_sided=speculative or fused,
                reduce_strategy=reduce_strategy)
        with obs.span("dispatch"):
            return lowering.call(program, x)


@functools.lru_cache(maxsize=128)
def _multi_program(mesh: Mesh, axis: str, qs: tuple, eps: float,
                   reduce_strategy: str, fused: bool, backend,
                   warm: bool, cap):
    """The jitted shard_map program of one ``distributed_quantile_multi``
    plan; a warm program takes the (Q,) pivots as a replicated argument."""
    fused_fn = None
    if fused:
        from ..kernels.ops import make_fused_multi_fn   # lazy: kernels optional
        fused_fn = make_fused_multi_fn(backend=backend)
    body = functools.partial(gk_select_multi_sharded, qs=qs, eps=eps,
                             axis=axis, num_shards=mesh.shape[axis],
                             reduce_strategy=reduce_strategy,
                             fused_fn=fused_fn, cap=cap)
    if warm:
        def run(x_local, pivots):
            return body(x_local, pivots=pivots)
        in_specs = (P(axis), P())
    else:
        run, in_specs = body, (P(axis),)
    return jax.jit(shard_map_compat(run, mesh=mesh, in_specs=in_specs,
                                    out_specs=P()))


def distributed_quantile_multi(x: jax.Array, qs: Sequence[float], mesh: Mesh,
                               *, axis: str = "data", eps: float = 0.01,
                               reduce_strategy: str = "tree",
                               fused: bool = False, backend=None,
                               pivots=None, cap: int = None,
                               check_nans: bool = True) -> jax.Array:
    """Exact quantiles at ALL the (static) levels in ``qs`` from one sharded
    job: one sketch phase, one count+extract pass per shard (fused=True
    with a Pallas ``backend`` streams the shard from HBM once for every
    pivot via the multi-pivot kernel — 3Q passes -> 1; ``backend=None``
    selects per platform, see ``distributed_quantile``), one butterfly for
    all Q candidate buffers.  Returns the (Q,) values, replicated — every
    level bit-identical to the sort oracle.  Works on any shard count,
    power of two or not.

    ``pivots`` runs the job WARM (DESIGN.md §6): a (Q,) vector of
    externally-maintained pivots (e.g. from a live ``SketchState``) skips
    the sketch phase — and its per-shard sort — entirely; ``cap`` then
    sizes the candidate buffers from the supplier's tracked rank bound.
    NaN policy: reject; ``check_nans=False`` opts out (see
    ``distributed_quantile``).
    """
    num_shards = mesh.shape[axis]
    qs = tuple(float(q) for q in qs)
    if not qs:
        raise ValueError("qs must name at least one quantile level")
    if x.ndim != 1:
        raise ValueError("distributed_quantile_multi expects a flat array")
    if x.size % num_shards:
        raise ValueError(f"size {x.size} % shards {num_shards} != 0 — pad first")
    with obs.span("distributed_quantile_multi"):
        if check_nans:
            local_ops.reject_nans(x, "distributed_quantile_multi")
        program = _multi_program(mesh, axis, qs, eps, reduce_strategy, fused,
                                 backend if fused else None,
                                 pivots is not None,
                                 None if cap is None else int(cap))
        _count_collectives(
            x, num_shards,
            local_ops.candidate_cap(x.size, eps, x.size // num_shards)
            if cap is None else int(cap),
            levels=len(qs), two_sided=True, sketch=pivots is None,
            reduce_strategy=reduce_strategy)
        with obs.span("dispatch"):
            if pivots is None:
                return lowering.call(program, x)
            return lowering.call(
                program, x, jnp.asarray(pivots, x.dtype).reshape(len(qs)))
