"""GK Select — the paper's exact distributed quantile algorithm.

This module is the *single-process reference*: data is a (P, n_i) array whose
leading axis plays the role of Spark partitions / mesh shards.  Per-shard work
is vmapped ``local_ops``; the cross-shard phases are leading-axis reductions.
``repro.core.distributed`` runs the identical phases under shard_map with real
collectives.

Round structure (paper §V):
  Round 1: per-shard sketch -> merge -> approximate pivot
  Round 2: per-shard 3-way counts -> global sum -> signed rank gap Delta_k
  Round 3: per-shard candidate extraction -> tree reduce -> exact value

``speculative=True`` is the beyond-paper 2-round variant (DESIGN.md §2):
candidates on *both* sides of the pivot are extracted in the same pass as the
counts, removing the sign-dependency between rounds 2 and 3.
"""
from __future__ import annotations

import functools
import math
from typing import Sequence

import jax
import jax.numpy as jnp

from .. import obs
from . import local_ops, lowering
from .sketch import local_sample_sketch, query_merged_sketch, sample_sketch_params


def _count_whole_band(parts, eps: float, block_select: bool) -> None:
    """Tick ``extract.whole_band`` once for an eager job whose extraction
    is the masked shard (``local_ops.whole_band``): every path but the
    kernel layer's (``block_select``).  Nothing is counted while ``parts``
    is traced or the entry is lowered: no job runs then."""
    if (block_select or isinstance(parts, jax.core.Tracer)
            or lowering.active()):
        return
    P, n_i = jnp.shape(parts)
    if local_ops.whole_band(local_ops.candidate_cap(P * n_i, eps, n_i), n_i):
        obs.count(local_ops.WHOLE_BAND)


def _pivot_from_sample_sketch(parts: jax.Array, k: jax.Array, eps: float) -> jax.Array:
    P, n_i = parts.shape
    n = P * n_i
    m, s = sample_sketch_params(n, n_i, eps, P)
    with jax.named_scope("phase_sketch"):
        vals, weights = jax.vmap(lambda x: local_sample_sketch(x, m, s))(parts)
    with jax.named_scope("phase_pivot"):
        return query_merged_sketch(vals.ravel(), weights.ravel(), k, P, m)


@functools.partial(jax.jit, static_argnames=("q", "eps", "speculative",
                                             "block_select", "k", "backend"))
def _gk_select_jit(parts: jax.Array, q: float, *, eps: float = 0.01,
                   speculative: bool = False, block_select: bool = False,
                   k: int = None, backend=None) -> jax.Array:
    """Exact q-quantile (k = ceil(q*n), 1-based) of a (P, n_i) partitioned array.

    Exactness does not depend on eps; eps only sizes the sketch and the
    candidate buffers (|Delta_k| <= eps*n by the sketch guarantee).

    ``k`` (static, 1-based) addresses the target by rank directly and
    overrides ``q`` (pass q=None) — the entry sentinel-padded callers need:
    with +inf padding, ``q * n_padded`` lies about the true target rank
    while a rank on the unpadded count stays exact.

    ``block_select=True`` routes the count+extract work through the kernel
    layer (``kernels.ops.fused_count_extract``) with the speculative
    two-sided data flow (it subsumes ``speculative``); ``backend`` picks
    the kernel implementation (None = per-platform default: compiled
    Pallas on TPU, jitted jnp fallback on CPU — see
    ``kernels.dispatch.select_backend``) and is ignored without
    ``block_select``.

    Each round runs under a ``jax.named_scope`` (``phase_sketch``,
    ``phase_pivot``, ``phase_count``, ``phase_extract``,
    ``phase_count_extract``, ``phase_resolve``): compile-time metadata
    that names every operation of the compiled program by its round.
    """
    P, n_i = parts.shape
    n = P * n_i
    rank = local_ops.target_rank(n, q) if k is None else int(min(n, max(1, k)))
    k = jnp.int32(rank)

    # ---- Round 1: sketch + merged pivot (Steps 1-3) ----
    pivot = _pivot_from_sample_sketch(parts, k, eps)

    cap = local_ops.candidate_cap(n, eps, n_i)

    if block_select:
        # ---- Rounds 2+3 fused into ONE streaming pass per shard: the
        # kernel emits counts and both candidate bands from a single
        # HBM->VMEM sweep.  (Lazy import: core stays usable without the
        # kernels layer.)
        from ..kernels import ops as kernel_ops
        with jax.named_scope("phase_count_extract"):
            counts, below, above = jax.vmap(
                lambda x: kernel_ops.fused_count_extract(
                    x, pivot, cap, backend=backend))(parts)
            counts = counts.sum(0)
        with jax.named_scope("phase_resolve"):
            return local_ops.resolve(pivot, k, counts[0], counts[1],
                                     below, above, cap)

    if speculative:
        # ---- Rounds 2+3 fused: count and two-sided extraction in one
        # logical phase (still 3 jnp streams; block_select=True is the
        # 1-stream kernel version).
        with jax.named_scope("phase_count_extract"):
            counts, below, above = jax.vmap(
                lambda x: local_ops.fused_count_extract(x, pivot, cap))(parts)
            counts = counts.sum(0)
            lt, eq = counts[0], counts[1]
        with jax.named_scope("phase_resolve"):
            return local_ops.resolve(pivot, k, lt, eq, below, above, cap)

    # ---- Round 2: counts -> Delta_k (Steps 4-6) ----
    with jax.named_scope("phase_count"):
        counts = jax.vmap(lambda x: local_ops.count3(x, pivot))(parts).sum(0)
        lt, eq = counts[0], counts[1]
        need_left = lt - k + 1
        need_right = k - (lt + eq)

    # ---- Round 3: one-sided extraction + reduce (Steps 7-9) ----
    # Paper semantics: only the deficient side is scanned.  Static shapes force
    # both branches to exist in the graph; lax.cond keeps only one side's
    # compute live per invocation.
    def left_branch(_):
        with jax.named_scope("phase_extract"):
            below = jax.vmap(
                lambda x: local_ops.extract_below(x, pivot, cap))(parts)
        with jax.named_scope("phase_resolve"):
            return local_ops.kth_largest(below, jnp.maximum(need_left, 1), cap)

    def right_branch(_):
        with jax.named_scope("phase_extract"):
            above = jax.vmap(
                lambda x: local_ops.extract_above(x, pivot, cap))(parts)
        with jax.named_scope("phase_resolve"):
            return local_ops.kth_smallest(above, jnp.maximum(need_right, 1),
                                          cap)

    side_val = jax.lax.cond(need_left > 0, left_branch, right_branch, operand=None)
    with jax.named_scope("phase_resolve"):
        return jnp.where((need_left <= 0) & (need_right <= 0), pivot,
                         side_val)


def gk_select(parts: jax.Array, q: float, *, eps: float = 0.01,
              speculative: bool = False, block_select: bool = False,
              k: int = None, check_nans: bool = True,
              backend=None) -> jax.Array:
    """Eager entry for ``_gk_select_jit`` (same signature and semantics).

    Exactness guarantee: the result is bit-identical to
    ``sorted(parts.ravel())[ceil(q*n) - 1]`` regardless of ``eps``,
    ``speculative``, ``block_select`` or ``backend`` — those flags change
    the data movement, never the answer.

    NaN policy: reject (``local_ops.reject_nans``; DESIGN.md §7) — float
    inputs containing NaN raise ``ValueError`` here; when ``parts`` is a
    tracer (embedded in a caller's jit) the check is skipped and NaN-free
    input is the caller's contract.  The check is one extra data pass + a
    host sync; ``check_nans=False`` opts out for hot loops (mirroring the
    sharded entries and ``QuantileService``).

    ``backend`` (None | "pallas" | "pallas_interpret" | "jnp" | a
    ``kernels.dispatch.Backend``) picks the kernel implementation when
    ``block_select=True``; None selects per platform at trace time.
    """
    with obs.span("gk_select"):
        if check_nans:
            local_ops.reject_nans(parts, "gk_select")
        with obs.span("dispatch"):
            out = lowering.call(_gk_select_jit, parts, q, eps=eps,
                                speculative=speculative,
                                block_select=block_select, k=k,
                                backend=backend)
        _count_whole_band(parts, eps, block_select)
        return out


def exact_quantile(x: jax.Array, q: float, *, eps: float = 0.01,
                   num_partitions: int = 8) -> jax.Array:
    """Flat-array convenience wrapper: reshape into P pseudo-partitions and
    run GK Select. x.size must be divisible by num_partitions (pad upstream).
    NaN policy: reject (see ``gk_select``)."""
    n = x.size
    if n % num_partitions:
        raise ValueError(f"size {n} not divisible by P={num_partitions}")
    parts = x.reshape(num_partitions, n // num_partitions)
    return gk_select(parts, q, eps=eps)


def exact_quantile_rank(x: jax.Array, k: int, *, eps: float = 0.01,
                        num_partitions: int = 8) -> jax.Array:
    """Rank-addressed ``exact_quantile``: the k-th smallest (1-based) element
    of the flat array.  Sentinel-padding callers (calibration) compute
    k = ceil(q * n_true) on the TRUE element count and pad with +inf, which
    never disturbs ranks <= n_true — unlike zero-padding, which inflates n
    and shifts every quantile.  NaN policy: reject (see ``gk_select``)."""
    n = x.size
    if n % num_partitions:
        raise ValueError(f"size {n} not divisible by P={num_partitions}")
    if not 1 <= k <= n:
        raise ValueError(f"rank k={k} outside [1, {n}]")
    parts = x.reshape(num_partitions, n // num_partitions)
    return gk_select(parts, None, k=int(k), eps=eps)


@functools.partial(jax.jit, static_argnames=("qs", "eps", "speculative",
                                             "block_select", "backend"))
def _gk_select_multi_jit(parts: jax.Array, qs: tuple, *, eps: float = 0.01,
                         speculative: bool = True,
                         block_select: bool = False,
                         backend=None) -> jax.Array:
    """Beyond-paper: Q quantiles in one job (qs is a static tuple of floats).
    The sketch phase is shared; the count/extract phases vmap over pivots
    (Spark would run Q separate jobs).

    ``block_select=True`` uses the multi-pivot fused kernel entry
    (``kernels.ops.fused_count_extract_multi``): on a Pallas backend each
    shard is streamed from HBM ONCE for all Q pivots, instead of 3 passes
    per pivot; ``backend`` picks the implementation (see ``gk_select``)."""
    P, n_i = parts.shape
    n = P * n_i
    ks = jnp.array([local_ops.target_rank(n, q) for q in qs], jnp.int32)

    m, s = sample_sketch_params(n, n_i, eps, P)
    with jax.named_scope("phase_sketch"):
        vals, weights = jax.vmap(lambda x: local_sample_sketch(x, m, s))(parts)
    fv, fw = vals.ravel(), weights.ravel()
    with jax.named_scope("phase_pivot"):
        pivots = jax.vmap(lambda k: query_merged_sketch(fv, fw, k, P, m))(ks)

    cap = local_ops.candidate_cap(n, eps, n_i)

    if block_select:
        from ..kernels import ops as kernel_ops
        with jax.named_scope("phase_count_extract"):
            counts, below, above = jax.vmap(
                lambda x: kernel_ops.fused_count_extract_multi(
                    x, pivots, cap, backend=backend))(parts)
            counts = counts.sum(0)                 # (Q, 3)

        def resolve_one(pivot, k, c, b, a):
            return local_ops.resolve(pivot, k, c[0], c[1], b, a, cap)

        with jax.named_scope("phase_resolve"):
            below = jnp.swapaxes(below, 0, 1)      # (P, Q, cap) -> (Q, P, cap)
            above = jnp.swapaxes(above, 0, 1)
            return jax.vmap(resolve_one)(pivots, ks, counts, below, above)

    def one(pk):
        pivot, k = pk
        with jax.named_scope("phase_count_extract"):
            counts, below, above = jax.vmap(
                lambda x: local_ops.fused_count_extract(x, pivot, cap))(parts)
            counts = counts.sum(0)
        with jax.named_scope("phase_resolve"):
            return local_ops.resolve(pivot, k, counts[0], counts[1], below,
                                     above, cap)

    # one level at a time: a vmap over the Q pivots would hold Q masked
    # copies of the whole input at once (Q x 4 GB at the paper's 10^9 f32)
    return jax.lax.map(one, (pivots, ks))


def gk_select_multi(parts: jax.Array, qs: tuple, *, eps: float = 0.01,
                    speculative: bool = True, block_select: bool = False,
                    check_nans: bool = True, backend=None) -> jax.Array:
    """Eager entry for ``_gk_select_multi_jit`` (same signature/semantics).

    Exactness guarantee: every returned level is bit-identical to the sort
    oracle, independent of eps/flags.  NaN policy: reject;
    ``check_nans=False`` opts out (see ``gk_select``).  ``backend`` picks
    the kernel implementation when ``block_select=True`` (see
    ``gk_select``)."""
    with obs.span("gk_select_multi"):
        if check_nans:
            local_ops.reject_nans(parts, "gk_select_multi")
        with obs.span("dispatch"):
            out = lowering.call(_gk_select_multi_jit, parts, tuple(qs),
                                eps=eps, speculative=speculative,
                                block_select=block_select, backend=backend)
        _count_whole_band(parts, eps, block_select)
        return out
