"""QuantileService: vectorized multi-tenant streaming quantile queries.

The paper's headline is that GK Select answers an exact quantile in a
constant number of actions; its most expensive action is sketch
construction — a full per-shard sort.  A query-per-job system pays that
sort on EVERY query.  This service keeps that cost amortized AND scales to
many tenants at once (DESIGN.md §6, §9): tenant sketches live in a single
**slot table** of stacked ``SketchState`` pytrees — one device array per
leaf with a leading stream axis — so one ingest tick advances every
touched stream with a constant number of jitted device calls
(``sketch_update_batch`` under vmap), not one dispatch per stream.

Storage model (DESIGN.md §9):

  * ``_stacked`` — a ``SketchState`` whose leaves carry a leading capacity
    axis ``(S, ...)``; a name→slot registry maps stream names to rows, and
    capacity doubles when the registry outgrows the table.
  * a **tick ring** of ``_TickRecord``s — each batched ingest stores one
    sentinel-padded ``(S_tick, L)`` matrix plus the slot row each row fed;
    per-stream chunks are sliced lazily at query time, so the raw
    population for exact queries is kept without per-stream Python lists.

Queries then come in three costs:

  ``approx(q)``    O(s) from the stream's sketch row — no data pass.
  ``exact(q)``     WARM GK Select: pivot from the live sketch row, so the
                   sketch phase — and its full-data sort — is skipped;
                   only count+extract (one streaming pass per chunk, fused
                   to a single HBM stream with ``fused=True``) and resolve
                   run.  3 actions -> 2 for every query after ingest.
  ``exact_all(qs)``ALL tenants × all levels in ONE fused job through the
                   grouped engine: G·Q pivots from the stacked table in
                   one call, one segmented count+extract pass per tick
                   record (one HBM stream each with ``fused=True``).

Exactness is unconditional: candidate caps are sized from the sketch's
*tracked* rank bound (``sketch_rank_bound``), and if a pathological stream
ever pushes the realized rank gap past the cap the service retries with
the exact gap — so ``exact``/``exact_all`` are always bit-identical to the
cold path (which is bit-identical to a full sort).

Quancurrent-style concurrency (PAPERS.md, DESIGN.md §10): workers ingest
into private ``QuantileService`` local buffers and periodically ``fold``
them into the shared service — one batched ``sketch_merge_batch`` dispatch
per fold, slack composing by max — so the hot ingest path never contends
on the shared table.  Three faces serve the threaded pipeline
(``launch/ingest_pool.py`` drives all of them):

  * ``stage(name, batch)`` — host-side append into the buffer, NO device
    work; ``commit_staged()`` folds everything staged as one batched tick.
    This is the worker thread's write path: device dispatch moves to the
    fold scheduler, where it batches across buffers.
  * ``fold_many(buffers)`` — ONE batched ingest tick for all staged data
    across the buffers plus ONE ``sketch_merge_many`` dispatch for their
    materialized slot rows, so K buffers cost one fold's dispatches.
  * a reader-writer lock — every public mutator takes the write side,
    every query the read side, so ``approx``/``exact``/``exact_all`` run
    concurrently with each other and are serialized only against folds.
    Exact answers are order-invariant (the rank-k element of a multiset
    does not depend on arrival order), so concurrent ingest keeps
    ``exact*`` bit-identical to a serial replay of the same batches.

Windowed queries (DESIGN.md §11): constructing with ``window_ticks=W_t``
turns on ring-buffered sub-window sketching — each stream additionally
maintains up to ``window_subs + 1`` mergeable fixed-budget sub-window rows
IN THE SAME slot table (a fresh row opens every ``ceil(W_t/window_subs)``
ticks, the oldest is retired back to the free list as the window slides),
and tick-ring records older than ``W_t`` ticks are retired, so resident
memory is bounded by the window, independent of total history length.
``windowed(name, q, window=...)`` then answers the EXACT quantile of the
values inside a trailing window (count- or tick-based): the pivot comes
from a ``sketch_merge_rows`` merge-on-query over the covering sub-window
rows (no sketch-phase sort — the warm path), count+extract runs only over
the ring slices inside the window, and the candidate cap adds half the
cover overcount to the merged sketch's tracked bound — with the same
widen-and-retry fallback, so window answers are bit-identical to sorting
the raw window.  ``approx_decayed`` reuses the sub-window rows for an
exponential-decay weighted quantile (newer sub-windows count more).
Without ``window_ticks`` the service behaves exactly as before (nothing is
retired; ``windowed`` still works via a cold per-window pivot).

Snapshot/restore: ``snapshot()`` captures the stacked table + tick ring +
registry as a flat leaf list plus JSON-able metadata (the format
``checkpoint.save_service_snapshot`` persists); ``from_snapshot`` rebuilds
a service whose warm ``exact()`` answers are bit-identical with zero
history replay.  Window state (tick clock, sub-window registry, retention
counters) rides the snapshot, so a restored windowed service resumes warm
mid-window.

Grouped streams (DESIGN.md §7): ``ingest_grouped(name, values, keys)``
buffers keyed batches and ``grouped(name, qs, num_groups)`` answers the
whole (group, level) matrix exactly in ONE job — one fused HBM pass per
chunk with ``fused=True``.  NaN policy: reject at ingest, so queries never
see a NaN.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import itertools
import math
import os
import threading
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import engine, local_ops
from repro.core.sketch import (SketchState, record_sketch_sort, sketch_budget,
                               sketch_init, sketch_init_stack,
                               sketch_merge_batch, sketch_merge_many,
                               sketch_merge_rows, sketch_query_decayed,
                               sketch_query_rank,
                               sketch_query_rank_batch, sketch_rank_bound,
                               sketch_rank_bound_batch, sketch_update,
                               sketch_update_batch)


def _round_up(x: int, multiple: int) -> int:
    return -(-x // multiple) * multiple


# --- ingest dispatch counter ------------------------------------------------
# Structural proof obligation for the slot-table refactor: one ingest tick
# must issue a CONSTANT number of jitted device calls regardless of how many
# streams it touches (the dict-of-streams design issued O(S)).  Every device
# dispatch on the ingest path ticks the ``service.ingest_dispatches``
# counter of ``repro.obs``; the tests assert the count is the same at any S,
# and the registry's lock keeps it exact under threaded ingest
# (launch/ingest_pool.py).
INGEST_DISPATCHES = "service.ingest_dispatches"
# A query program built for a candidate cap not seen before: ticked where
# ``_chunk_fn`` and ``_resolve_fn`` miss their caches.
CAP_PROGRAMS = "service.cap_programs"


def reset_ingest_dispatches() -> None:
    obs.reset(INGEST_DISPATCHES)


def ingest_dispatches() -> int:
    return obs.counters().get(INGEST_DISPATCHES, 0)


def record_ingest_dispatch(n: int = 1) -> None:
    obs.count(INGEST_DISPATCHES, n)


# --- reader-writer lock -----------------------------------------------------


class RWLock:
    """Shared/exclusive lock with a reentrant writer (DESIGN.md §10).

    Queries (readers) overlap each other and are excluded only while a fold
    or ingest (writer) holds the exclusive side.  The writer is reentrant —
    ``fold_many`` re-enters ``ingest_batch`` for staged data — and a thread
    holding the write side may take the read side (it degenerates to a
    no-op).  Read->write upgrades are NOT supported; no query path mutates.
    Readers re-entering while a writer *waits* are admitted (writers can
    starve under a saturated read load, never deadlock — folds are short
    and ingest pressure bounds read bursts in practice).
    """

    def __init__(self):
        self._cond = threading.Condition()
        self._readers = 0
        self._writer: Optional[int] = None   # owning thread ident
        self._depth = 0

    @contextlib.contextmanager
    def read(self):
        me = threading.get_ident()
        if self._writer == me:        # writer re-entering as a reader
            yield
            return
        with self._cond:
            while self._writer is not None:
                self._cond.wait()
            self._readers += 1
        try:
            yield
        finally:
            with self._cond:
                self._readers -= 1
                if self._readers == 0:
                    self._cond.notify_all()

    @contextlib.contextmanager
    def write(self):
        me = threading.get_ident()
        with self._cond:
            if self._writer == me:
                self._depth += 1
            else:
                while self._writer is not None or self._readers > 0:
                    self._cond.wait()
                self._writer = me
                self._depth = 1
        try:
            yield
        finally:
            with self._cond:
                self._depth -= 1
                if self._depth == 0:
                    self._writer = None
                    self._cond.notify_all()


def _locked(kind: str):
    """Method decorator: run under the service's read ("r") or write ("w")
    lock.  Public entry points are decorated; internals stay lock-free and
    rely on the reentrant writer for nested mutator->mutator calls."""
    def deco(fn):
        @functools.wraps(fn)
        def wrapper(self, *args, **kwargs):
            ctx = self._rw.read() if kind == "r" else self._rw.write()
            with ctx:
                return fn(self, *args, **kwargs)
        return wrapper
    return deco


def _query(fn):
    """Query decorator: commit any staged host batches first (a write),
    then run the query under the read lock — so queries always see every
    value handed to this service, and concurrent queries overlap.

    Every decorated query accepts ``commit=False`` to skip that implicit
    write: the query then reads COMMITTED state only, never mutates, and
    staged-but-uncommitted values are invisible to it.  This is the
    contract monitoring readers need (``StragglerMonitor.decide`` is
    documented non-mutating — before this flag its threshold query could
    land staged chunks mid-ingest)."""
    @functools.wraps(fn)
    def wrapper(self, *args, commit: bool = True, **kwargs):
        if commit and self._staged:
            self.commit_staged()
        with self._rw.read():
            return fn(self, *args, **kwargs)
    return wrapper


# Jitted phase kernels live at module level (not on the service instance):
# an lru_cache keyed on ``self`` would pin every service — and its buffered
# device chunks — for the process lifetime.  jax.jit's own shape-keyed cache
# handles per-batch-shape specialization.
_update_jit = jax.jit(sketch_update)
_query_jit = jax.jit(sketch_query_rank)
_query_batch_jit = jax.jit(sketch_query_rank_batch)
_bound_batch_jit = jax.jit(sketch_rank_bound_batch)


@jax.jit
def _update_rows(stacked: SketchState, slots, matrix, n_valid) -> SketchState:
    """ONE dispatch that advances every touched slot: gather the slot rows,
    run the vmapped masked update, scatter the rows back."""
    rows = jax.tree.map(lambda a: a[slots], stacked)
    upd = sketch_update_batch(rows, matrix, n_valid)
    return jax.tree.map(lambda a, r: a.at[slots].set(r), stacked, upd)


@jax.jit
def _update_rows_doubled(stacked: SketchState, slots2, matrix,
                         n_valid) -> SketchState:
    """Windowed-mode ingest: ONE dispatch that advances both the
    all-history row AND the current sub-window row of every touched stream.
    ``slots2`` is (2S,) — row i of the (S, L) tick matrix feeds
    ``slots2[i]`` (main) and ``slots2[S + i]`` (sub); the matrix is tiled
    once so the batched update stays a single sort.  Rows with no valid
    lanes point both entries at the main slot — a zero-length update leaves
    the row bit-untouched, so the duplicate scatter writes identical
    values."""
    rows = jax.tree.map(lambda a: a[slots2], stacked)
    m2 = jnp.concatenate([matrix, matrix], axis=0)
    nv2 = jnp.concatenate([n_valid, n_valid])
    upd = sketch_update_batch(rows, m2, nv2)
    return jax.tree.map(lambda a, r: a.at[slots2].set(r), stacked, upd)


# Merge-on-query pivot source for windowed queries: K gathered sub-window
# rows -> ONE summary via the sketch_merge_rows pairwise tree.  jit's
# shape-keyed cache specializes per cover size K, so a steady-state window
# replays one traced dispatch per query.
_merge_subs_jit = jax.jit(sketch_merge_rows)
_decayed_jit = jax.jit(sketch_query_decayed)


@jax.jit
def _merge_rows(mine: SketchState, my_slots, theirs: SketchState,
                their_slots) -> SketchState:
    """ONE dispatch that folds a worker buffer's slot rows into ours."""
    a = jax.tree.map(lambda x: x[my_slots], mine)
    b = jax.tree.map(lambda x: x[their_slots], theirs)
    merged = sketch_merge_batch(a, b)
    return jax.tree.map(lambda x, r: x.at[my_slots].set(r), mine, merged)


@jax.jit
def _reset_rows(stacked: SketchState, slots) -> SketchState:
    """Re-initialize recycled slots (rows freed by ``drop_stream``)."""
    budget = stacked.values.shape[1]
    fresh = sketch_init_stack(slots.shape[0], budget,
                              stacked.values.dtype)
    return jax.tree.map(lambda a, f: a.at[slots].set(f), stacked, fresh)


# Transforms a batched ingest may apply on device before padding — keyed by
# name so the packing jit cache stays hashable.  "abs_f32" is the
# StreamingCalibrator's |activation| in f32.
_TRANSFORMS = {
    "abs_f32": lambda a: jnp.abs(a.astype(jnp.float32)),
}

# Host-side mirrors of _TRANSFORMS, applied at stage() time in a worker
# thread (|x| clears the sign bit and the ->f32 cast rounds identically on
# host and device, so staged-then-committed answers stay bit-identical to
# the device-transform tick).
_HOST_TRANSFORMS = {
    "abs_f32": lambda a: np.abs(np.asarray(a).astype(np.float32)),
}


@functools.lru_cache(maxsize=None)
def _fold_many_fn(num_buffers: int):
    """ONE dispatch that folds the materialized slot rows of ``num_buffers``
    worker tables into ours: gather our rows for the union of their stream
    names, gather each buffer's rows aligned to that union (missing names
    index an appended empty row via -1), tree-merge all of them with
    ``sketch_merge_many``, scatter back.  K buffers -> one `_merge_rows`-
    class dispatch instead of K (DESIGN.md §10)."""
    @jax.jit
    def fn(mine: SketchState, my_slots, tables, idxs) -> SketchState:
        mine_rows = jax.tree.map(lambda a: a[my_slots], mine)
        parts = [mine_rows]
        for table, idx in zip(tables, idxs):
            budget = table.values.shape[1]
            empty = sketch_init_stack(1, budget, table.values.dtype)
            ext = jax.tree.map(lambda a, e: jnp.concatenate([a, e], axis=0),
                               table, empty)
            parts.append(jax.tree.map(lambda a: a[idx], ext))
        merged = sketch_merge_many(parts)
        return jax.tree.map(lambda a, r: a.at[my_slots].set(r), mine, merged)
    return fn


@functools.lru_cache(maxsize=None)
def _pack_fn(length: int, dtype_str: str, transform: Optional[str]):
    """Device-side pack: flatten/transform each array, pad to ``length``
    with the dtype's high sentinel, stack to one (S, L) matrix — ONE
    dispatch for arbitrarily many device-resident inputs."""
    tf = _TRANSFORMS[transform] if transform else None
    dtype = jnp.dtype(dtype_str)
    _, hi = local_ops._sentinels(dtype)

    def fn(*arrays):
        rows = []
        for a in arrays:
            a = jnp.asarray(a).reshape(-1)
            if tf is not None:
                a = tf(a)
            a = a.astype(dtype)
            pad = length - a.shape[0]
            if pad:
                a = jnp.concatenate([a, jnp.full((pad,), hi, dtype)])
            rows.append(a)
        return jnp.stack(rows)
    return jax.jit(fn)


def _high_sentinel_np(dtype):
    """Host-side high sentinel matching ``local_ops._sentinels``."""
    if jnp.issubdtype(dtype, jnp.floating):
        return dtype.type(np.inf)
    return np.iinfo(dtype).max


@functools.lru_cache(maxsize=None)
def _chunk_fn(cap: int, fused: bool, backend=None):
    """Per-chunk count+extract with a static candidate cap: the warm query's
    only data pass.  fused=True routes through the single-pass kernel seam
    (one HBM stream per chunk on a Pallas ``backend``); the kernel takes the
    pivot as a plain operand, so externally-supplied (warm) pivots need no
    retrace.  ``backend`` is the dispatch handle the seam closes over
    (hashable: None / spec string / frozen Backend — safe as an lru key)."""
    obs.count(CAP_PROGRAMS)
    if fused:
        from repro.kernels import ops as kernel_ops

        def fn(x, pivot):
            return kernel_ops.fused_count_extract(x, pivot, cap,
                                                  backend=backend)
        return fn   # kernel wrapper dispatches (and ticks) itself

    def fn(x, pivot):
        return local_ops.fused_count_extract(x, pivot, cap)
    return jax.jit(fn)


@functools.lru_cache(maxsize=None)
def _grouped_sketch_fn(num_groups: int, s: int):
    """Per-chunk segmented sketch (one (key, value) sort of the chunk)."""
    from repro.core.grouped import segmented_sketch_local
    return jax.jit(lambda v, k: segmented_sketch_local(v, k, num_groups, s))


@functools.lru_cache(maxsize=None)
def _grouped_chunk_fn(cap: int, fused: bool, backend=None):
    """Per-chunk segmented count+extract for all (G, Q) pivots: the grouped
    query's only data pass — ONE HBM stream per chunk on a Pallas
    ``backend`` (fused=True), 3*G*Q jnp streams otherwise."""
    if fused:
        from repro.kernels import ops as kernel_ops

        def fn(v, k, pivots):
            return kernel_ops.segmented_count_extract(v, k, pivots, cap,
                                                      backend=backend)
        return fn   # kernel wrapper dispatches (and ticks) itself

    def fn(v, k, pivots):
        return local_ops.grouped_count_extract(v, k, pivots, cap)
    return jax.jit(fn)


@functools.lru_cache(maxsize=None)
def _row_chunk_fn(cap: int):
    """Row-aligned count+extract for a tick record: every row of the
    (S, L) matrix belongs to exactly ONE stream, so it only meets its own
    Q pivots — O(S*L*Q) work in one dispatch, where the flat segmented
    fallback would pay O(S*L * G*Q).  Pad lanes are masked by ``n_valid``.
    Returns ``(counts (S, Q, 3), below (S, Q, cap), above (S, Q, cap))``
    with ``fused_count_extract`` sentinel semantics."""
    @jax.jit
    def fn(data, row_pivots, n_valid):
        lo, hi = local_ops._sentinels(data.dtype)
        lane = jnp.arange(data.shape[1])

        def per_row(row, pv, nv):
            valid = lane < nv

            def per_pivot(p):
                is_lt = valid & (row < p)
                is_gt = valid & (row > p)
                counts = jnp.stack([
                    jnp.sum(is_lt, dtype=jnp.int32),
                    jnp.sum(valid & (row == p), dtype=jnp.int32),
                    jnp.sum(is_gt, dtype=jnp.int32)])
                below = jax.lax.top_k(jnp.where(is_lt, row, lo), cap)[0]
                above = -jax.lax.top_k(-jnp.where(is_gt, row, hi), cap)[0]
                return counts, below, above
            return jax.vmap(per_pivot)(pv)
        return jax.vmap(per_row)(data, row_pivots, n_valid)
    return fn


@functools.lru_cache(maxsize=None)
def _resolve_fn(cap: int):
    obs.count(CAP_PROGRAMS)

    def fn(pivot, k, counts, belows, aboves):
        lt = sum(c[0] for c in counts)
        eq = sum(c[1] for c in counts)
        below = jnp.concatenate(belows)
        above = jnp.concatenate(aboves)
        return (local_ops.resolve(pivot, k, lt, eq, below, above, cap),
                lt, eq)
    return jax.jit(fn)


@dataclasses.dataclass(frozen=True)
class Window:
    """Trailing-window spec for ``QuantileService.windowed`` — exactly one
    of ``ticks`` (the last N ingest ticks on the service's logical clock;
    one landed ``ingest_batch`` call is one tick) or ``values`` (the last N
    values of the stream itself).  A bare ``int`` passed as ``window=``
    means ``Window(ticks=...)``."""
    ticks: Optional[int] = None
    values: Optional[int] = None

    def __post_init__(self):
        if (self.ticks is None) == (self.values is None):
            raise ValueError("specify exactly one of Window(ticks=...) or "
                             "Window(values=...)")
        span = self.ticks if self.ticks is not None else self.values
        if int(span) < 1:
            raise ValueError(f"window must be positive, got {span}")


def _as_window(window) -> Window:
    if isinstance(window, Window):
        return window
    return Window(ticks=int(window))


@dataclasses.dataclass
class _SubWindow:
    """One live sub-window of one stream: the slot-table row its sketch
    lives in, the sub-window index on the tick clock (it spans ticks
    ``[index*sub_ticks, (index+1)*sub_ticks - 1]``), and the number of
    values folded into it."""
    slot: int
    index: int
    n: int


@dataclasses.dataclass
class _TickRecord:
    """One batched ingest tick: a sentinel-padded (S_tick, L) value matrix
    plus, per row, the slot it fed (-1 after that stream is dropped) and
    the count of valid leading lanes.  Rows are sliced lazily at query
    time — the ring IS the buffered population of every stream.  ``tick``
    is the record's position on the service's logical clock (windowed mode
    retires records older than ``window_ticks``)."""
    data: jax.Array           # (S_tick, L) device matrix, sentinel-padded
    slots: np.ndarray         # (S_tick,) int32 slot ids, -1 = dropped
    n_valid: np.ndarray       # (S_tick,) int32 valid lanes per row
    tick: int = 0             # logical-clock stamp


@dataclasses.dataclass
class _StreamView:
    """Read-only view of one tenant: its sketch row, its buffered chunks
    (lazily sliced from the tick ring), and its count."""
    state: SketchState
    chunks: List[jax.Array]
    n: int


@dataclasses.dataclass
class _GroupedStream:
    chunks: List[jax.Array]        # values, flat per ingest batch
    key_chunks: List[jax.Array]    # int32 group ids, aligned with chunks
    n: int


class QuantileService:
    """Slot table of stacked tenant sketches + a tick ring of raw batches.

    All device work goes through shape-keyed jitted kernels, so streams fed
    by fixed-size batches (the serving case: one activation batch per
    decode step) trace each phase once and replay it for the service's
    lifetime.  A batched ingest tick touching 10^4 streams issues the same
    constant number of device calls as one touching a single stream
    (the ``service.ingest_dispatches`` counter of ``repro.obs`` counts
    them; the tests assert O(1)).
    """

    def __init__(self, *, eps: float = 0.01, budget: Optional[int] = None,
                 dtype=jnp.float32, fused: bool = False,
                 check_nans: bool = True, backend=None,
                 window_ticks: Optional[int] = None, window_subs: int = 8):
        """Exactness guarantee: ``exact``/``exact_all``/``grouped`` answers
        are bit-identical to a full sort of everything ingested, for every
        combination of the flags below — they steer data movement only.

        ``fused=True`` routes the count+extract pass of each query through
        the kernel layer (one HBM stream per chunk on a Pallas backend);
        ``backend`` (None | "pallas" | "pallas_interpret" | "jnp" | a
        ``kernels.dispatch.Backend``) picks the kernel implementation, with
        None selecting per platform at trace time — compiled Pallas on TPU,
        jitted jnp fallback on CPU (``kernels.dispatch.select_backend``).
        Ignored without ``fused``.

        ``window_ticks=W_t`` opts into windowed retention (DESIGN.md §11):
        ring records and sub-window sketch rows older than ``W_t`` ticks
        are retired, bounding resident memory by the window instead of
        total history; ``window_subs`` sets the number of sub-windows the
        window is split into (pivot-merge cost and decay resolution —
        each sub spans ``ceil(W_t/window_subs)`` ticks).  All-history
        ``exact``/``exact_all`` raise once a stream's history extends past
        the horizon (use ``windowed``); ``approx`` stays available.
        Without ``window_ticks`` nothing is ever retired and the service
        behaves exactly as before.

        NaN policy: reject at ingest (DESIGN.md §7), so queries never see a
        NaN.  ``check_nans=False`` opts out of that check: it is a blocking
        device->host sync per tick, which a tight decode loop (one ingest
        per token) may not afford.  Opting out transfers the NaN-free
        contract to the caller — queries over a NaN-poisoned stream are
        undefined."""
        if not 0.0 < eps < 1.0:
            raise ValueError(f"eps must be in (0,1), got {eps}")
        self.eps = eps
        self.budget = int(budget) if budget else sketch_budget(eps)
        self.dtype = jnp.dtype(dtype)
        self.fused = fused
        self.backend = backend
        self.check_nans = check_nans
        # --- windowed retention (DESIGN.md §11) ---------------------------
        if window_ticks is not None and int(window_ticks) < 1:
            raise ValueError(f"window_ticks must be >= 1, got {window_ticks}")
        if int(window_subs) < 1:
            raise ValueError(f"window_subs must be >= 1, got {window_subs}")
        self.window_ticks = int(window_ticks) if window_ticks else None
        self.window_subs = int(window_subs)
        self._sub_ticks = (-(-self.window_ticks // self.window_subs)
                           if self.window_ticks else 0)
        self._tick = 0                               # logical clock
        self._subs: Dict[int, List[_SubWindow]] = {}  # main slot -> subs
        self._retained: List[int] = []               # per-slot live values
        # --- concurrency (DESIGN.md §10) ----------------------------------
        # Mutators (ingest/fold/drop/stage-commit) take the write side,
        # queries the read side; worker threads never touch a shared
        # service's lock because they write into private local_buffer()s.
        self._rw = RWLock()
        self._requests = itertools.count()   # numbers windowed queries
        # --- slot table ---------------------------------------------------
        self._stacked: Optional[SketchState] = None   # leaves (capacity, ...)
        self._names: Dict[str, int] = {}              # name -> slot
        self._free: List[int] = []                    # unassigned slots
        self._dirty: set = set()                      # freed, needs re-init
        self._counts: List[int] = []                  # per-slot value count
        self._capacity: int = 0
        self._ring: List[_TickRecord] = []
        self._grouped: Dict[str, _GroupedStream] = {}
        # --- staged host batches (the worker-thread write path) -----------
        self._staged: Dict[str, List[np.ndarray]] = {}
        self._staged_n: int = 0
        self._staged_unchecked: bool = False   # exotic dtype skipped host NaN check

    # -- slot table ----------------------------------------------------------

    def _grow(self, min_capacity: int) -> None:
        """Capacity-doubling growth of the stacked table (amortized O(1)
        row moves per registered stream)."""
        new_cap = max(4, self._capacity)
        while new_cap < min_capacity:
            new_cap *= 2
        if new_cap == self._capacity:
            return
        add = new_cap - self._capacity
        fresh = jax.tree.map(jnp.asarray,
                             sketch_init_stack(add, self.budget, self.dtype))
        if self._stacked is None:
            self._stacked = fresh
        else:
            self._stacked = jax.tree.map(
                lambda a, f: jnp.concatenate([a, f], axis=0),
                self._stacked, fresh)
        record_ingest_dispatch()
        self._free.extend(range(self._capacity, new_cap))
        self._counts.extend([0] * add)
        self._retained.extend([0] * add)
        self._capacity = new_cap

    def _alloc_slots(self, count: int) -> List[int]:
        """Take ``count`` slots off the free list (growing the table as
        needed) with recycled rows re-initialized in ONE batched reset — a
        recycled slot must never leak its previous tenant's sketch row (the
        ring-record side of that guarantee is ``drop_stream`` marking rows
        -1)."""
        if len(self._free) < count:
            self._grow(self._capacity + (count - len(self._free)))
        out, recycled = [], []
        for _ in range(count):
            slot = self._free.pop()
            if slot in self._dirty:
                recycled.append(slot)
                self._dirty.discard(slot)
            self._counts[slot] = 0
            self._retained[slot] = 0
            out.append(slot)
        if recycled:
            self._stacked = _reset_rows(
                self._stacked, jnp.asarray(recycled, jnp.int32))
            record_ingest_dispatch()
        return out

    def _free_slot(self, slot: int) -> None:
        """Return one slot to the free list (sketch row re-init deferred to
        the next ``_alloc_slots`` via the dirty set)."""
        self._free.append(slot)
        self._dirty.add(slot)
        self._counts[slot] = 0
        self._retained[slot] = 0

    def _ensure_slots(self, names: Sequence[str]) -> np.ndarray:
        """Register any unknown names (growing the table as needed) and
        return the slot row per name."""
        missing = [n for n in names if n not in self._names]
        if missing:
            for n, slot in zip(missing, self._alloc_slots(len(missing))):
                self._names[n] = slot
        return np.asarray([self._names[n] for n in names], dtype=np.int32)

    def _row_state(self, slot: int) -> SketchState:
        return jax.tree.map(lambda a: a[slot], self._stacked)

    def _chunks_for(self, slot: int) -> List[jax.Array]:
        """Lazily slice this slot's buffered chunks out of the tick ring."""
        return [rec.data[i, :nv] for rec, i, nv in self._stream_rows(slot)]

    def _stream_rows(self, slot: int):
        """This slot's non-empty ring rows as (record, row, n_valid)
        triples, oldest tick first (appends are clock-ordered, so list
        order IS tick order)."""
        out = []
        for rec in self._ring:
            for i in np.nonzero(rec.slots == slot)[0]:
                nv = int(rec.n_valid[i])
                if nv:
                    out.append((rec, int(i), nv))
        return out

    # -- windowed retention internals (DESIGN.md §11) ------------------------

    def _rotate_subs(self, slots: np.ndarray, n_valid: np.ndarray,
                     tick: int) -> np.ndarray:
        """Per touched stream: retire sub-windows that slid past the
        retention horizon (their slots go back to the free list), open a
        fresh sub-window row when the tick crossed a ``sub_ticks`` boundary,
        and account this tick's values.  Returns the (S,) sub-window slot
        per tick row — rows with no valid lanes alias their main slot (the
        doubled update leaves those bit-untouched).  Retirement is lazy
        (on touch): an idle stream keeps at most ``window_subs + 1`` sub
        rows parked, never more."""
        idx = tick // self._sub_ticks
        horizon = tick + 1 - self.window_ticks   # oldest retained tick
        sub_slots = np.empty(len(slots), np.int32)
        need_new = []
        for i, (slot, nv) in enumerate(zip(slots, n_valid)):
            if not nv:
                sub_slots[i] = slot
                continue
            subs = self._subs.setdefault(int(slot), [])
            while subs and (subs[0].index + 1) * self._sub_ticks <= horizon:
                self._free_slot(subs.pop(0).slot)
            if subs and subs[-1].index == idx:
                sub_slots[i] = subs[-1].slot
            else:
                need_new.append(i)
        if need_new:
            for i, slot in zip(need_new, self._alloc_slots(len(need_new))):
                self._subs[int(slots[i])].append(
                    _SubWindow(slot=slot, index=idx, n=0))
                sub_slots[i] = slot
        for slot, nv in zip(slots, n_valid):
            if nv:
                self._subs[int(slot)][-1].n += int(nv)
        return sub_slots

    def _retire_ring(self) -> None:
        """Drop ring records that slid fully past the retention horizon,
        crediting their values out of the per-slot retained counters.  The
        ring holds at most ``window_ticks`` records afterwards, so windowed
        memory is bounded by the window, not by history."""
        horizon = self._tick - self.window_ticks
        if horizon <= 0:
            return
        keep = []
        for rec in self._ring:
            if rec.tick >= horizon:
                keep.append(rec)
                continue
            for s, nv in zip(rec.slots, rec.n_valid):
                if s >= 0:
                    self._retained[int(s)] -= int(nv)
        self._ring = keep

    # -- stream lifecycle ---------------------------------------------------

    @_locked("w")
    def stream(self, name: str) -> _StreamView:
        """Get-or-create accessor: registers ``name`` (assigning a slot) if
        unknown and returns a read-only view of its row + chunks.  Reads
        that must NOT mutate go through ``stream_count``/``rank_bound``."""
        self._ensure_slots([name])
        slot = self._names[name]
        return _StreamView(state=self._row_state(slot),
                           chunks=self._chunks_for(slot),
                           n=self._counts[slot])

    @_locked("r")
    def streams(self):
        return sorted(self._names)

    @_locked("w")
    def drop_stream(self, name: str) -> None:
        """Forget one stream: its slot (and any sub-window slots) return to
        the free list, its ring rows are marked dead (-1) so a future
        tenant of the recycled slot can never slice them into its chunks,
        windows, or ``exact_all`` groups."""
        slot = self._names.pop(name, None)
        if slot is not None:
            for sub in self._subs.pop(slot, []):
                self._free_slot(sub.slot)
            self._free_slot(slot)
            for rec in self._ring:
                rec.slots[rec.slots == slot] = -1
            # drop records no live stream references
            self._ring = [r for r in self._ring if (r.slots >= 0).any()]
        self._grouped.pop(name, None)

    @_locked("r")
    def stream_count(self, name: str) -> int:
        """Non-mutating read: 0 for unknown names (no slot is created).
        Staged-but-uncommitted values are not counted (``staged_count``
        tracks those)."""
        slot = self._names.get(name)
        return self._counts[slot] if slot is not None else 0

    @_locked("r")
    def grouped_stream_count(self, name: str) -> int:
        st = self._grouped.get(name)
        return st.n if st else 0

    @_locked("r")
    def rank_bound(self, name: str) -> int:
        """The live sketch's tracked worst-case query rank error.
        Non-mutating read: unknown names raise ``KeyError``."""
        slot = self._names.get(name)
        if slot is None:
            raise KeyError(f"unknown stream {name!r}")
        return int(sketch_rank_bound(self._row_state(slot)))

    # -- ingest -------------------------------------------------------------

    def ingest(self, name: str, batch) -> None:
        """Fold one batch into one stream: S=1 case of ``ingest_batch``."""
        self.ingest_batch([name], [batch])

    @_locked("w")
    def ingest_batch(self, names: Sequence[str], batches,
                     *, transform: Optional[str] = None,
                     _nan_checked: bool = False) -> None:
        """Fold one batch per named stream — ONE tick, a CONSTANT number of
        device dispatches no matter how many streams it touches:

          1. pack the batches into one sentinel-padded (S, L) matrix
             (host-side for numpy inputs; one jitted call for device
             inputs),
          2. one jitted gather→``sketch_update_batch``→scatter over the
             slot table (ONE batched sort — ticks the sketch-sort counter
             once),
          3. append one ``_TickRecord`` to the ring.

        ``transform`` names a device-side pre-transform from the module
        ``_TRANSFORMS`` table (e.g. ``"abs_f32"`` for calibration).
        NaN policy: reject (DESIGN.md §7) — validating once at ingest
        means queries never see a NaN, so they stay check-free.
        ``_nan_checked`` marks batches already validated host-side (the
        ``stage``/``commit_staged`` path) so the blocking device check is
        not paid twice.

        An ALL-empty tick (no names, or every batch zero-length — host or
        device) is a complete no-op: no stream registration, no sketch
        sort, no ring record, no logical-clock advance.  A MIXED tick still
        registers its empty rows' streams (count 0, sketch row untouched).
        """
        with obs.span("service.ingest_batch"):
            self._ingest_tick(list(names), list(batches), transform,
                              _nan_checked)

    def _ingest_tick(self, names: List[str], batches: list,
                     transform: Optional[str], nan_checked: bool) -> None:
        if len(names) != len(batches):
            raise ValueError(f"names/batches length mismatch: "
                             f"{len(names)} vs {len(batches)}")
        if len(set(names)) != len(names):
            raise ValueError("duplicate stream names in one ingest tick")
        if not names:
            return
        if transform is not None and transform not in _TRANSFORMS:
            raise ValueError(f"unknown transform {transform!r}; "
                             f"have {sorted(_TRANSFORMS)}")

        device_in = transform is not None or any(
            isinstance(b, jax.Array) for b in batches)
        if device_in:
            lengths = [int(np.prod(jnp.shape(b))) for b in batches]
        else:
            batches = [np.asarray(b).reshape(-1) for b in batches]
            lengths = [b.size for b in batches]
        length = max(lengths)
        if length == 0:
            return                      # all-empty tick: complete no-op

        slots = self._ensure_slots(names)

        with obs.span("service.pack"):
            if device_in:
                matrix = _pack_fn(length, self.dtype.name, transform)(*batches)
                record_ingest_dispatch()    # the one packing dispatch
            else:
                hi = _high_sentinel_np(self.dtype)
                host = np.full((len(batches), length), hi, dtype=self.dtype)
                for i, b in enumerate(batches):
                    host[i, :lengths[i]] = b
                matrix = jnp.asarray(host)
                record_ingest_dispatch()    # the one host->device transfer
        n_valid = np.asarray(lengths, dtype=np.int32)

        if self.check_nans and not nan_checked:
            local_ops.reject_nans(matrix, "QuantileService.ingest")

        tick = self._tick
        record_sketch_sort()            # sketch_update_batch sorts the tick
        record_ingest_dispatch()        # the one batched update dispatch
        if self.window_ticks is not None:
            with obs.span("service.rotate"):
                sub_slots = self._rotate_subs(slots, n_valid, tick)
            with obs.span("service.update"):
                self._stacked = _update_rows_doubled(
                    self._stacked,
                    jnp.asarray(np.concatenate([slots, sub_slots])),
                    matrix, jnp.asarray(n_valid))
        else:
            with obs.span("service.update"):
                self._stacked = _update_rows(self._stacked,
                                             jnp.asarray(slots), matrix,
                                             jnp.asarray(n_valid))
        for slot, nv in zip(slots, n_valid):
            self._counts[int(slot)] += int(nv)
            self._retained[int(slot)] += int(nv)
        self._ring.append(_TickRecord(data=matrix, slots=slots.copy(),
                                      n_valid=n_valid, tick=tick))
        self._tick = tick + 1
        if self.window_ticks is not None:
            with obs.span("service.retire"):
                self._retire_ring()

    @_locked("w")
    def ingest_grouped(self, name: str, values, keys) -> None:
        """Buffer one (values, keys) batch for per-group queries.  Keys are
        int32 group ids; out-of-range ids belong to no group (the engine
        ignores them — use them to mark pad/invalid lanes).  NaN policy:
        reject at ingest, like ``ingest``."""
        values = jnp.asarray(values).reshape(-1).astype(self.dtype)
        keys = jnp.asarray(keys).reshape(-1).astype(jnp.int32)
        if values.shape != keys.shape:
            raise ValueError(f"values/keys length mismatch: "
                             f"{values.shape} vs {keys.shape}")
        if self.check_nans:
            local_ops.reject_nans(values, "QuantileService.ingest_grouped")
        if values.size == 0:
            return
        st = self._grouped.setdefault(name, _GroupedStream([], [], 0))
        st.chunks.append(values)
        st.key_chunks.append(keys)
        st.n += int(values.size)

    # -- staging (the worker-thread write path; DESIGN.md §10) ---------------

    @_locked("w")
    def stage(self, name: str, batch, *,
              transform: Optional[str] = None) -> None:
        """Append one batch host-side WITHOUT any device work — the
        contention-free write an ingest-pool worker thread performs on its
        private ``local_buffer()``.  ``commit_staged`` (or the fold
        scheduler via ``fold_many``) later folds everything staged as ONE
        batched tick per stream, so device-dispatch overhead is paid per
        epoch, not per batch.

        ``transform`` applies the host mirror of the named ``_TRANSFORMS``
        entry immediately (in the calling worker thread — that is the
        point: it is off the producer's critical path).  NaN policy is
        enforced here when the host dtype supports it, so the error
        surfaces in the thread that staged the bad batch; exotic dtypes
        defer the check to commit.  Queries on this service auto-commit,
        so staged values are never silently invisible to ``exact``."""
        if transform is not None:
            if transform not in _HOST_TRANSFORMS:
                raise ValueError(f"unknown transform {transform!r}; "
                                 f"have {sorted(_HOST_TRANSFORMS)}")
            arr = _HOST_TRANSFORMS[transform](batch).reshape(-1)
        else:
            arr = np.asarray(batch).reshape(-1)
        if self.check_nans and jnp.issubdtype(self.dtype, jnp.floating):
            if isinstance(arr.dtype, np.dtype) and arr.dtype.kind == "f":
                if np.isnan(arr).any():
                    raise ValueError(
                        f"QuantileService.stage: NaN in input for stream "
                        f"{name!r} (NaN policy REJECT, DESIGN.md §7)")
            else:        # ml_dtypes etc: host isnan unsupported — defer
                self._staged_unchecked = True
        self._staged.setdefault(name, []).append(arr)
        self._staged_n += int(arr.size)

    @property
    def staged_count(self) -> int:
        """Values staged host-side and not yet committed to the table."""
        return self._staged_n

    @_locked("w")
    def commit_staged(self) -> None:
        """Fold everything staged as ONE batched ingest tick (per-stream
        concatenation -> ``ingest_batch``).  No-op when nothing is staged."""
        if not self._staged:
            return
        staged, self._staged = self._staged, {}
        self._staged_n = 0
        unchecked, self._staged_unchecked = self._staged_unchecked, False
        names = sorted(staged)
        batches = [staged[n][0] if len(staged[n]) == 1
                   else np.concatenate(staged[n]) for n in names]
        self.ingest_batch(names, batches, _nan_checked=not unchecked)

    # -- fold (Quancurrent-style worker buffers) -----------------------------

    def local_buffer(self) -> "QuantileService":
        """A private worker-side buffer with this service's sketch/engine
        configuration — ingest (or ``stage``) into it contention-free, then
        ``fold`` it back in.  Window config is deliberately NOT inherited:
        a buffer has no meaningful tick clock (folds land its values at the
        target's current tick), and a windowed target only accepts staged
        data from buffers (see ``fold_many``)."""
        return QuantileService(eps=self.eps, budget=self.budget,
                               dtype=self.dtype, fused=self.fused,
                               check_nans=self.check_nans,
                               backend=self.backend)

    def _validate_fold(self, other: "QuantileService") -> None:
        """A buffer folds in only if the FULL sketch/engine config matches.
        budget/dtype mismatches corrupt the merge outright; an ``eps``
        mismatch is subtler — cap sizing (``grouped``) and the claimed
        rank bound follow self.eps, so silently folding a coarser buffer
        would under-size caps and over-claim precision; ``fused``/
        ``backend`` steer data movement only, but a mismatch means the
        buffer was not made by ``local_buffer()`` and the caller's intent
        is ambiguous — reject loudly rather than guess."""
        mismatched = [
            f"{field}: {theirs!r} vs {ours!r}"
            for field, theirs, ours in [
                ("budget", other.budget, self.budget),
                ("dtype", other.dtype, self.dtype),
                ("eps", other.eps, self.eps),
                ("fused", bool(other.fused), bool(self.fused)),
                ("backend", other.backend, self.backend),
            ] if theirs != ours]
        if mismatched:
            raise ValueError("cannot fold: config mismatch "
                             "(" + "; ".join(mismatched) + ")")
        if other.window_ticks is not None:
            raise ValueError(
                "cannot fold a windowed buffer: its tick clock is private "
                "and meaningless on the target — worker buffers must be "
                "plain (local_buffer() makes them so)")

    def fold(self, other: "QuantileService") -> None:
        """Fold one worker buffer into this service: ONE batched
        ``sketch_merge_batch`` dispatch aligns the buffer's streams onto
        our slots (slack composes by max under merge, so warm answers stay
        exact), and the buffer's tick ring is re-slotted host-side.
        ``fold_many`` is the K-buffer generalization."""
        self.fold_many([other])

    @_locked("w")
    def fold_many(self, others: Sequence["QuantileService"]) -> None:
        """Fold SEVERAL worker buffers at once — the fold scheduler's batch
        step (DESIGN.md §10).  Device cost is one fold, not K: all staged
        host batches across the buffers land as ONE batched ingest tick
        (per-stream concatenation), and all materialized slot rows land in
        ONE ``sketch_merge_many`` dispatch.  Buffers must be quiescent
        (handed off — no concurrent writers); fold order only shapes the
        approximate summary, never ``exact*`` answers, which are
        order-invariant.  The buffers are left drained of staged data but
        otherwise untouched."""
        others = [o for o in others if o is not self]
        for other in others:
            self._validate_fold(other)

        # 1. staged host data: one batched tick for everything -------------
        staged: Dict[str, List[np.ndarray]] = {}
        unchecked = False
        for other in others:
            if not other._staged:
                continue
            for name, arrs in other._staged.items():
                staged.setdefault(name, []).extend(arrs)
            unchecked |= other._staged_unchecked
            other._staged = {}
            other._staged_n = 0
            other._staged_unchecked = False
        if staged:
            names = sorted(staged)
            batches = [staged[n][0] if len(staged[n]) == 1
                       else np.concatenate(staged[n]) for n in names]
            self.ingest_batch(names, batches, _nan_checked=not unchecked)

        # 2. materialized slot rows: one sketch_merge_many dispatch --------
        tabled = [o for o in others if o._names and o._stacked is not None]
        if tabled and self.window_ticks is not None:
            # a buffer's materialized rows carry no tick attribution, so a
            # windowed target cannot place them on its clock; the staged
            # path above (what IngestPool uses) lands as a normal tick and
            # stays fully supported
            raise ValueError(
                "cannot fold materialized worker tables into a windowed "
                "service — stage() into the buffer (or ingest through the "
                "shared service) so values land with a tick")
        if tabled:
            union = sorted({n for o in tabled for n in o._names})
            my_slots = self._ensure_slots(union)
            tables = tuple(o._stacked for o in tabled)
            idxs = tuple(
                jnp.asarray([o._names.get(n, -1) for n in union],
                            dtype=jnp.int32)
                for o in tabled)
            self._stacked = _fold_many_fn(len(tabled))(
                self._stacked, jnp.asarray(my_slots), tables, idxs)
            record_ingest_dispatch()
            slot_of = {n: int(m) for n, m in zip(union, my_slots)}
            adopted = False
            for o in tabled:
                remap = {int(t): slot_of[n] for n, t in o._names.items()}
                for t, m in remap.items():
                    self._counts[m] += o._counts[t]
                    self._retained[m] += o._counts[t]
                for rec in o._ring:
                    new_slots = np.asarray(
                        [remap.get(int(s), -1) for s in rec.slots],
                        dtype=np.int32)
                    if (new_slots >= 0).any():
                        # adopted records land at the CURRENT tick: the
                        # buffer's own clock is meaningless here, and
                        # stamping now keeps the ring clock-ordered
                        self._ring.append(_TickRecord(
                            data=rec.data, slots=new_slots,
                            n_valid=rec.n_valid.copy(), tick=self._tick))
                        adopted = True
            if adopted:
                self._tick += 1

        # 3. grouped streams: host-side adoption ---------------------------
        for other in others:
            for name, gs in other._grouped.items():
                mine = self._grouped.setdefault(name,
                                                _GroupedStream([], [], 0))
                mine.chunks.extend(gs.chunks)
                mine.key_chunks.extend(gs.key_chunks)
                mine.n += gs.n

    # -- queries ------------------------------------------------------------

    def _require(self, name: str) -> int:
        slot = self._names.get(name)
        if slot is None or self._counts[slot] == 0:
            raise ValueError(f"stream {name!r} is empty")
        return slot

    def _require_full_history(self, name: str, slot: int) -> None:
        """All-history exact queries need the whole population resident; a
        windowed service retires ring records past the horizon, after which
        only ``windowed``/``approx`` remain answerable for that stream."""
        if self._retained[slot] < self._counts[slot]:
            raise ValueError(
                f"stream {name!r}: {self._counts[slot] - self._retained[slot]}"
                f" of {self._counts[slot]} values have been retired past the "
                f"retention horizon ({self.window_ticks} ticks) — "
                f"all-history exact queries are unavailable on a windowed "
                f"service once history slides out; use windowed() or "
                f"approx()")

    @_query
    def approx(self, name: str, q: float):
        """Approximate q-quantile from the sketch alone: O(s), zero passes
        over the data; rank error <= ``rank_bound(name)``."""
        slot = self._require(name)
        k = local_ops.target_rank(self._counts[slot], q)
        return _query_jit(self._row_state(slot), k)

    @_query
    def exact(self, name: str, q: float, *, warm: bool = True):
        """EXACT q-quantile of everything ingested so far.

        warm=True (default): pivot straight from the live sketch row — no
        sketch-phase sort; 2 of the paper's 3 actions.  warm=False is the
        cold reference path: rebuild the sketch from the buffered chunks
        (one sort per chunk) exactly as a stateless job would, then run the
        same count+extract+resolve.  Both are exact, hence bit-identical.
        """
        slot = self._require(name)
        self._require_full_history(name, slot)
        n = self._counts[slot]
        k = local_ops.target_rank(n, q)
        chunks = self._chunks_for(slot)

        if warm:
            state = self._row_state(slot)
            pivot = _query_jit(state, k)
            # cap from the TRACKED bound (+inf-safe), padded to a stable
            # 128-lane multiple so growing streams reuse the same trace
            bound = int(sketch_rank_bound(state))
        else:
            pivot, bound = self._cold_pivot(chunks, k)
        cap = min(n, _round_up(bound + 2, 128))
        return self._count_extract_resolve(chunks, n, k, pivot, cap)

    @_query
    def windowed(self, name: str, q: float, *, window):
        """EXACT q-quantile of the values inside a trailing window
        (DESIGN.md §11) — bit-identical to sorting the raw window.

        ``window`` is a ``Window`` (``Window(ticks=N)`` for the last N
        ingest ticks, ``Window(values=N)`` for the stream's last N values)
        or a bare int meaning ticks.  On a windowed service this is a WARM
        query: the pivot comes from merging the covering sub-window sketch
        rows (``sketch_merge_rows`` — no sketch-phase sort), the candidate
        cap is the merged sketch's tracked bound plus half the cover
        overcount (sub-windows over-cover the window by at most one
        sub-window width on each side), and count+extract+resolve runs
        only over the ring slices inside the window — widen-and-retry
        keeps exactness unconditional.  On an unwindowed service the pivot
        is rebuilt cold from the window slices (everything is retained, so
        any window is answerable).

        Raises when the window reaches past the retention horizon (unless
        the stream's full history is still resident — then the window
        simply covers everything and the answer equals ``exact()``), and
        when no value falls inside the window."""
        win = _as_window(window)
        with obs.span("service.windowed", request=next(self._requests)):
            slot = self._require(name)
            with obs.span("service.slices"):
                slices, n_w, start = self._window_slices(name, slot, win)
            if n_w == 0:
                raise ValueError(
                    f"stream {name!r} has no values in the window")
            k = local_ops.target_rank(n_w, q)
            with obs.span("service.pivot"):
                pivot, bound = self._window_pivot(slot, k, n_w, start,
                                                  slices)
            cap = min(n_w, _round_up(bound + 2, 128))
            return self._count_extract_resolve(slices, n_w, k, pivot, cap)

    @_locked("r")
    def window_count(self, name: str, *, window) -> int:
        """Values of ``name`` inside the trailing window — the windowed
        analogue of ``stream_count``.  Non-mutating read: 0 for unknown
        streams; a count window reports ``min(N, retained)``."""
        win = _as_window(window)
        slot = self._names.get(name)
        if slot is None:
            return 0
        if win.values is not None:
            return min(int(win.values), self._retained[slot])
        start = self._tick - int(win.ticks)
        return sum(nv for rec, _, nv in self._stream_rows(slot)
                   if rec.tick >= start)

    @_query
    def approx_decayed(self, name: str, q: float, *,
                       halflife: float):
        """Exponential-decay weighted approximate q-quantile: a value
        ingested ``halflife`` ticks ago counts half as much as one ingested
        this tick (weight ``2^(-age/halflife)``, age measured from the
        tick its sub-window opened — decay resolution is the sub-window
        width).  O(window_subs · s) from the retained sub-window sketch
        rows alone, no data pass; requires a windowed service (only it
        maintains sub-window rows)."""
        if self.window_ticks is None:
            raise ValueError("approx_decayed requires a windowed service "
                             "(construct with window_ticks=...)")
        if not halflife > 0:
            raise ValueError(f"halflife must be positive, got {halflife}")
        slot = self._require(name)
        subs = [s for s in self._subs.get(slot, []) if s.n > 0]
        if not subs:
            raise ValueError(f"stream {name!r} has no retained sub-windows")
        now = self._tick - 1
        ages = np.asarray(
            [max(0, now - s.index * self._sub_ticks) for s in subs],
            np.float32)
        rows = jax.tree.map(
            lambda a: a[jnp.asarray([s.slot for s in subs])], self._stacked)
        return _decayed_jit(rows, jnp.asarray(np.exp2(-ages / halflife)),
                            jnp.float32(q))

    @_locked("r")
    def memory_stats(self) -> Dict[str, int]:
        """Resident-footprint counters (host-side bookkeeping only — no
        device work).  ``resident_values`` is the total device-array lane
        count held by the service: ring lanes + slot-table rows × budget.
        The windowed bench asserts it stays flat as history grows — the
        W × budget memory-bound claim."""
        ring_lanes = sum(int(np.prod(rec.data.shape)) for rec in self._ring)
        ring_values = sum(int(rec.n_valid.sum()) for rec in self._ring)
        return {
            "ring_records": len(self._ring),
            "ring_values": ring_values,
            "ring_lanes": ring_lanes,
            "table_rows": self._capacity,
            "live_rows": self._capacity - len(self._free),
            "budget": self.budget,
            "resident_values": ring_lanes + self._capacity * self.budget,
        }

    @_query
    def exact_all(self, qs):
        """EXACT quantiles at every level in ``qs`` for EVERY non-empty
        stream — ONE fused job through the grouped engine instead of a
        per-stream query loop.  Streams become group ids, the slot table
        answers all G·Q pivots in one batched call (no sketch-phase sort —
        this is the warm path for the whole tenant population), and each
        tick record is counted/extracted in ONE segmented pass (one HBM
        stream with ``fused=True``).  Returns ``{name: (Q,) values}``.
        """
        qs = tuple(float(q) for q in qs)
        if not qs:
            raise ValueError("need at least one level")
        active = [(n, s) for n, s in sorted(self._names.items())
                  if self._counts[s] > 0]
        if not active:
            return {}
        for name, s in active:
            self._require_full_history(name, s)
        G, Q = len(active), len(qs)
        slots = np.asarray([s for _, s in active], dtype=np.int32)
        gid_of_slot = {int(s): g for g, s in enumerate(slots)}
        counts = [self._counts[int(s)] for s in slots]

        rows = jax.tree.map(lambda a: a[jnp.asarray(slots)], self._stacked)
        # per-stream counts are host-side registry state, so the float
        # target-rank rule matches exact()'s bit-for-bit
        kmat_host = [[local_ops.target_rank(c, q) for q in qs]
                     for c in counts]
        kmat = jnp.asarray(kmat_host, jnp.int32)
        pivots = _query_batch_jit(rows, kmat)              # (G, Q), one call
        bound = int(jnp.max(_bound_batch_jit(rows)))       # one call
        n_max = max(counts)
        cap = min(n_max, _round_up(bound + 2, 128))

        if self.fused:
            # the Pallas segmented kernel streams each record from HBM once
            # for ALL G*Q pivots — the one-pass-per-shard contract
            pairs = self._ring_pairs(gid_of_slot)
            out = self._segmented_resolve(pairs, kmat, pivots, cap, G, Q,
                                          n_max)
        else:
            # jnp path: the ring is row-per-stream, so each row meets only
            # its own Q pivots (O(S*L*Q), scalable to 10^6 streams where
            # the flat segmented fallback would pay O(S*L * G*Q))
            out = self._rowwise_resolve(gid_of_slot, kmat, pivots, cap,
                                        G, Q, n_max)
        return {name: out[g] for g, (name, _) in enumerate(active)}

    @_query
    def grouped(self, name: str, qs, num_groups: int):
        """EXACT quantiles at every level in ``qs`` for ALL ``num_groups``
        group ids over everything ``ingest_grouped`` buffered — ONE job for
        the whole (G, Q) matrix instead of G*Q, with chunks playing the
        shard role (DESIGN.md §7).  Per-group target ranks follow the
        grouped engine's exact-rational rule (``local_ops.exact_target_rank``
        — group counts are data, so ranks must be computable on device and
        host bit-identically).  Empty groups yield the dtype's high
        sentinel.  Returns the (num_groups, len(qs)) values.

        This is a COLD query: per-group sketches are rebuilt from the
        buffered chunks each time (one (key, value) sort per chunk, ticked
        on the sketch-sort counter).  ``exact_all`` is the warm analogue
        over named streams; the count+extract side is already minimal —
        one fused HBM pass per chunk with ``fused=True``.
        """
        from repro.core.grouped import (grouped_sketch_samples,
                                        query_grouped_sketch)
        st = self._grouped.get(name)
        if st is None or st.n == 0:
            raise ValueError(f"grouped stream {name!r} is empty")
        qs = tuple(float(q) for q in qs)
        G, Q = int(num_groups), len(qs)
        if G < 1 or Q < 1:
            raise ValueError("need num_groups >= 1 and at least one level")

        # ---- action 1: per-chunk segmented sketches, merged -------------
        vals_l, wts_l = [], []
        n_g = jnp.zeros((G,), jnp.int32)
        slack = jnp.zeros((G,), jnp.int32)
        for v, k in zip(st.chunks, st.key_chunks):
            s = grouped_sketch_samples(self.eps, v.shape[0])
            record_sketch_sort()        # segmented sketch sorts the chunk
            va, wa, ca, sa = _grouped_sketch_fn(G, s)(v, k)
            vals_l.append(va)
            wts_l.append(wa)
            n_g = n_g + ca
            slack = slack + sa
        g_vals = jnp.concatenate(vals_l, axis=1)
        g_wts = jnp.concatenate(wts_l, axis=1)
        counts_host = np.asarray(jax.device_get(n_g)).tolist()
        kmat = jnp.asarray(
            [[local_ops.exact_target_rank(c, q) for q in qs]
             for c in counts_host], jnp.int32)
        pivots = query_grouped_sketch(g_vals, g_wts, slack, kmat)

        cap = min(st.n, _round_up(math.ceil(self.eps * st.n) + 2, 128))
        pairs = list(zip(st.chunks, st.key_chunks))
        return self._segmented_resolve(pairs, kmat, pivots, cap, G, Q, st.n)

    # -- internals ----------------------------------------------------------

    def _ring_pairs(self, gid_of_slot: Dict[int, int]):
        """(values, group-keys) flat pairs from the tick ring: each record's
        matrix flattens to one chunk whose keys are the per-row group id
        (-1 on pad lanes and rows of inactive/dropped streams — the
        segmented engine ignores out-of-range ids)."""
        pairs = []
        for rec in self._ring:
            s_tick, length = rec.data.shape
            keys = np.full((s_tick, length), -1, dtype=np.int32)
            hit = False
            for i in range(s_tick):
                gid = gid_of_slot.get(int(rec.slots[i]))
                if gid is not None and rec.n_valid[i]:
                    keys[i, :rec.n_valid[i]] = gid
                    hit = True
            if hit:
                pairs.append((rec.data.reshape(-1),
                              jnp.asarray(keys.reshape(-1))))
        return pairs

    def _finish_resolve(self, counts, belows, aboves, kmat, pivots,
                        cap: int, G: int, Q: int):
        """Shared resolve tail of every segmented query: flatten the (G, Q)
        matrix onto ``engine.phase_resolve`` and report the realized rank
        ``need`` so callers can widen-and-retry."""
        below = jnp.concatenate(
            [b.reshape(G * Q, -1) for b in belows], axis=-1)
        above = jnp.concatenate(
            [a.reshape(G * Q, -1) for a in aboves], axis=-1)
        flat_c = counts.reshape(G * Q, 3)
        out = engine.phase_resolve(pivots.reshape(G * Q),
                                   kmat.reshape(G * Q),
                                   flat_c, below, above, cap)
        lt, eq = flat_c[:, 0], flat_c[:, 1]
        kf = kmat.reshape(G * Q)
        need = int(jnp.max(jnp.maximum(lt - kf + 1, kf - (lt + eq))))
        return out.reshape(G, Q), need

    def _segmented_resolve(self, pairs, kmat, pivots, cap: int,
                           G: int, Q: int, n_limit: int):
        """Actions 2+3 of a segmented job over (values, keys) chunk pairs,
        with the same widen-and-retry guard as ``_count_extract_resolve``
        so exactness never hinges on the sketch bound.  Shared by
        ``grouped`` (keyed batches) and fused ``exact_all`` (tick ring)."""
        counts = jnp.zeros((G, Q, 3), jnp.int32)
        belows, aboves = [], []
        for v, k in pairs:
            cap_c = min(v.shape[0], cap)
            c, b, a = _grouped_chunk_fn(cap_c, self.fused,
                                        self.backend)(v, k, pivots)
            counts = counts + c
            belows.append(b)
            aboves.append(a)
        out, need = self._finish_resolve(counts, belows, aboves, kmat,
                                         pivots, cap, G, Q)
        if need > cap:     # sketch bound violated — widen and rerun
            return self._segmented_resolve(
                pairs, kmat, pivots,
                min(n_limit, _round_up(need + 2, 128)), G, Q, n_limit)
        return out

    def _rowwise_resolve(self, gid_of_slot: Dict[int, int], kmat, pivots,
                         cap: int, G: int, Q: int, n_limit: int):
        """Actions 2+3 of ``exact_all`` straight off the tick ring: one
        row-aligned dispatch per record (each row counts against its own
        stream's Q pivots), results scattered onto the group axis.  Same
        widen-and-retry guard as every other resolve."""
        lo, hi = local_ops._sentinels(self.dtype)
        counts = jnp.zeros((G, Q, 3), jnp.int32)
        belows, aboves = [], []
        for rec in self._ring:
            sel = [i for i, s in enumerate(rec.slots)
                   if int(s) in gid_of_slot and rec.n_valid[i]]
            if not sel:
                continue
            gids = np.asarray([gid_of_slot[int(rec.slots[i])] for i in sel],
                              dtype=np.int32)
            cap_c = min(rec.data.shape[1], cap)
            c, b, a = _row_chunk_fn(cap_c)(
                rec.data[np.asarray(sel)], pivots[jnp.asarray(gids)],
                jnp.asarray(rec.n_valid[sel]))
            # one slot appears at most once per record, so scatter is 1:1
            counts = counts.at[gids].add(c)
            belows.append(jnp.full((G, Q, cap_c), lo,
                                   self.dtype).at[gids].set(b))
            aboves.append(jnp.full((G, Q, cap_c), hi,
                                   self.dtype).at[gids].set(a))
        out, need = self._finish_resolve(counts, belows, aboves, kmat,
                                         pivots, cap, G, Q)
        if need > cap:
            return self._rowwise_resolve(
                gid_of_slot, kmat, pivots,
                min(n_limit, _round_up(need + 2, 128)), G, Q, n_limit)
        return out

    def _window_slices(self, name: str, slot: int, win: Window):
        """The raw window population: device slices of this stream's ring
        rows inside the window, their total count, and the oldest tick the
        window touches (``None`` = the window covers the whole retained
        history — every sub-window row is part of the pivot cover).

        Feasibility: a window reaching past the retention horizon is
        answerable only while the stream's FULL history is still resident
        (then it degenerates to all-history); otherwise values it should
        see are gone and we raise rather than silently narrow the window.
        """
        rows = self._stream_rows(slot)
        total = self._counts[slot]
        retained = self._retained[slot]
        if win.ticks is not None:
            start = self._tick - int(win.ticks)
            horizon = self._tick - (self.window_ticks or self._tick)
            if start < horizon and retained < total:
                raise ValueError(
                    f"window of {win.ticks} ticks reaches past the "
                    f"retention horizon ({self.window_ticks} ticks) for "
                    f"stream {name!r} (retained {retained} of {total} "
                    f"values)")
            slices, n_w = [], 0
            for rec, i, nv in rows:
                if rec.tick >= start:
                    slices.append(rec.data[i, :nv])
                    n_w += nv
            return slices, n_w, (None if n_w == retained else start)
        n_want = int(win.values)
        if n_want >= total and retained == total:
            return [rec.data[i, :nv] for rec, i, nv in rows], total, None
        if n_want > retained:
            raise ValueError(
                f"window of {n_want} values reaches past the retention "
                f"horizon for stream {name!r} (retained {retained} of "
                f"{total} values)")
        slices, remaining, start = [], n_want, None
        for rec, i, nv in reversed(rows):
            take = min(nv, remaining)
            slices.append(rec.data[i, nv - take:nv])
            remaining -= take
            if remaining == 0:
                start = rec.tick
                break
        return list(reversed(slices)), n_want, start

    def _window_pivot(self, slot: int, k: int, n_w: int,
                      start: Optional[int], slices: List[jax.Array]):
        """Action 1 of a windowed query: a pivot near window rank ``k``
        plus a rank-error bound the candidate cap is sized from.

        Warm path (windowed service): merge the sub-window rows whose tick
        span intersects ``[start, now]`` — every window value lives in one
        of them, so the merged sketch covers a SUPERSET of the window with
        overcount ``n_cover - n_w`` (stale mass at the cover's edges).
        Querying the merged sketch at ``k + overcount//2`` centers the
        window rank inside the cover's uncertainty, and the bound widens by
        ``ceil(overcount/2)`` — the cap stays ~|sub-window| + sketch bound,
        and the widen-and-retry fallback in the resolve keeps exactness
        independent of this arithmetic.  Cold path (no sub-window rows:
        unwindowed service, or a stream restored from a pre-window
        snapshot): rebuild a sketch from the window slices themselves."""
        subs = [s for s in self._subs.get(slot, [])
                if s.n > 0 and (start is None
                                or (s.index + 1) * self._sub_ticks > start)]
        if not subs:
            return self._cold_pivot(slices, k)
        n_cover = sum(s.n for s in subs)
        over = max(0, n_cover - n_w)
        rows = jax.tree.map(
            lambda a: a[jnp.asarray([s.slot for s in subs])], self._stacked)
        merged = _merge_subs_jit(rows)
        pivot = _query_jit(merged, k + over // 2)
        bound = sketch_rank_bound(merged)
        with obs.span("read"):
            bound = int(bound)
        return pivot, bound + (over + 1) // 2

    def _cold_pivot(self, chunks: List[jax.Array], k: int):
        """The stateless job's action 1: re-sketch every buffered chunk from
        scratch (one sort per chunk — ticks the sketch-sort counter), merge,
        query.  This is what every query would cost without the resident
        state."""
        cold = sketch_init(self.budget, self.dtype)
        for chunk in chunks:
            record_sketch_sort()
            cold = _update_jit(cold, chunk)
        pivot = _query_jit(cold, k)
        bound = sketch_rank_bound(cold)
        with obs.span("read"):
            bound = int(bound)
        return pivot, bound

    def _count_extract_resolve(self, chunks: List[jax.Array], n: int,
                               k: int, pivot, cap: int):
        """Actions 2+3 over the buffered chunks (chunks == shards of the
        single-process engine).  Retries with a wider cap in the
        (tracked-bound-violating) pathological case so exactness never
        depends on the stream's history."""
        counts, belows, aboves = [], [], []
        with obs.span("service.count_extract"):
            for chunk in chunks:
                cap_c = min(chunk.shape[0], cap)
                c, b, a = _chunk_fn(cap_c, self.fused, self.backend)(
                    chunk, pivot)
                counts.append(c)
                belows.append(b)
                aboves.append(a)
        with obs.span("service.resolve"):
            out, lt, eq = _resolve_fn(cap)(
                jnp.asarray(pivot), jnp.int32(k), tuple(counts),
                tuple(belows), tuple(aboves))
            with obs.span("read"):
                lt, eq = int(lt), int(eq)
            need = max(lt - k + 1, k - (lt + eq))
            if need > cap:     # tracked bound violated — impossible by the
                # invariant, but exactness must not hinge on it: widen and
                # rerun
                return self._count_extract_resolve(
                    chunks, n, k, pivot, min(n, _round_up(need + 2, 128)))
        return out

    # -- snapshot / restore -------------------------------------------------

    @_locked("w")
    def snapshot(self):
        """Capture the full service state as ``(leaves, extra)``:

          * ``leaves`` — a flat list of arrays (the stacked ``SketchState``
            leaves, then per tick record its data/slots/n_valid, then each
            grouped stream's value/key chunks), the pytree a checkpoint
            round-trips leaf-by-leaf, and
          * ``extra`` — JSON-able metadata (registry, counts, config, ring
            and grouped-chunk layout) that rebuilds the structure.

        ``checkpoint.save_service_snapshot`` persists this pair;
        ``from_snapshot`` inverts it bit-exactly — a restored service's
        warm ``exact()`` answers match without replaying any history.
        Staged host batches are committed first, so a snapshot never
        silently drops in-flight values."""
        if self._staged:
            self.commit_staged()
        leaves: List = []
        if self._stacked is not None:
            leaves.extend([self._stacked.values, self._stacked.weights,
                           self._stacked.n, self._stacked.slack])
        for rec in self._ring:
            leaves.extend([rec.data, rec.slots, rec.n_valid])
        grouped_meta = {}
        for name in sorted(self._grouped):
            gs = self._grouped[name]
            for v, k in zip(gs.chunks, gs.key_chunks):
                leaves.extend([v, k])
            grouped_meta[name] = {"chunks": len(gs.chunks), "n": gs.n}
        extra = {
            # format 2 adds the window-state keys below; from_snapshot
            # still reads format-1 snapshots (missing keys default to the
            # unwindowed behavior they were saved under)
            "format": 2,
            "eps": self.eps,
            "budget": self.budget,
            "dtype": self.dtype.name,
            "fused": self.fused,
            "check_nans": self.check_nans,
            "has_table": self._stacked is not None,
            "capacity": self._capacity,
            "names": dict(self._names),
            "free": list(self._free),
            "dirty": sorted(self._dirty),
            "counts": list(self._counts),
            "num_ticks": len(self._ring),
            "grouped": grouped_meta,
            "window_ticks": self.window_ticks,
            "window_subs": self.window_subs,
            "tick": self._tick,
            "ring_ticks": [rec.tick for rec in self._ring],
            "retained": list(self._retained),
            "subs": {str(slot): [[s.slot, s.index, s.n] for s in subs]
                     for slot, subs in self._subs.items()},
        }
        return leaves, extra

    @classmethod
    def from_snapshot(cls, leaves, extra, *, fused: Optional[bool] = None,
                      backend=None) -> "QuantileService":
        """Rebuild a service from ``snapshot()`` output.  ``fused`` /
        ``backend`` override the saved execution flags (they steer data
        movement only — answers are exactness-invariant), so a restore may
        land on different hardware than the save."""
        svc = cls(eps=extra["eps"], budget=extra["budget"],
                  dtype=extra["dtype"],
                  fused=extra["fused"] if fused is None else fused,
                  check_nans=extra["check_nans"], backend=backend,
                  window_ticks=extra.get("window_ticks"),
                  window_subs=extra.get("window_subs", 8))
        it = iter(leaves)
        if extra["has_table"]:
            svc._stacked = SketchState(values=jnp.asarray(next(it)),
                                       weights=jnp.asarray(next(it)),
                                       n=jnp.asarray(next(it)),
                                       slack=jnp.asarray(next(it)))
        svc._capacity = int(extra["capacity"])
        svc._names = {str(k): int(v) for k, v in extra["names"].items()}
        svc._free = [int(s) for s in extra["free"]]
        svc._dirty = {int(s) for s in extra["dirty"]}
        svc._counts = [int(c) for c in extra["counts"]]
        num_ticks = int(extra["num_ticks"])
        # format-1 snapshots carry no window state: the ring orders ticks
        # 0..T-1, nothing was ever retained-limited, no sub-window rows
        ring_ticks = [int(t) for t in
                      extra.get("ring_ticks", range(num_ticks))]
        svc._tick = int(extra.get("tick", num_ticks))
        svc._retained = [int(c) for c in
                         extra.get("retained", extra["counts"])]
        svc._subs = {
            int(slot): [_SubWindow(slot=int(s), index=int(i), n=int(n))
                        for s, i, n in subs]
            for slot, subs in extra.get("subs", {}).items()}
        for t in ring_ticks:
            data = jnp.asarray(next(it))
            slots = np.asarray(next(it)).astype(np.int32)
            n_valid = np.asarray(next(it)).astype(np.int32)
            svc._ring.append(_TickRecord(data=data, slots=slots,
                                         n_valid=n_valid, tick=t))
        for name, meta in extra["grouped"].items():
            gs = _GroupedStream([], [], int(meta["n"]))
            for _ in range(int(meta["chunks"])):
                gs.chunks.append(jnp.asarray(next(it)))
                gs.key_chunks.append(jnp.asarray(next(it)))
            svc._grouped[name] = gs
        return svc


class StreamingCalibrator:
    """int8 activation calibration that maintains running |activation|
    sketches across decode steps (DESIGN.md §6).

    The pre-streaming flow re-ran GK Select's full 3-action job on a
    re-concatenated activation history every time a scale was needed; this
    folds each step's activations into persistent per-tensor streams and
    answers scales either approximately in O(s) (``approx_scale``) or
    exactly with a WARM 2-action query (``scale``) — no sketch-phase sort
    ever happens at scale-query time.  ``observe_many`` batches ALL of a
    decode step's tensors into ONE device tick (the slot-table ingest), so
    per-step calibration overhead stays constant in the tensor count.

    ``ingest_threads`` > 0 opts into the threaded ingest pipeline
    (``ingest_pool.IngestPool``): ``observe_many`` becomes a queue hand-
    off so calibration stops stealing decode-loop time, ``scale()``
    flushes first (still exact up to now), and ``approx_scale`` reads
    the folded state without a barrier — stale by at most the pool's
    ``lag_values()``.  ``None`` reads ``REPRO_INGEST_THREADS`` (default
    0 = synchronous).  Call ``close()`` (or use as a context manager)
    when threaded."""

    def __init__(self, q: float = 0.999, *, eps: float = 0.01,
                 fused: bool = False, backend=None,
                 ingest_threads: Optional[int] = None):
        self.q = q
        self.service = QuantileService(eps=eps, fused=fused, backend=backend)
        if ingest_threads is None:
            from .ingest_pool import default_ingest_workers
            ingest_threads = (default_ingest_workers()
                              if "REPRO_INGEST_THREADS" in os.environ else 0)
        self.pool = None
        if ingest_threads:
            from .ingest_pool import IngestPool
            self.pool = IngestPool(self.service, workers=ingest_threads)

    def observe(self, name: str, activations) -> None:
        self.observe_many({name: activations})

    def observe_many(self, named: Dict[str, jax.typing.ArrayLike]) -> None:
        """Fold one decode step's activations — every tensor at once — into
        the per-tensor streams: ONE batched device call regardless of how
        many tensors the step observed (|x| in f32 applied on device).
        Threaded mode queues the tensors instead (|x| applied host-side
        in the worker thread, bit-identical) and returns immediately."""
        if not named:
            return
        if self.pool is not None:
            for n in sorted(named):
                self.pool.submit(n, named[n], transform="abs_f32")
            return
        names = sorted(named)
        self.service.ingest_batch(names, [named[n] for n in names],
                                  transform="abs_f32")

    def scale(self, name: str):
        """Exact symmetric int8 scale (the paper's reproducibility case):
        warm GK Select over everything observed so far.  Threaded mode
        flushes the pool first, so 'so far' includes every queued step."""
        self.flush()
        return self.service.exact(name, self.q)

    def approx_scale(self, name: str):
        """O(s) scale estimate from the sketch alone (rank error within
        ``service.rank_bound(name)``) — for per-step monitoring.  In
        threaded mode this does NOT flush: it reads the folded state,
        stale by at most ``pool.lag_values()`` queued values."""
        return self.service.approx(name, self.q)

    def observed(self, name: str) -> int:
        """Values folded for ``name`` (flushes first in threaded mode so
        the count covers every queued observation)."""
        self.flush()
        return self.service.stream_count(name)

    def flush(self) -> None:
        """Barrier for threaded mode (no-op when synchronous)."""
        if self.pool is not None:
            self.pool.flush()

    def close(self) -> None:
        """Stop the ingest pool, folding everything queued (no-op when
        synchronous).  Idempotent."""
        if self.pool is not None:
            self.pool.close()

    def __enter__(self) -> "StreamingCalibrator":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        try:
            self.close()
        except BaseException:
            if exc_type is None:
                raise
