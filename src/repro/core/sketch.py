"""Quantile sketches: the approximate-summary layer that GK Select pivots on.

Two families, per DESIGN.md §2:

* ``GKSketch`` — faithful Greenwald–Khanna summary with Spark's head-buffer
  batching (``QuantileSummaries`` semantics: append → flush (sort+merge) →
  compress at ``2εn``).  Array-based, host-side (numpy): classical GK's
  pointer-chased tuple list is inherently sequential and does not map to the
  MXU/VPU; it is kept for paper-faithful benchmarks, invariant tests and the
  Modified-Spark-GK (geometric buffer) analysis of §IV-E3.

* ``sample sketch`` — the TPU-native mergeable summary (sort + stride-m
  rank-tagged subsample; the paper's own §IV-D "every fifth percentile"
  construction).  Pure jnp, fully vectorizable, identical worst-case rank
  guarantee ``|rank(query(k)) - k| <= eps * n``.

* ``SketchState`` — the *streaming* form of the sample sketch (DESIGN.md §6):
  a jit-compatible pytree holding a fixed-budget weighted summary that is
  maintained incrementally as batches arrive (``sketch_init`` /
  ``sketch_update`` / ``sketch_merge``).  Each update sorts only the new
  batch and tile-merges it into the resident summary, so GK Select's most
  expensive action — the per-shard full sort — is paid once per *batch* at
  ingest time instead of once per *query*.

All are interchangeable as GK Select's pivot oracle.
"""
from __future__ import annotations

import dataclasses
import math
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs


# ---------------------------------------------------------------------------
# sketch-phase sort accounting: the ``sketch.sorts`` counter of
# ``repro.obs``, read by the tests (a warm exact query ticks it ZERO times).
# Ticked at the DISPATCH layer only — QuantileService.ingest / the cold
# rebuild — never inside traced code, so the count is exact per eager call
# (a trace-time tick would double-count the first call of each shape).
# ---------------------------------------------------------------------------

SKETCH_SORTS = "sketch.sorts"


def reset_sketch_sorts() -> None:
    """Zero the sketch-phase sort counter."""
    obs.reset(SKETCH_SORTS)


def sketch_sorts() -> int:
    """Sketch-construction sorts dispatched since the last reset."""
    return obs.counters().get(SKETCH_SORTS, 0)


def record_sketch_sort(n: int = 1) -> None:
    """Tick the sketch-phase sort counter (called by every code path that
    sorts raw data to build or rebuild a sketch).  Thread-safe."""
    obs.count(SKETCH_SORTS, n)


# ---------------------------------------------------------------------------
# TPU-native sample sketch (pure jnp; used inside jit / shard_map)
# ---------------------------------------------------------------------------


def sample_sketch_params(n_total: int, n_local: int, eps: float, num_shards: int
                         ) -> Tuple[int, int]:
    """Static (stride m, samples-per-shard s) for a target rank error eps*n.

    m is chosen so that the summed per-shard uncertainty P*m stays <= eps*n
    (see DESIGN.md §2 for the bound); s = ceil(n_local / m) samples cover the
    whole shard including a final partial group.
    """
    if not 0.0 < eps < 1.0:
        raise ValueError(f"eps must be in (0,1), got {eps}")
    m = max(1, int(math.floor(eps * n_total / max(1, num_shards))))
    m = min(m, n_local)
    s = int(math.ceil(n_local / m))
    return m, s


def local_sample_sketch(x: jax.Array, m: int, s: int) -> Tuple[jax.Array, jax.Array]:
    """Sorted stride-m summary of one shard.

    Returns (values (s,), weights (s,)): sample t is the element of local rank
    min((t+1)*m, n_i); its weight is the number of elements it covers (the gap
    to the previous sample).  Clamped duplicates at the tail get weight 0 so
    the shapes stay static.
    """
    n_i = x.shape[0]
    xs = jnp.sort(x)
    idx = jnp.minimum(jnp.arange(1, s + 1, dtype=jnp.int32) * m - 1, n_i - 1)
    vals = xs[idx]
    prev = jnp.concatenate([jnp.full((1,), -1, jnp.int32), idx[:-1]])
    weights = (idx - prev).astype(jnp.int32)
    return vals, weights


def query_merged_sketch(values: jax.Array, weights: jax.Array, k: jax.Array,
                        num_shards: int, m: int) -> jax.Array:
    """Query the concatenated per-shard summaries for the rank-k pivot.

    values/weights are flat (P*s,).  rank(v_t) in [cum_t, cum_t + P*m], so the
    midpoint estimate cum_t + P*m/2 is within eps*n of the true rank of the
    chosen sample (DESIGN.md §2).

    The argmin runs in int32: the old float32 path could not represent ranks
    above 2^24, so at n ~ 1e9 the chosen pivot's rank error could exceed the
    eps*n guarantee and blow the candidate cap.  int32 is exact to 2^31
    (single-job counts are pinned below that anyway — see local_ops.count3).
    """
    order = jnp.argsort(values)
    v = values[order]
    w = weights[order]
    cum = jnp.cumsum(w)                                   # int32: exact ranks
    est = cum + jnp.int32(num_shards * m // 2)
    ki = jnp.asarray(k).astype(jnp.int32)
    t = jnp.argmin(jnp.abs(est - ki))
    return v[t]


# ---------------------------------------------------------------------------
# SketchState: incrementally-maintained device-resident sample sketch
# (mergeable-summary form of the stride-m sketch; DESIGN.md §6)
# ---------------------------------------------------------------------------


class SketchState(NamedTuple):
    """Fixed-budget weighted quantile summary, maintained incrementally.

    A jit-compatible pytree (NamedTuple of arrays — flows through jit, vmap,
    shard_map and device_put unchanged):

      values  (s,)  sorted ascending; unused lanes carry the dtype's high
                    sentinel with weight 0 so shapes stay static
      weights (s,)  int32 mass per sample; cumsum(weights) estimates each
                    sample's rank in the ingested multiset
      n       ()    int32 true ingested count (sum of weights)
      slack   ()    int32 upper bound on how far any sample's cumulative
                    weight can UNDERcount its true rank (interleave loss)

    Invariant (DESIGN.md §6): for every sample, ``cum_i <= rank(v_i) <=
    cum_i + slack``; gaps between adjacent samples are bounded by
    ``max(weights)``.  Queries therefore have rank error at most
    ``slack/2 + max(weights)`` (``sketch_rank_bound``), and the engine sizes
    its candidate cap from that *tracked* bound — streaming can degrade
    precision (bigger cap, more bandwidth) but never exactness.

    ``slack`` composes by MAX, not sum: every sample's cum is fixed at its
    own ingest/merge and later tile-merges add exact counts to it, so the
    undercount of the whole summary is the worst single ingest, not the sum
    over the stream's history.
    """

    values: jax.Array
    weights: jax.Array
    n: jax.Array
    slack: jax.Array


def sketch_budget(eps: float) -> int:
    """Static sample budget s for a streamed rank-error target of eps*n.

    16/eps lanes keep the steady-state compression stride near eps*n/16, so
    the tracked query bound (slack/2 + max gap) stays well inside eps*n even
    after many update/compress cycles.
    """
    if not 0.0 < eps < 1.0:
        raise ValueError(f"eps must be in (0,1), got {eps}")
    return int(min(1 << 16, max(64, math.ceil(16.0 / eps))))


def sketch_init(budget: int, dtype=jnp.float32) -> SketchState:
    """Empty stream summary with a static ``budget``-lane budget."""
    if jnp.issubdtype(jnp.dtype(dtype), jnp.floating):
        hi = jnp.array(jnp.inf, dtype)
    else:
        hi = jnp.array(jnp.iinfo(dtype).max, dtype)
    return SketchState(values=jnp.full((budget,), hi, dtype),
                       weights=jnp.zeros((budget,), jnp.int32),
                       n=jnp.int32(0), slack=jnp.int32(0))


def _batch_run(batch: jax.Array, budget: int):
    """Sort one incoming batch into a (<=budget,)-sample weighted run with
    EXACT cumulative ranks (stride m_b = ceil(n_b/budget); m_b = 1 keeps
    full resolution).  Returns (values, weights, m_b)."""
    n_b = batch.shape[0]
    m_b = max(1, -(-n_b // budget))
    s_b = min(n_b, budget)
    vals, wts = local_sample_sketch(batch, m_b, s_b)
    return vals, wts, m_b


def _compress(values: jax.Array, weights: jax.Array, n, budget: int):
    """Re-compress a merged weighted run to the static ``budget``.

    Kept samples are a SUBSET of the input chosen at evenly-spaced rank
    targets; dropped mass folds into the next kept sample, so kept
    cumulative weights are exactly the input's — compression adds zero rank
    error, it only widens gaps (which ``sketch_rank_bound`` reads off the
    weights).  Targets t_j = j*(n//s) + min(j, n%s) avoid the j*n overflow
    while still summing the remainder in; duplicate selections become
    weight-0 lanes, and for n <= budget every element is kept exactly.
    """
    cum = jnp.cumsum(weights)
    j = jnp.arange(1, budget + 1, dtype=jnp.int32)
    q_, r_ = n // budget, n % budget
    targets = j * q_ + jnp.minimum(j, r_)
    idx = jnp.searchsorted(cum, targets, side="left")
    idx = jnp.minimum(idx, values.shape[0] - 1)
    kept_cum = cum[idx]
    new_w = jnp.diff(kept_cum, prepend=jnp.int32(0))
    return values[idx], new_w.astype(jnp.int32)


def sketch_update(state: SketchState, batch: jax.Array) -> SketchState:
    """Fold one batch into the resident summary: sort the BATCH only, tile-
    merge the two sorted runs, re-compress to the static budget.

    Pure jnp with static shapes (state budget + batch length fix the trace),
    so the whole update jits and the state stays device-resident.  Per-batch
    cost is O(n_b log n_b + s log s) — the full-data sort GK Select would
    otherwise pay per query is never rebuilt.
    """
    budget = state.values.shape[0]
    batch = batch.reshape(-1).astype(state.values.dtype)
    b_vals, b_wts, m_b = _batch_run(batch, budget)

    # tile-merge of the two sorted runs (argsort of 2s lanes, not a data sort)
    v = jnp.concatenate([state.values, b_vals])
    w = jnp.concatenate([state.weights, b_wts])
    order = jnp.argsort(v)
    v, w = v[order], w[order]

    n_new = state.n + jnp.int32(batch.shape[0])
    v, w = _compress(v, w, n_new, budget)

    # Undercount bound: resident samples miss at most the batch's stride of
    # new mass (m_b - 1); batch samples miss at most the resident summary's
    # widest gap.  MAX-composition across the two sides — see the
    # SketchState docstring.
    gap = jnp.max(state.weights)
    new_slack = jnp.where(
        state.n > 0,
        jnp.maximum(state.slack + jnp.int32(m_b - 1), gap),
        jnp.int32(m_b - 1))
    return SketchState(values=v, weights=w, n=n_new, slack=new_slack)


def _batch_run_padded(batch: jax.Array, n_valid, budget: int):
    """``_batch_run`` with a TRACED valid count: lanes ``>= n_valid`` must
    hold the dtype's high sentinel (they sort last and receive weight 0).

    Emits a fixed ``budget`` lanes instead of the static ``min(n_b, budget)``
    so every stream of a stacked batch shares one shape.  The extra lanes
    duplicate the last valid sample with weight 0, which ``_compress``'s
    first-to-reach-target selection provably never picks — the compressed
    result is bit-identical to the static ``_batch_run`` path for the same
    valid prefix (pinned by tests/test_service_stacked.py).
    """
    xs = jnp.sort(batch)
    nv = jnp.asarray(n_valid, jnp.int32)
    m_b = jnp.maximum(jnp.int32(1), -(-nv // jnp.int32(budget)))
    t = jnp.arange(1, budget + 1, dtype=jnp.int32)
    r = jnp.minimum(t * m_b, nv)
    idx = jnp.clip(jnp.maximum(r, 1) - 1, 0, batch.shape[0] - 1)
    vals = xs[idx]
    wts = jnp.diff(r, prepend=jnp.int32(0))
    return vals, wts, m_b


def sketch_update_padded(state: SketchState, batch: jax.Array,
                         n_valid) -> SketchState:
    """``sketch_update`` for a sentinel-padded batch with a traced valid
    count — the vmap-compatible form batched multi-tenant ingest runs on.

    ``batch`` lanes at index ``>= n_valid`` must carry the dtype's high
    sentinel.  For ``n_valid == batch.size`` the result is bit-identical to
    ``sketch_update``; for ``n_valid == 0`` the state is returned unchanged.
    All shapes are static (budget + padded length fix the trace), so
    ``jax.vmap`` lifts this directly to a stacked ``SketchState``.
    """
    budget = state.values.shape[0]
    batch = batch.reshape(-1).astype(state.values.dtype)
    nv = jnp.asarray(n_valid, jnp.int32)
    b_vals, b_wts, m_b = _batch_run_padded(batch, nv, budget)

    v = jnp.concatenate([state.values, b_vals])
    w = jnp.concatenate([state.weights, b_wts])
    order = jnp.argsort(v, stable=True)
    v, w = v[order], w[order]

    n_new = state.n + nv
    v, w = _compress(v, w, n_new, budget)

    gap = jnp.max(state.weights)
    new_slack = jnp.where(
        state.n > 0,
        jnp.maximum(state.slack + (m_b - 1), gap),
        m_b - 1)
    new = SketchState(values=v, weights=w, n=n_new, slack=new_slack)
    # empty batch: the update above would re-compress (a no-op numerically,
    # but lane layout could shift) — return the state bit-unchanged instead
    return jax.tree.map(lambda a, b_: jnp.where(nv > 0, a, b_), new, state)


def sketch_update_batch(states: SketchState, batches: jax.Array,
                        n_valid: jax.Array) -> SketchState:
    """Advance S streams in ONE traced op: ``states`` is a stacked
    ``SketchState`` (leading axis S on every leaf), ``batches`` an (S, L)
    sentinel-padded matrix, ``n_valid`` the (S,) true lengths.  Row i is
    bit-identical to ``sketch_update(states[i], batches[i, :n_valid[i]])``.
    This is the storage-model core of multi-tenant ingest: one device
    dispatch per tick regardless of S (DESIGN.md §9)."""
    return jax.vmap(sketch_update_padded)(states, batches, n_valid)


def sketch_merge_batch(a: SketchState, b: SketchState) -> SketchState:
    """Row-wise ``sketch_merge`` of two stacked summaries (same leading axis
    and budget) — the one-call fold of a worker-local slot table into the
    shared one (Quancurrent-style merge; DESIGN.md §9)."""
    if a.values.shape != b.values.shape:
        raise ValueError(f"stacked sketch shapes differ: {a.values.shape} "
                         f"vs {b.values.shape}")
    return jax.vmap(sketch_merge)(a, b)


def sketch_merge_many(states) -> SketchState:
    """Tree-reduce merge of ANY number of equally-shaped stacked summaries in
    one traced expression — the fold scheduler's multi-buffer primitive: K
    worker buffers land in the shared table through ONE jitted dispatch
    instead of K pairwise ``sketch_merge_batch`` calls (DESIGN.md §10).

    Merge composes the §6 slack bound in every association order (each
    pairwise merge takes max(own slack + other's widest gap)), so the reduce
    shape only affects the *approximate* summary, never exactness.  The tree
    keeps the bound tight: the worst-case slack grows with the reduce depth
    ceil(log2 K), not with K as a sequential foldl would.
    """
    items = list(states)
    if not items:
        raise ValueError("need at least one SketchState to merge")
    while len(items) > 1:
        nxt = [sketch_merge_batch(items[i], items[i + 1])
               for i in range(0, len(items) - 1, 2)]
        if len(items) % 2:
            nxt.append(items[-1])
        items = nxt
    return items[0]


def sketch_stack(states) -> SketchState:
    """Stack per-stream ``SketchState``s into one slot-table pytree (leading
    axis = len(states) on every leaf).  All inputs must share one budget."""
    states = list(states)
    if not states:
        raise ValueError("need at least one SketchState to stack")
    return jax.tree.map(lambda *xs: jnp.stack(xs), *states)


def sketch_unstack(stacked: SketchState):
    """Split a stacked ``SketchState`` back into per-stream states."""
    count = stacked.values.shape[0]
    return [jax.tree.map(lambda a: a[i], stacked) for i in range(count)]


def sketch_init_stack(count: int, budget: int, dtype=jnp.float32) -> SketchState:
    """``count`` empty stream summaries as one stacked pytree."""
    one = sketch_init(budget, dtype)
    return jax.tree.map(
        lambda a: jnp.broadcast_to(a, (count,) + a.shape), one)


def sketch_query_rank_batch(stacked: SketchState, ks: jax.Array) -> jax.Array:
    """Per-stream rank queries over a stacked summary: ``ks`` is (S, Q)
    target ranks; returns the (S, Q) pivot values — one traced op for the
    whole slot table (the warm multi-tenant pivot source)."""
    ks = jnp.asarray(ks, jnp.int32)
    return jax.vmap(lambda st, kvec: jax.vmap(
        lambda k: sketch_query_rank(st, k))(kvec))(stacked, ks)


def sketch_rank_bound_batch(stacked: SketchState) -> jax.Array:
    """(S,) tracked per-stream query rank-error bounds (``sketch_rank_bound``
    row-wise)."""
    return (stacked.slack // 2 + jnp.max(stacked.weights, axis=-1)
            + jnp.int32(2))


def sketch_merge_rows(stacked: SketchState) -> SketchState:
    """Merge the K rows of one stacked summary into a SINGLE summary through
    the ``sketch_merge_many`` pairwise tree (slack depth ceil(log2 K), not K
    — DESIGN.md §6/§11).  K is static (the leading axis), so the whole merge
    is one traced expression — the windowed service's merge-on-query
    primitive: a stream's retained sub-window rows are gathered from the
    slot table and merged per query instead of maintaining every possible
    window alignment eagerly."""
    k = stacked.values.shape[0]
    parts = [jax.tree.map(lambda a, i=i: a[i:i + 1], stacked)
             for i in range(k)]
    return jax.tree.map(lambda a: a[0], sketch_merge_many(parts))


def sketch_query_decayed(stacked: SketchState, factors: jax.Array,
                         q) -> jax.Array:
    """Exponential-decay weighted approximate quantile over K stacked
    sub-window summaries (DESIGN.md §11).

    ``factors`` is a (K,) float array of per-row decay multipliers (the
    windowed service passes ``2^(-age/halflife)`` with age in ticks since
    the sub-window opened).  Every sample's integer weight is scaled by its
    row's factor, all lanes are ranked together, and the first sample whose
    decayed cumulative weight reaches ``q * total`` is returned — i.e. the
    q-quantile of the distribution in which a value ingested ``halflife``
    ticks ago counts half as much as one ingested now.  Decay resolution is
    the sub-window width: values inside one sub-window share a factor.

    Weight-0 lanes (sentinel padding / compression duplicates) can never be
    selected.  This is an approximate query by construction — decayed rank
    error stays within the undecayed ``sketch_rank_bound`` of each row
    scaled by its factor — there is no exact counterpart because the raw
    ring stores no per-value timestamps finer than the tick."""
    w = stacked.weights.astype(jnp.float32) \
        * jnp.asarray(factors, jnp.float32)[:, None]
    v = stacked.values.reshape(-1)
    w = w.reshape(-1)
    order = jnp.argsort(v)
    v, w = v[order], w[order]
    cum = jnp.cumsum(w)
    target = jnp.asarray(q, jnp.float32) * cum[-1]
    # cum only increases at positive-weight lanes, so the first lane where
    # it reaches the target always carries weight (guard anyway: a
    # zero-total pathological input must not surface a sentinel)
    hit = (cum >= target) & (w > 0)
    pos = jnp.where(w > 0, jnp.arange(v.shape[0]), -1)
    return v[jnp.where(jnp.any(hit), jnp.argmax(hit), jnp.argmax(pos))]


def sketch_merge(a: SketchState, b: SketchState) -> SketchState:
    """Merge two stream summaries (mergeable-summaries property): concat the
    sorted runs, re-compress to a's budget.  Each side's samples can miss at
    most the OTHER side's widest gap, once — slacks compose by max(own +
    other's gap), not by sum."""
    if a.values.shape != b.values.shape:
        raise ValueError(f"sketch budgets differ: {a.values.shape} vs "
                         f"{b.values.shape}")
    budget = a.values.shape[0]
    v = jnp.concatenate([a.values, b.values])
    w = jnp.concatenate([a.weights, b.weights])
    order = jnp.argsort(v)
    v, w = v[order], w[order]
    n_new = a.n + b.n
    v, w = _compress(v, w, n_new, budget)
    gap_a = jnp.max(a.weights)
    gap_b = jnp.max(b.weights)
    slack = jnp.maximum(
        jnp.where(b.n > 0, a.slack + gap_b, a.slack),
        jnp.where(a.n > 0, b.slack + gap_a, b.slack))
    return SketchState(values=v, weights=w, n=n_new, slack=slack)


def sketch_query_rank(state: SketchState, k) -> jax.Array:
    """Value whose rank is within ``sketch_rank_bound(state)`` of ``k``
    (1-based), O(s).  Integer arithmetic throughout — exact to 2^31."""
    cum = jnp.cumsum(state.weights)
    est = cum + state.slack // 2
    ki = jnp.asarray(k).astype(jnp.int32)
    # weight-0 lanes are sentinel padding / compression duplicates: never
    # let one win the argmin (a +inf sentinel pivot would poison GK Select)
    err = jnp.where(state.weights > 0, jnp.abs(est - ki),
                    jnp.int32(jnp.iinfo(jnp.int32).max))
    return state.values[jnp.argmin(err)]


def sketch_rank_bound(state: SketchState) -> jax.Array:
    """Tracked upper bound on ``sketch_query_rank``'s rank error: undercount
    midpoint (slack/2) + gap resolution (max weight) + rounding.  The warm
    engine sizes candidate caps from this, keeping exactness unconditional
    no matter how the stream arrived."""
    return state.slack // 2 + jnp.max(state.weights) + jnp.int32(2)


# ---------------------------------------------------------------------------
# Faithful GK sketch (host-side numpy; Spark QuantileSummaries semantics)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class GKSketch:
    """Greenwald–Khanna summary with Spark's head-buffer batching.

    Tuples (v_i, g_i, delta_i) maintain the invariant  g_i + delta_i <= 2*eps*n
    (Eq. 1 of the paper), guaranteeing query rank error <= eps*n.

    ``head_size`` / ``compress_threshold`` follow Spark defaults (50_000 /
    10_000).  ``adaptive_head=True`` switches to the paper's Modified Spark GK
    Sketch (§IV-E3): after each flush, B <- ceil(alpha * |S|), restoring the
    classical O(loglog) per-insert asymptotics.
    """

    eps: float
    head_size: int = 50_000
    compress_threshold: int = 10_000
    adaptive_head: bool = False
    alpha: float = 1.5

    def __post_init__(self):
        self.v = np.empty(0, dtype=np.float64)
        self.g = np.empty(0, dtype=np.int64)
        self.delta = np.empty(0, dtype=np.int64)
        self.n = 0
        self._buf: list = []
        self._B = 8 if self.adaptive_head else self.head_size
        self.flush_count = 0
        self.compress_count = 0

    # -- ingest ------------------------------------------------------------

    def insert(self, x: float) -> None:
        self._buf.append(float(x))
        if len(self._buf) >= self._B:
            self.flush()

    def insert_batch(self, xs) -> None:
        xs = np.asarray(xs, dtype=np.float64).ravel()
        pos = 0
        while pos < xs.size:
            take = self._B - len(self._buf)
            self._buf.extend(xs[pos:pos + take].tolist())
            pos += take
            if len(self._buf) >= self._B:
                self.flush()

    def flush(self) -> None:
        """Sort the head buffer and merge it into the tuple list (Spark's
        insertHeadSampled), then compress if above the threshold."""
        if not self._buf:
            return
        self.flush_count += 1
        batch = np.sort(np.asarray(self._buf, dtype=np.float64))
        self._buf = []
        new_n = self.n + batch.size
        # Inserted tuples: g=1, delta = floor(2*eps*n)-1 interior, 0 at extremes.
        ins_delta = max(0, int(math.floor(2 * self.eps * new_n)) - 1)
        pos = np.searchsorted(self.v, batch, side="right")
        total = self.v.size + batch.size
        v = np.empty(total)
        g = np.empty(total, dtype=np.int64)
        d = np.empty(total, dtype=np.int64)
        # Stable positions of the new elements in the merged array.
        new_idx = pos + np.arange(batch.size)
        mask = np.zeros(total, dtype=bool)
        mask[new_idx] = True
        v[mask] = batch
        g[mask] = 1
        d[mask] = ins_delta
        v[~mask] = self.v
        g[~mask] = self.g
        d[~mask] = self.delta
        # Extremes carry delta 0 (exact min/max).
        if total:
            d[0] = 0
            d[-1] = 0
        self.v, self.g, self.delta, self.n = v, g, d, new_n
        if self.size > self.compress_threshold or self.adaptive_head:
            self.compress()
        if self.adaptive_head:
            # Modified Spark GK (§IV-E3): B tracks the *compressed* size
            self._B = max(8, int(math.ceil(self.alpha * max(1, self.size))))

    def compress(self) -> None:
        """Greedy right-to-left merge of tuples whose combined gap+slack stays
        under 2*eps*n (Spark compressImmut). Keeps the extremes."""
        if self.size <= 2:
            return
        self.compress_count += 1
        thresh = math.floor(2 * self.eps * self.n)
        v, g, d = self.v, self.g, self.delta
        keep = np.ones(v.size, dtype=bool)
        gg = g.copy()
        nxt = v.size - 1  # index of the next *kept* tuple (tail always kept)
        for i in range(v.size - 2, 0, -1):
            if gg[i] + gg[nxt] + d[nxt] < thresh:
                gg[nxt] += gg[i]       # fold i's mass into its kept successor
                keep[i] = False
            else:
                nxt = i
        self.v, self.g, self.delta = v[keep], gg[keep], d[keep]

    # -- query -------------------------------------------------------------

    @property
    def size(self) -> int:
        return int(self.v.size)

    def rank_bounds(self) -> Tuple[np.ndarray, np.ndarray]:
        rmin = np.cumsum(self.g)
        rmax = rmin + self.delta
        return rmin, rmax

    def query_rank(self, k: int) -> float:
        """Value whose rank is within eps*n of k (k is 1-based)."""
        if self._buf:
            self.flush()
        if self.size == 0:
            raise ValueError("empty sketch")
        rmin, rmax = self.rank_bounds()
        err = np.maximum(k - rmin, rmax - k)
        return float(self.v[int(np.argmin(err))])

    def query(self, q: float) -> float:
        if self._buf:
            self.flush()
        k = min(self.n, max(1, int(math.ceil(q * self.n))))
        return self.query_rank(k)

    # -- merge (mergeable-summaries rank-bound merge) ----------------------

    def merge(self, other: "GKSketch") -> "GKSketch":
        """Merge two summaries; rank errors add (<= eps*(n_a+n_b) when both
        are eps-summaries). Rank bounds of each tuple against the other sketch
        are derived by searchsorted (Agarwal et al.'s mergeable-summaries
        merge, which is what Spark's QuantileSummaries.merge approximates).

        The sketches need not share ``eps``: the merged summary tracks
        max(eps_a, eps_b), the tightest bound the merge can still honour —
        silently keeping the smaller eps would claim a rank guarantee the
        coarser input never provided."""
        if self._buf:
            self.flush()
        if other._buf:
            other.flush()
        eps = max(self.eps, other.eps)
        if other.size == 0:
            if eps == self.eps:
                return self
            # never mutate the receiver: a widened-eps result is a new sketch
            out = GKSketch(eps, self.head_size, self.compress_threshold,
                           self.adaptive_head, self.alpha)
            out.v, out.g, out.delta, out.n = (self.v.copy(), self.g.copy(),
                                              self.delta.copy(), self.n)
            return out
        if self.size == 0:
            out = GKSketch(eps, self.head_size, self.compress_threshold,
                           self.adaptive_head, self.alpha)
            out.v, out.g, out.delta, out.n = (other.v.copy(), other.g.copy(),
                                              other.delta.copy(), other.n)
            return out

        def bounds_against(v_mine, sk: "GKSketch"):
            rmin_o, rmax_o = sk.rank_bounds()
            j = np.searchsorted(sk.v, v_mine, side="right") - 1
            lb = np.where(j >= 0, rmin_o[np.clip(j, 0, None)], 0)
            succ = j + 1
            ub = np.where(succ < sk.size,
                          rmax_o[np.clip(succ, None, sk.size - 1)] - 1, sk.n)
            return lb, ub

        rmin_a, rmax_a = self.rank_bounds()
        rmin_b, rmax_b = other.rank_bounds()
        lb_ab, ub_ab = bounds_against(self.v, other)
        lb_ba, ub_ba = bounds_against(other.v, self)
        v = np.concatenate([self.v, other.v])
        rmin = np.concatenate([rmin_a + lb_ab, rmin_b + lb_ba])
        rmax = np.concatenate([rmax_a + ub_ab, rmax_b + ub_ba])
        order = np.argsort(v, kind="stable")
        v, rmin, rmax = v[order], rmin[order], rmax[order]
        rmin = np.maximum.accumulate(rmin)
        rmax = np.maximum.accumulate(rmax)
        g = np.diff(np.concatenate([[0], rmin]))
        delta = np.maximum(0, rmax - rmin)
        out = GKSketch(eps, self.head_size, self.compress_threshold,
                       self.adaptive_head, self.alpha)
        out.v, out.g, out.delta = v, g.astype(np.int64), delta.astype(np.int64)
        out.n = self.n + other.n
        out.compress()
        return out


def merge_fold_left(sketches) -> GKSketch:
    """Spark's driver merge: sequential pairwise foldLeft (Theta(P/eps log) —
    Eq. 7's asymptotically-worse path)."""
    out = sketches[0]
    for s in sketches[1:]:
        out = out.merge(s)
    return out


def merge_tree(sketches) -> GKSketch:
    """The paper's recommended driver-side recursive tree reduce."""
    items = list(sketches)
    while len(items) > 1:
        nxt = []
        for i in range(0, len(items) - 1, 2):
            nxt.append(items[i].merge(items[i + 1]))
        if len(items) % 2:
            nxt.append(items[-1])
        items = nxt
    return items[0]
