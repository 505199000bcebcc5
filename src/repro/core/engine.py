"""Phase-based distributed quantile engine (DESIGN.md §6).

Every sharded engine in this repo is a *plan* over four composable phase
functions, each a plain shard_map-body fragment:

  phase_sketch        per-shard stride-m summary -> all_gather (the paper's
                      "collect sketches" action; the only phase that sorts)
  phase_pivot         replicated merged-summary query for Q target ranks
  phase_count_extract 3-way counts + both capped candidate bands for all Q
                      pivots (optionally ONE fused HBM pass), counts psum'd
  phase_reduce        candidate buffers across shards: generalized butterfly
                      (`tree_reduce_candidates`) or capped all_gather
  phase_resolve       rank arithmetic -> the exact values (no collective)

The plans:

  gk_select_sharded        faithful 3-phase GK Select (one-sided extraction)
  gk_select_multi_sharded  Q quantiles, one job; accepts externally-supplied
                           pivots — the WARM path: a maintained SketchState
                           already knows the pivots, so the sketch phase
                           (and its per-shard sort) is skipped entirely,
                           dropping one of the paper's three actions
  approx_quantile_sharded  sketch + pivot only (Spark approxQuantile)
  count_discard_sharded    AFS / Jeffers rounds (phase_count per round)
  full_sort_sharded        PSRS full-shuffle baseline

``repro.core.grouped`` adds the segmented plan
(``gk_select_grouped_sharded``): per-group phases for its sketch and
count+extract, then the SAME phase_reduce / phase_resolve over the
flattened (G*Q) axis — the butterfly and resolve are group-agnostic.

``repro.core.distributed`` keeps the public entry points
(``distributed_quantile`` / ``distributed_quantile_multi``) as thin wrappers
over these plans — signatures and semantics unchanged.
"""
from __future__ import annotations

import math
from typing import Sequence

import jax
import jax.numpy as jnp

from . import local_ops
from .sketch import local_sample_sketch, query_merged_sketch, sample_sketch_params


# ---------------------------------------------------------------------------
# collective helpers
# ---------------------------------------------------------------------------


def _axis_size(axis) -> int:
    return jax.lax.psum(1, axis)


def shard_map_compat(body, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication checking off — the bodies return
    deliberately replicated scalars from psum/pmax chains."""
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def tree_reduce_candidates(buf: jax.Array, axis: str, num_shards: int,
                           keep_largest: bool) -> jax.Array:
    """Butterfly reduction of a fixed-capacity candidate buffer, generalized
    to ARBITRARY shard counts: every step merges two buffers along the last
    axis and keeps the ``cap`` best; all shards end with the globally-best
    cap candidates.  Leading axes (e.g. the Q quantiles of the multi engine)
    ride along — one butterfly reduces all of them.

    A plain XOR butterfly ``(i, i ^ d)`` only works when P is a power of two
    (for P=120 it indexes shards out of range).  For general P the reduction
    runs in three stages over p2 = the largest power of two <= P (DESIGN.md
    §5):

      1. fold: the r = P - p2 extra shards send their buffers to shards
         0..r-1, which merge them in;
      2. butterfly: log2(p2) XOR ppermute steps over shards 0..p2-1 — shards
         >= p2 receive nothing and mask the incoming zeros to sentinels;
      3. broadcast: shards 0..r-1 return the fully-reduced buffer to the
         extra shards.

    log2(p2) + 2 ppermutes total; for power-of-two P this is exactly the
    old butterfly.  The globally best cap values always survive: each kept
    set is a superset of the intersection of the global best with the
    merged pair's union.
    """
    cap = buf.shape[-1]
    if num_shards <= 1:
        return buf
    lo, hi = local_ops._sentinels(buf.dtype)
    sentinel = lo if keep_largest else hi

    def merge(a, b):
        both = jnp.concatenate([a, b], axis=-1)
        if keep_largest:
            return jax.lax.top_k(both, cap)[0]
        return -jax.lax.top_k(-both, cap)[0]

    p2 = 1 << (num_shards.bit_length() - 1)   # largest power of two <= P
    r = num_shards - p2
    me = jax.lax.axis_index(axis)
    sent_buf = jnp.full(buf.shape, sentinel, buf.dtype)

    if r:
        # fold the r extra shards into shards 0..r-1 (non-destinations
        # receive zeros from ppermute — mask them to identity sentinels)
        other = jax.lax.ppermute(buf, axis, [(p2 + i, i) for i in range(r)])
        buf = merge(buf, jnp.where(me < r, other, sent_buf))

    for j in range(int(math.log2(p2))):
        d = 1 << j
        other = jax.lax.ppermute(buf, axis,
                                 [(i, i ^ d) for i in range(p2)])
        if r:
            other = jnp.where(me < p2, other, sent_buf)
        buf = merge(buf, other)

    if r:
        # hand the reduced buffer back to the extra shards
        other = jax.lax.ppermute(buf, axis, [(i, p2 + i) for i in range(r)])
        buf = jnp.where(me >= p2, other, buf)
    return buf


def gather_candidates(buf: jax.Array, axis: str) -> jax.Array:
    """Flat all_gather alternative (Jeffers-style collect): O(cap*P) volume.
    Leading axes are preserved; only the candidate (last) axis is merged
    across shards, so a (Q, cap) buffer gathers to (Q, P*cap)."""
    g = jax.lax.all_gather(buf, axis)       # (P, *buf.shape)
    g = jnp.moveaxis(g, 0, -2)              # (*lead, P, cap)
    return g.reshape(*g.shape[:-2], -1)


def _pmax_pair(priority: jax.Array, value: jax.Array, axis: str):
    """Value attached to the max priority across the axis (distributed
    reservoir pick), dtype-safe: the owner is the lowest rank holding the
    max priority and its value travels through a one-hot psum.  The old
    float32/-inf masking round-trip rounded int32/float64 values with
    magnitude > 2^24; the one-hot sum (value + P-1 zeros) is bit-exact for
    every dtype."""
    gp = jax.lax.pmax(priority, axis)
    me = jax.lax.axis_index(axis)
    owner = jax.lax.pmin(jnp.where(priority == gp, me, jnp.int32(1 << 30)),
                         axis)
    return jax.lax.psum(jnp.where(me == owner, value, jnp.zeros_like(value)),
                        axis)


# ---------------------------------------------------------------------------
# phase functions
# ---------------------------------------------------------------------------


def phase_sketch(x_local: jax.Array, *, axis: str, num_shards: int, n: int,
                 eps: float):
    """Action 1 (collect sketches): per-shard sorted stride-m summary,
    all_gather'd so every shard holds the merged summary.  The only phase
    that sorts the shard — the warm path skips it (DESIGN.md §6).
    Returns ``(g_vals, g_wts, m)``."""
    n_local = x_local.shape[0]
    m, s = sample_sketch_params(n, n_local, eps, num_shards)
    vals, weights = local_sample_sketch(x_local, m, s)
    g_vals = jax.lax.all_gather(vals, axis).reshape(-1)
    g_wts = jax.lax.all_gather(weights, axis).reshape(-1)
    return g_vals, g_wts, m


def phase_pivot(g_vals: jax.Array, g_wts: jax.Array, ks: jax.Array, *,
                num_shards: int, m: int) -> jax.Array:
    """Replicated pivot selection: query the merged summary for every target
    rank in ``ks`` (a (Q,) int32 vector).  No collective — the summary is
    already replicated post-gather (the paper's TorrentBroadcast is free)."""
    return jax.vmap(
        lambda k: query_merged_sketch(g_vals, g_wts, k, num_shards, m))(ks)


def phase_count(x_local: jax.Array, pivot: jax.Array, *, axis: str,
                count3_fn=None, collect: str = "psum") -> jax.Array:
    """Action 2 (collect counts) for a single pivot: per-shard 3-way counts
    combined across shards — ``psum`` (AFS / treeReduce) or ``all_gather``
    (Jeffers / collect; dtype pinned int32 so an x64 carry never changes the
    while_loop contract of round-based callers)."""
    c = (count3_fn or local_ops.count3)(x_local, pivot)
    if collect == "psum":
        return jax.lax.psum(c, axis)
    return jax.lax.all_gather(c, axis).sum(0, dtype=jnp.int32)


def phase_count_extract(x_local: jax.Array, pivots: jax.Array, cap: int, *,
                        axis: str, fused_fn=None, count_extract_fn=None):
    """Actions 2+3's per-shard work, speculative two-sided form: 3-way
    counts AND both capped candidate bands for every pivot in the (Q,)
    vector; counts ride one psum.  ``fused_fn`` (the multi-pivot Pallas
    kernel, signature ``(x, pivots, cap) -> (counts (Q,3), below (Q,cap),
    above (Q,cap))``) streams the shard from HBM ONCE for all Q pivots; the
    jnp fallback vmaps ``count_extract_fn`` (single-pivot seam, default
    ``local_ops.fused_count_extract`` — 3 streams per pivot).  The pivot is
    a plain input: it can come from phase_pivot (cold) or from a maintained
    ``SketchState`` (warm) without retracing the phase."""
    if fused_fn is not None:
        c_local, below, above = fused_fn(x_local, pivots, cap)
    else:
        one = count_extract_fn or local_ops.fused_count_extract
        c_local, below, above = jax.vmap(
            lambda p: one(x_local, p, cap))(pivots)
    counts = jax.lax.psum(c_local, axis)              # (Q, 3)
    return counts, below, above


def phase_reduce(below: jax.Array, above: jax.Array, *, axis: str,
                 num_shards: int, strategy: str = "tree"):
    """Action 3 (treeReduce candidates): both (Q, cap) buffers cross shards
    — ONE generalized butterfly each (collective count independent of Q),
    or a single capped all_gather (strategy="all_gather")."""
    if strategy == "tree":
        below = tree_reduce_candidates(below, axis, num_shards,
                                       keep_largest=True)
        above = tree_reduce_candidates(above, axis, num_shards,
                                       keep_largest=False)
    else:
        below = gather_candidates(below, axis)        # (Q, P*cap)
        above = gather_candidates(above, axis)
    return below, above


def phase_resolve(pivots: jax.Array, ks: jax.Array, counts: jax.Array,
                  below: jax.Array, above: jax.Array, cap: int) -> jax.Array:
    """Final rank arithmetic (paper Steps 5+9), vmapped over the Q levels;
    purely local — every shard already holds the reduced buffers.  Also the
    single resolve seam above the engine: the streaming service's segmented
    queries (``grouped``/``exact_all``) flatten their (G, Q) matrices onto
    this same call, so one implementation owns the rank→value step."""
    def one(pivot, k, c, b, a):
        return local_ops.resolve(pivot, k, c[0], c[1], b, a, cap)
    return jax.vmap(one)(pivots, ks, counts, below, above)


# ---------------------------------------------------------------------------
# plans (shard_map bodies)
# ---------------------------------------------------------------------------


def gk_select_multi_sharded(x_local: jax.Array, *, qs: Sequence[float],
                            eps: float, axis: str, num_shards: int,
                            reduce_strategy: str = "tree",
                            fused_fn=None, count_extract_fn=None,
                            pivots=None, cap: int = None) -> jax.Array:
    """Q quantiles from ONE sharded job (the multi-quantile production
    engine; DESIGN.md §5): phase_sketch -> phase_pivot ->
    phase_count_extract -> phase_reduce -> phase_resolve.  ``qs`` is a
    static tuple of quantile levels; returns the (Q,) exact values,
    replicated on every shard.

    ``pivots`` (a (Q,) vector) supplies externally-computed pivots — the
    WARM path: a live ``SketchState`` already knows rank-accurate pivots,
    so phase_sketch (the only phase that sorts the shard) is skipped and
    the job runs in 2 of the paper's 3 actions.  ``cap`` overrides the
    eps-derived candidate capacity; warm callers size it from
    ``sketch_rank_bound`` so exactness survives any stream history.
    """
    n_local = x_local.shape[0]
    n = n_local * num_shards
    ks = jnp.array([local_ops.target_rank(n, q) for q in qs], jnp.int32)

    # ---- Phase 1: one shared sketch, queried for all Q ranks (cold only) --
    if pivots is None:
        g_vals, g_wts, m = phase_sketch(x_local, axis=axis,
                                        num_shards=num_shards, n=n, eps=eps)
        pivots = phase_pivot(g_vals, g_wts, ks, num_shards=num_shards, m=m)
    else:
        pivots = jnp.asarray(pivots, x_local.dtype).reshape(len(qs))

    if cap is None:
        cap = local_ops.candidate_cap(n, eps, n_local)

    # ---- Phase 2: one (fused) pass over the shard for all Q pivots ----
    counts, below, above = phase_count_extract(
        x_local, pivots, cap, axis=axis, fused_fn=fused_fn,
        count_extract_fn=count_extract_fn)

    # ---- Phase 3: one butterfly for all Q candidate buffers ----
    below, above = phase_reduce(below, above, axis=axis,
                                num_shards=num_shards,
                                strategy=reduce_strategy)
    return phase_resolve(pivots, ks, counts, below, above, cap)


def gk_select_sharded(x_local: jax.Array, *, q: float, eps: float, axis: str,
                      num_shards: int, speculative: bool = False,
                      reduce_strategy: str = "tree",
                      count3_fn=None, extract_fns=None,
                      fused_fn=None) -> jax.Array:
    """Faithful GK Select plan: x_local is this shard's (n_local,) block.
    Returns the exact quantile, replicated on every shard.

    count3_fn / extract_fns allow kernel injection (Pallas partition_count /
    block-select) without changing the algorithm.  fused_fn injects the
    single-pass fused band-extraction kernel
    (``kernels.ops.fused_count_extract`` signature ``(x, pivot, cap) ->
    (counts, below, above)``): the whole speculative count+extract phase
    becomes ONE HBM stream over the shard (implies ``speculative=True``).
    """
    n_local = x_local.shape[0]
    n = n_local * num_shards
    k = jnp.int32(local_ops.target_rank(n, q))
    count3 = count3_fn or local_ops.count3
    ex_below = extract_fns[0] if extract_fns else local_ops.extract_below
    ex_above = extract_fns[1] if extract_fns else local_ops.extract_above

    if speculative or fused_fn is not None:
        # The speculative round is exactly the Q=1 case of the multi plan:
        # delegate (one data flow to maintain), adapting any injected
        # single-pivot seams to the multi signatures.
        multi_fused = None
        if fused_fn is not None:
            def multi_fused(x, pivots, cap_):
                c, b, a = fused_fn(x, pivots[0], cap_)
                return c[None], b[None], a[None]

        def count_extract(x, pivot_, cap_):
            return (count3(x, pivot_), ex_below(x, pivot_, cap_),
                    ex_above(x, pivot_, cap_))

        return gk_select_multi_sharded(
            x_local, qs=(q,), eps=eps, axis=axis, num_shards=num_shards,
            reduce_strategy=reduce_strategy, fused_fn=multi_fused,
            count_extract_fn=count_extract)[0]

    # ---- Phase 1: sketch -> replicated pivot ----
    g_vals, g_wts, m = phase_sketch(x_local, axis=axis,
                                    num_shards=num_shards, n=n, eps=eps)
    pivot = phase_pivot(g_vals, g_wts, k[None], num_shards=num_shards, m=m)[0]

    cap = local_ops.candidate_cap(n, eps, n_local)

    # ---- Phase 2: counts -> Delta_k ----
    counts = phase_count(x_local, pivot, axis=axis, count3_fn=count3_fn)
    lt, eq = counts[0], counts[1]
    need_left = lt - k + 1
    need_right = k - (lt + eq)
    go_left = need_left > 0

    # ---- Phase 3: one-sided extraction (sign-folded for static shapes) ----
    # For the left side we negate values so "smallest above -pivot" ==
    # "largest below pivot"; extraction volume stays 1x (paper-faithful).
    y = jnp.where(go_left, -x_local, x_local)
    piv = jnp.where(go_left, -pivot, pivot)
    cand = ex_above(y, piv, cap)           # cap smallest of y above piv
    if reduce_strategy == "tree":
        cand = tree_reduce_candidates(cand, axis, num_shards, keep_largest=False)
    else:
        cand = gather_candidates(cand, axis)
    need = jnp.maximum(jnp.where(go_left, need_left, need_right), 1)
    kth = local_ops.kth_smallest(cand, need, cap)
    side_val = jnp.where(go_left, -kth, kth)
    return jnp.where((need_left <= 0) & (need_right <= 0), pivot, side_val)


def approx_quantile_sharded(x_local: jax.Array, *, q: float, eps: float,
                            axis: str, num_shards: int) -> jax.Array:
    """GK Sketch plan (Spark approxQuantile): phase_sketch + phase_pivot
    only — 1 collective phase."""
    n_local = x_local.shape[0]
    n = n_local * num_shards
    k = jnp.int32(local_ops.target_rank(n, q))
    g_vals, g_wts, m = phase_sketch(x_local, axis=axis,
                                    num_shards=num_shards, n=n, eps=eps)
    return phase_pivot(g_vals, g_wts, k[None], num_shards=num_shards, m=m)[0]


def count_discard_sharded(x_local: jax.Array, *, q: float, axis: str,
                          num_shards: int, max_rounds: int = 128, seed: int = 0,
                          collect_counts: bool = False) -> jax.Array:
    """AFS (collect_counts=False: psum ~ treeReduce) / Jeffers
    (collect_counts=True: all_gather ~ collect) plan — O(log n) rounds, one
    phase_count per round inside a while_loop.

    Candidates are drawn strictly inside the open band (lo, hi), so values
    equal to a dtype extreme (int32 min/max, +-inf) can never be picked as
    pivots.  When the target lands on such a value the band empties; the
    loop detects that and terminates on the boundary whose side rank says
    holds rank k — instead of spinning on an arbitrary all-inactive pick
    until max_rounds.  The band population is derived from carried rank
    masses (``n_le_lo`` = #{x <= lo}, ``n_lt_hi`` = #{x < hi}, both
    updatable from the counts already collected each round), so detection
    adds no per-round collective.
    """
    n_local = x_local.shape[0]
    n = n_local * num_shards
    k = local_ops.target_rank(n, q)
    lo, hi = local_ops._sentinels(x_local.dtype)
    collect = "all_gather" if collect_counts else "psum"
    base = jax.random.fold_in(jax.random.PRNGKey(seed),
                              jax.lax.axis_index(axis))

    def candidate(lo_, hi_, key):
        pri = jax.random.uniform(key, x_local.shape)
        active = (x_local > lo_) & (x_local < hi_)
        pri = jnp.where(active, pri, -1.0)
        i = jnp.argmax(pri)
        return _pmax_pair(pri[i], x_local[i], axis)

    # elements equal to a sentinel boundary are never active; count them once
    # (one stacked psum) so an emptied band resolves to the right boundary
    c_lo = local_ops.count3(x_local, lo)
    c_hi = local_ops.count3(x_local, hi)
    sums = jax.lax.psum(jnp.stack([c_lo[0] + c_lo[1], c_hi[0]]), axis)
    n_le_lo0, n_lt_hi0 = sums[0], sums[1]

    key0, sub = jax.random.split(base)
    pivot0 = candidate(lo, hi, sub)

    def cond(st):
        done, rounds = st[5], st[7]
        return (~done) & (rounds < max_rounds)

    def body(st):
        lo_, hi_, pivot, n_le_lo, n_lt_hi, done, ans, rounds, key = st
        empty = (n_lt_hi - n_le_lo) == 0
        boundary = jnp.where(k <= n_le_lo, lo_, hi_)
        counts = phase_count(x_local, pivot, axis=axis, collect=collect)
        lt, eq = counts[0], counts[1]
        found = (~empty) & (lt < k) & (k <= lt + eq)
        go_left = k <= lt
        lo2 = jnp.where(go_left, lo_, pivot)
        hi2 = jnp.where(go_left, pivot, hi_)
        n_le_lo2 = jnp.where(go_left, n_le_lo, lt + eq)
        n_lt_hi2 = jnp.where(go_left, lt, n_lt_hi)
        key2, sub2 = jax.random.split(key)
        nxt = candidate(lo2, hi2, sub2)
        hit = found | empty
        return (jnp.where(hit, lo_, lo2), jnp.where(hit, hi_, hi2),
                jnp.where(hit, pivot, nxt),
                jnp.where(hit, n_le_lo, n_le_lo2),
                jnp.where(hit, n_lt_hi, n_lt_hi2), done | hit,
                jnp.where(empty, boundary, jnp.where(found, pivot, ans)),
                rounds + 1, key2)

    st0 = (lo, hi, pivot0, n_le_lo0, n_lt_hi0, jnp.array(False), pivot0,
           jnp.array(0, jnp.int32), key0)
    st = jax.lax.while_loop(cond, body, st0)
    return st[6]


def full_sort_sharded(x_local: jax.Array, *, q: float, axis: str,
                      num_shards: int, capacity_factor: float = 2.0) -> jax.Array:
    """PSRS / Spark range-partition sort plan: the O(n) full-shuffle
    baseline.

    Per-shard regular samples -> replicated splitters -> capacity-padded
    all_to_all shuffle -> local sort -> rank-addressed exact quantile.
    Capacity lanes are sentinel-padded; with pathological skew the quantile
    falls back on the (exact) global-min of dropped lanes being impossible —
    capacity_factor sizes the buckets, tests use distributions within it.
    """
    n_local = x_local.shape[0]
    n = n_local * num_shards
    k = local_ops.target_rank(n, q)
    lo, hi = local_ops._sentinels(x_local.dtype)

    # splitters from regular samples (r per shard)
    r = min(n_local, 64)
    xs = jnp.sort(x_local)
    stride = max(1, n_local // r)
    samples = xs[::stride][:r]
    all_samples = jnp.sort(jax.lax.all_gather(samples, axis).reshape(-1))
    # r >= 1 so the gathered sample count is >= num_shards, but guard the
    # stride anyway: step == 0 would make the splitter slice a wrap-around
    step = max(1, all_samples.size // num_shards)
    splitters = all_samples[step::step][: num_shards - 1]

    # bucket & pack into capacity lanes per destination
    bucket = jnp.searchsorted(splitters, x_local, side="right")
    cap = int(min(n_local, math.ceil(capacity_factor * n_local / num_shards)))
    order = jnp.argsort(bucket)
    xb = x_local[order]
    bb = bucket[order]
    # position within bucket
    start = jnp.searchsorted(bb, jnp.arange(num_shards), side="left")
    pos = jnp.arange(n_local) - start[bb]
    valid = pos < cap
    send = jnp.full((num_shards, cap), hi, x_local.dtype)
    send = send.at[bb, jnp.where(valid, pos, cap - 1)].set(
        jnp.where(valid, xb, send[bb, jnp.where(valid, pos, cap - 1)]))
    # counts actually shipped per destination (for exact global ranks)
    sent = jax.ops.segment_sum(valid.astype(jnp.int32), bb, num_shards)

    recv = jax.lax.all_to_all(send, axis, split_axis=0, concat_axis=0,
                              tiled=False)
    recv = recv.reshape(-1)
    local_sorted = jnp.sort(recv)  # sentinels sort last

    # exact rank bookkeeping: ranks below my bucket
    counts_all = jax.lax.psum(sent, axis)          # (P,) global per-bucket
    below = jnp.cumsum(counts_all) - counts_all    # exclusive prefix
    mine = jax.lax.axis_index(axis)
    k_local = k - below[mine]
    have = (k_local >= 1) & (k_local <= counts_all[mine])
    val = local_sorted[jnp.clip(k_local - 1, 0, recv.size - 1)]
    # exactly one shard owns rank k; a one-hot psum ships its value without
    # the float32/-inf round-trip that rounded wide int32/float64 answers.
    # If capacity overflow dropped rank k entirely (pathological skew), no
    # shard owns it — surface the high sentinel, not a plausible-looking 0.
    contrib = jnp.where(have, val, jnp.zeros_like(val))
    out = jax.lax.psum(contrib, axis)
    owned = jax.lax.psum(have.astype(jnp.int32), axis)
    return jnp.where(owned > 0, out, hi)
