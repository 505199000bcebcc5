"""Per-shard primitives shared by the simulated (vmap) and distributed
(shard_map) GK Select implementations.

Everything here is static-shape jnp; the Pallas kernels in
``repro.kernels.ops`` provide drop-in accelerated versions of
``count3`` and the block-select stage of ``extract_candidates``.
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp

from .. import obs
from . import lowering


def _sentinels(dtype):
    """(lowest, highest) total-order sentinels for a dtype."""
    if jnp.issubdtype(dtype, jnp.floating):
        info = jnp.finfo(dtype)
        return jnp.array(-jnp.inf, dtype), jnp.array(jnp.inf, dtype)
    info = jnp.iinfo(dtype)
    return jnp.array(info.min, dtype), jnp.array(info.max, dtype)


def pad_with_high_sentinel(x: jax.Array, multiple: int, *,
                           axis: int = -1) -> jax.Array:
    """Pad ``axis`` up to a multiple of ``multiple`` lanes with the dtype's
    highest total-order sentinel (+inf / int max).

    Top-sentinel padding never disturbs the k-th smallest for any
    k <= n_true (pads tie at-or-above the maximum, and tied ranks resolve
    to the same value) — unlike zero padding, which inserts mass in the
    middle of the distribution and corrupts every rank above the zeros.
    """
    pad = (-x.shape[axis]) % multiple
    if pad:
        _, hi = _sentinels(x.dtype)
        shape = list(x.shape)
        shape[axis] = pad
        x = jnp.concatenate([x, jnp.full(shape, hi, x.dtype)], axis=axis)
    return x


def reject_nans(x: jax.Array, where: str) -> None:
    """NaN policy (DESIGN.md §7): REJECT.

    GK Select's rank arithmetic assumes the 3-way counts partition n; a NaN
    compares False against every pivot (neither lt, eq nor gt), so counts
    silently stop summing to n and the resolved "quantile" is an arbitrary
    element.  Rather than define quantiles over a non-total order, every
    public *eager* entry point raises ``ValueError`` on float inputs
    containing NaN.  Inside a jit trace the check is skipped (a traced value
    cannot raise) — callers embedding the engine in larger jitted programs
    own their NaN hygiene, and the contract is documented at each entry.
    """
    if isinstance(x, jax.core.Tracer) or lowering.active():
        return
    x = jnp.asarray(x)
    if not jnp.issubdtype(x.dtype, jnp.floating):
        return
    with obs.span("nan_check", where=where):
        found = jnp.any(jnp.isnan(x))
        with obs.span("read"):
            found = bool(found)
    if found:
        raise ValueError(
            f"{where}: input contains NaN — quantiles are undefined over a "
            f"non-total order (NaN policy: reject; see DESIGN.md §7)")


def count3(x: jax.Array, pivot: jax.Array) -> jax.Array:
    """Dutch 3-way counts (lt, eq, gt) of one shard vs the pivot.

    Paper Step 4 / ``firstPass``. Linear streaming pass — the Pallas
    ``partition_count`` kernel implements the tiled HBM->VMEM version.
    """
    lt = jnp.sum(x < pivot, dtype=jnp.int32)
    eq = jnp.sum(x == pivot, dtype=jnp.int32)
    gt = x.size - lt - eq
    # int32 counts bound a single job to n < 2^31 elements; jobs larger than
    # that shard the count over the pod axis before it ever materializes.
    return jnp.stack([lt, eq, gt])


def candidate_cap(n_total: int, eps: float, n_local: int) -> int:
    """Static per-shard candidate-buffer capacity.

    The sketch guarantees |Delta_k| <= eps*n, so ceil(eps*n)+2 lanes always
    hold every candidate a shard can contribute (clamped to the shard size).
    This is the static-shape replacement for Spark's dynamic Delta_k slices
    (DESIGN.md §2).
    """
    return int(min(n_local, math.ceil(eps * n_total) + 2))


def extract_above(x: jax.Array, pivot: jax.Array, cap: int) -> jax.Array:
    """The ``cap`` smallest values strictly above the pivot, ascending;
    missing lanes are +sentinel. Paper Step 7, Delta_k > 0 branch
    (Dutch partition + QuickSelect == masked top-k on TPU)."""
    lo, hi = _sentinels(x.dtype)
    keys = jnp.where(x > pivot, x, hi)
    # top_k on negated keys -> k smallest.
    vals, _ = jax.lax.top_k(-keys, cap)
    return -vals


def extract_below(x: jax.Array, pivot: jax.Array, cap: int) -> jax.Array:
    """The ``cap`` largest values strictly below the pivot, descending;
    missing lanes are -sentinel. Paper Step 7, Delta_k < 0 branch."""
    lo, hi = _sentinels(x.dtype)
    keys = jnp.where(x < pivot, x, lo)
    vals, _ = jax.lax.top_k(keys, cap)
    return vals


def fused_count_extract(x: jax.Array, pivot: jax.Array, cap: int):
    """The speculative round's per-shard work behind one seam: 3-way counts
    plus both capped candidate bands, ``(counts, below, above)``.

    This jnp reference implementation still streams the shard three times
    (count + 2x top_k); ``repro.kernels.ops.fused_count_extract`` is the
    bit-exact single-HBM-pass drop-in (DESIGN.md §2).  Callers that want
    kernel injection swap the whole seam, not the three pieces.
    """
    return (count3(x, pivot),
            extract_below(x, pivot, cap),
            extract_above(x, pivot, cap))


# Candidate buffers of at least this many lanes pick their k-th value by
# order-key bisection; smaller ones sort.  The bisection costs one fused
# compare-and-sum over the buffer per key bit (32 for 32-bit data) and no
# copy of it, plus a fixed ~0.05 ms of loop; the sort, up to 4.8 ns a lane.
# On one v5e, flat float32 (``experiments/kth_crossover.py``, its numbers
# in PERF.md): at 2^16 lanes the key sort takes 0.049 ms and the bisection
# 0.065 ms, at 2^17 0.106 ms and 0.080 ms; at 2^30 a sort of the values
# took 5.19 s and the bisection 0.18 s.
BISECT_MIN_LANES = 1 << 17

_KEY_UINT = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32, 8: jnp.uint64}
_KEY_FLOATS = (jnp.float16, jnp.bfloat16, jnp.float32, jnp.float64)


def _has_order_key(dtype) -> bool:
    """True for the dtypes with an order key (``_order_key``): integers of
    8 to 64 bits and the IEEE-style floats with infinities."""
    dt = jnp.dtype(dtype)
    if jnp.issubdtype(dt, jnp.integer):
        return dt.itemsize in _KEY_UINT
    return any(dt == f for f in _KEY_FLOATS)


def _bisects(cands: jax.Array) -> bool:
    """The selector a candidate buffer takes, from its shape and dtype
    alone: bisection from ``BISECT_MIN_LANES`` lanes (int32 counts bound it
    below 2^31 lanes), the sort below that and for dtypes with no order
    key."""
    return (BISECT_MIN_LANES <= cands.size < 2 ** 31
            and _has_order_key(cands.dtype))


def _order_key(x: jax.Array) -> jax.Array:
    """The order key of ``x``: the unsigned integer of its width that
    orders as the values do.  Integers are offset by the sign bit; floats
    are sign-flipped (a positive gains the top bit, a negative is
    complemented), -0.0 taking +0.0's key, as ``jnp.sort`` ties them.
    Integer compares of keys rank subnormals as IEEE does, where a float
    compare may flush them to zero (XLA's CPU sort does)."""
    dt = x.dtype
    u = _KEY_UINT[dt.itemsize]
    top = u(1 << (8 * dt.itemsize - 1))
    b = jax.lax.bitcast_convert_type(x, u)
    if jnp.issubdtype(dt, jnp.floating):
        b = jnp.where(b == top, u(0), b)
        return jnp.where(b >= top, ~b, b | top)
    if jnp.issubdtype(dt, jnp.signedinteger):
        return b ^ top
    return b


def _from_key(key: jax.Array, dtype) -> jax.Array:
    """The value of ``dtype`` whose order key is ``key``."""
    dt = jnp.dtype(dtype)
    top = key.dtype.type(1 << (8 * dt.itemsize - 1))
    if jnp.issubdtype(dt, jnp.floating):
        return jax.lax.bitcast_convert_type(
            jnp.where(key >= top, key ^ top, ~key), dt)
    if jnp.issubdtype(dt, jnp.signedinteger):
        return jax.lax.bitcast_convert_type(key ^ top, dt)
    return key


def _count_below_key(cands: jax.Array, key: jax.Array) -> jax.Array:
    """#{c in cands : order key of c < key}: one compare-and-sum of the
    buffer against scalars, so no key of the buffer is made.

    Integers compare their values with the value of ``key``.  Floats
    compare their bits as signed integers ``s``, which order positives as
    values do, and negatives (but -0.0, ``s == int min``) in reverse: a
    ``key`` in the positive half counts every negative and the positives
    with ``s`` below ``key``'s magnitude, -0.0 once that magnitude is past
    +0.0; one in the negative half counts the negatives with ``s`` above
    its complement.  So the count is exact for subnormals too (a float
    compare may flush them to zero)."""
    dt = cands.dtype
    if not jnp.issubdtype(dt, jnp.floating):
        return jnp.sum(cands < _from_key(key, dt), dtype=jnp.int32)
    sint = jnp.dtype(f"int{8 * dt.itemsize}")
    smin = jnp.iinfo(sint).min
    top = key.dtype.type(1 << (8 * dt.itemsize - 1))
    mag = jax.lax.bitcast_convert_type(key ^ top, sint)   # >= 0 when positive
    comp = jax.lax.bitcast_convert_type(~key, sint)       # < 0 when negative
    positive = key >= top
    lo = jnp.where(positive, jnp.where(mag == 0, smin + 1, smin),
                   comp + 1).astype(sint)
    hi = jnp.where(positive, mag, 0).astype(sint)
    # tied to the loop-variant bounds, the bitcast stays in the pass's
    # fusion: hoisted out of the loop it would be a copy of the buffer
    cands, lo = jax.lax.optimization_barrier((cands, lo))
    s = jax.lax.bitcast_convert_type(cands, sint)
    return jnp.sum((s >= lo) & (s < hi), dtype=jnp.int32)


def _kth_bisect(cands: jax.Array, k: jax.Array) -> jax.Array:
    """k-th smallest (1-based, k in [1, cands.size]) of any-shape ``cands``
    by bisection on the order key, from the high bit down: a bit is kept
    when fewer than k candidates have a key below the prefix with it set,
    so the prefix ends as the least key t with #{key <= t} >= k, which is
    the sort's k-th key.  One ``_count_below_key`` pass a key bit.  On a
    run of equal keys the value returned equals (``==``) the sort's; for a
    run of zeros it is +0.0."""
    dt = cands.dtype
    u = _KEY_UINT[dt.itemsize]
    bits = 8 * dt.itemsize

    def step(i, prefix):
        trial = prefix | (u(1) << (bits - 1 - i).astype(u))
        return jnp.where(_count_below_key(cands, trial) < k, trial, prefix)

    with jax.named_scope("kth_bisect"):
        key = jax.lax.fori_loop(0, bits, step, jnp.zeros((), u))
        return _from_key(key, dt)


def _kth_sort(cands: jax.Array, k: jax.Array) -> jax.Array:
    """k-th smallest (1-based, k in [1, cands.size]) by a sort of the
    buffer's order keys, or of its values for a dtype with none."""
    if not _has_order_key(cands.dtype):
        return jnp.sort(cands.ravel())[k - 1]
    keys = jnp.sort(_order_key(cands).ravel())
    return _from_key(keys[k - 1], cands.dtype)


def kth_smallest(cands: jax.Array, k: jax.Array, cap: int) -> jax.Array:
    """k-th smallest (1-based, traced k, clamped to [1, cands.size]) among
    candidate lanes of any shape; invalid lanes must be +sentinel so they
    rank last.  Large buffers (``_bisects``) take the order-key bisection,
    32 streaming passes for 32-bit data where a sort would reorder every
    lane; small ones, where the loop's fixed cost outweighs the sort,
    sort.  Both return the same value: on a run of zeros, +0.0."""
    k = jnp.clip(jnp.asarray(k).astype(jnp.int32), 1, cands.size)
    return (_kth_bisect if _bisects(cands) else _kth_sort)(cands, k)


def kth_largest(cands: jax.Array, k: jax.Array, cap: int) -> jax.Array:
    """k-th largest (1-based, traced k, clamped to [1, cands.size]); invalid
    lanes must be -sentinel.  The (size - k + 1)-th smallest, by the same
    selector as ``kth_smallest``: no value is negated, so integer minima
    are safe."""
    k = jnp.clip(jnp.asarray(k).astype(jnp.int32), 1, cands.size)
    return (_kth_bisect if _bisects(cands) else _kth_sort)(
        cands, cands.size + 1 - k)


def target_rank(n: int, q: float) -> int:
    """1-based target rank k = clamp(ceil(q*n), 1, n).

    Computed host-side in exact integer arithmetic: f32 ceil(q*n) is off by
    several ranks for n >~ 2^24, which would silently break exactness.
    """
    return int(min(n, max(1, math.ceil(q * n))))


def exact_target_rank(n: int, q: float) -> int:
    """Host-side EXACT-rational target rank: k = ceil(q*n) over the dyadic
    rational q = a/2^t that the float ``q`` actually is, clamped to
    [1, max(n, 1)].

    ``target_rank`` rounds the product q*n to double before the ceil; this
    variant never rounds, so it agrees bit-for-bit with the traced
    ``target_rank_traced`` (the grouped engine's rank rule, where n is
    data-dependent).  The two rules differ only when q*n lies within one
    double ulp of an integer.
    """
    a, b = float(q).as_integer_ratio()
    if not 0 < a <= b:
        raise ValueError(f"q must be in (0, 1], got {q}")
    return int(min(max(n, 1), max(1, -((-a * n) // b))))


def target_rank_traced(n: jax.Array, q: float) -> jax.Array:
    """``exact_target_rank`` for a TRACED int32 count ``n`` (static q).

    The grouped engine needs per-group ranks k_g = ceil(q * n_g) where the
    group counts n_g are data-dependent, so the ceil must run on device.
    float32 is exact only below 2^24 ranks; instead the product a*n (a up to
    2^54, n < 2^31) is computed in base-2^10 int32 limbs — every partial
    product and carry stays far below 2^31 — then shifted down by t and
    ceil'd exactly.  Elementwise over any ``n`` shape.  Empty groups
    (n == 0) clamp to k = 1, which the resolve phase turns into the dtype's
    high sentinel (no candidate ever satisfies rank 1 of nothing).
    """
    a, b = float(q).as_integer_ratio()
    if not 0 < a <= b:
        raise ValueError(f"q must be in (0, 1], got {q}")
    t = b.bit_length() - 1                       # b == 2**t (q is a float)
    n = jnp.asarray(n, jnp.int32)
    n_limbs = [(n >> (10 * j)) & 1023 for j in range(4)]         # n < 2^31
    a_limbs = [(a >> (10 * i)) & 1023
               for i in range(max(1, -(-a.bit_length() // 10)))]
    L = len(a_limbs) + 4
    r = [jnp.zeros_like(n) for _ in range(L + 1)]
    for i, ai in enumerate(a_limbs):             # D = a*n ...
        if ai == 0:
            continue
        for j, nj in enumerate(n_limbs):
            r[i + j] = r[i + j] + jnp.int32(ai) * nj
    for m in range(L + 1):                       # ... + (2^t - 1)
        cm = ((b - 1) >> (10 * m)) & 1023
        if cm:
            r[m] = r[m] + jnp.int32(cm)
    for m in range(L):                           # carry-propagate
        r[m + 1] = r[m + 1] + (r[m] >> 10)
        r[m] = r[m] & 1023
    mb, rb = divmod(t, 10)                       # k = floor(D / 2^t)
    # D < 2^t * (n+1), so the quotient is < 2^31: every limb whose shifted
    # contribution lands at bit >= 31 is provably zero and must be skipped
    # (an int32 shift by >= 32 is implementation-defined in XLA), and a
    # tiny q can push mb past the last limb entirely (quotient 0 -> k = 1).
    k = (r[mb] >> rb) if mb <= L else jnp.zeros_like(n)
    for m in range(mb + 1, L + 1):
        shift = 10 * (m - mb) - rb
        if shift >= 31:
            break
        k = k + (r[m] << shift)
    return jnp.clip(k, 1, jnp.maximum(n, 1))


def grouped_count_extract(values: jax.Array, keys: jax.Array,
                          pivots: jax.Array, cap: int):
    """Segmented speculative round, jnp reference: per-group 3-way counts
    AND both capped candidate bands for every (group, level) pivot.

    ``pivots`` is (G, Q); returns ``(counts (G, Q, 3), below (G, Q, cap),
    above (G, Q, cap))`` with exactly the sentinel-padding semantics of
    ``fused_count_extract`` restricted to ``keys == g``.  Keys outside
    [0, G) belong to no group and are ignored.  This streams the shard
    3*G*Q times; ``repro.kernels.ops.segmented_count_extract`` is the
    bit-exact single-HBM-pass drop-in (DESIGN.md §7).
    """
    G, Q = pivots.shape
    lo, hi = _sentinels(values.dtype)

    def one(g, pivot):
        in_g = keys == g
        is_lt = in_g & (values < pivot)
        is_gt = in_g & (values > pivot)
        counts = jnp.stack([
            jnp.sum(is_lt, dtype=jnp.int32),
            jnp.sum(in_g & (values == pivot), dtype=jnp.int32),
            jnp.sum(is_gt, dtype=jnp.int32)])
        below = jax.lax.top_k(jnp.where(is_lt, values, lo), cap)[0]
        above = -jax.lax.top_k(-jnp.where(is_gt, values, hi), cap)[0]
        return counts, below, above

    gids = jnp.repeat(jnp.arange(G, dtype=keys.dtype), Q)
    c, b, a = jax.vmap(one)(gids, pivots.reshape(-1))
    return (c.reshape(G, Q, 3), b.reshape(G, Q, cap), a.reshape(G, Q, cap))


def resolve(pivot: jax.Array, k: jax.Array, lt: jax.Array, eq: jax.Array,
            below: jax.Array, above: jax.Array, cap: int) -> jax.Array:
    """Paper Steps 5+9: pick the exact quantile from the pivot and the merged
    candidate slices.

    below: merged candidates < pivot, descending-sorted semantics with
           -sentinel padding (any layout; only rank arithmetic is used).
    above: merged candidates > pivot with +sentinel padding.

    Both sides pick their value with ``kth_largest`` / ``kth_smallest``:
    order-key bisection for buffers of ``BISECT_MIN_LANES`` lanes or more
    (the paper's job: 1.007e9), a sort of the buffer below that.
    """
    need_left = lt - k + 1          # >0  => answer is need_left-th largest < pivot
    need_right = k - (lt + eq)      # >0  => answer is need_right-th smallest > pivot
    left_val = kth_largest(below, jnp.maximum(need_left, 1), cap)
    right_val = kth_smallest(above, jnp.maximum(need_right, 1), cap)
    return jnp.where(need_left > 0, left_val,
                     jnp.where(need_right > 0, right_val, pivot))
