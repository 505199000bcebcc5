"""Kernel-layer operations: backend-dispatched wrappers + pass accounting.

``count3`` / ``band_count``      — layout + dispatch (kernel vs jnp oracle).
``fused_count_extract``          — the single-pass speculative round: one
                                   HBM stream emits (lt, eq, gt) counts AND
                                   both capped candidate bands (replaces the
                                   count3 + 2x whole-array top_k trio).
``fused_count_extract_multi``    — Q pivots answered by the same one pass.
``byte_histogram``               — 256-bin histogram of one byte of the
                                   sortable-u32 domain within a prefix group.
``radix_select_kth``             — exact k-th smallest with *zero* sorting:
                                   4 byte-histogram passes (8 bits/pass) over
                                   the sortable-uint transform.  The
                                   bit-at-a-time binary search it replaces is
                                   kept as ``radix_select_kth_bitwise`` for
                                   the pass-count benchmark (<= 32 passes).

Every public wrapper takes ``backend=`` (None | name string | alias |
``dispatch.Backend``) and routes through ``kernels.dispatch``:
``backend=None`` selects per platform at trace time (TPU -> compiled
Pallas, GPU -> gated Pallas-Triton, CPU -> the jitted jnp oracles — the
wall-clock winner there); ``backend="pallas"`` pins the Pallas kernels
(compiled on TPU, interpret elsewhere) — what the kernel-contract tests
and pass-count benchmarks use.  The legacy ``use_pallas=False`` flag is
kept as a hard alias for ``backend="jnp"``.

Every wrapper is a plain Python function that bumps the module HBM-pass
counter once per full-array stream *the selected backend actually
dispatches* — 1 for a fused Pallas sweep, 3 per pivot for the jnp oracle
(count + 2x top_k streams), 3*G*Q for the segmented oracle — and then
executes.  The counter (``kernels.hbm_passes`` in ``repro.obs``) counts
eager dispatches; calls traced inside an outer jit tick once at trace
time and are not the counter's job.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .. import obs
from . import dispatch, ref
from .dispatch import JNP
from .partition_count import LANES, partition_count
from .fused_select import byte_histogram as _byte_histogram_kernel  # noqa: F401 — re-export for tests


# ---------------------------------------------------------------------------
# HBM pass accounting (the bandwidth-bound cost model; see DESIGN.md §2): the
# ``kernels.hbm_passes`` counter of ``repro.obs``, whose lock keeps the
# count exact under concurrent ingest/query threads (launch/ingest_pool.py)
# ---------------------------------------------------------------------------

HBM_PASSES = "kernels.hbm_passes"


def reset_hbm_passes() -> None:
    """Zero the full-array streaming-pass counter."""
    obs.reset(HBM_PASSES)


def hbm_passes() -> int:
    """Full-array HBM streaming passes dispatched since the last reset."""
    return obs.counters().get(HBM_PASSES, 0)


def _tick(n: int = 1) -> None:
    obs.count(HBM_PASSES, n)


def _backend(backend, use_pallas: bool):
    """Fold the legacy use_pallas flag into the backend spec."""
    if not use_pallas:
        return JNP
    return backend       # None -> dispatch.select_backend() downstream


def pad_to_tiles(x: jax.Array, lanes: int = LANES) -> jax.Array:
    """Flat -> (rows, lanes) row-major, padded at the tail (values are masked
    by n_valid inside the kernels, so the pad content is irrelevant).
    ``lanes`` defaults to the 4-byte layout; pass ``dispatch.lanes_for``'s
    answer for dtype-specialized tiling."""
    return dispatch.pad_to_lanes(x, lanes)


def _cap_pad(cap: int) -> int:
    """Candidate-buffer lanes rounded to the VREG width (multiple of 128)."""
    return dispatch.cap_pad_for(cap)


def count3(x: jax.Array, pivot: jax.Array, *, use_pallas: bool = True,
           backend=None) -> jax.Array:
    """(lt, eq, gt) of flat x vs pivot — kernel-backed ``local_ops.count3``.
    One HBM pass on every backend."""
    _tick()
    out, _ = dispatch.run_partition_count(
        x, pivot, backend=_backend(backend, use_pallas))
    return out


def band_count(x: jax.Array, lo: jax.Array, hi: jax.Array, *,
               use_pallas: bool = True, backend=None) -> jax.Array:
    """#{ lo < x < hi } over the flat array.  One HBM pass."""
    _tick()
    out, _ = dispatch.run_band_count(
        x, lo, hi, backend=_backend(backend, use_pallas))
    return out


def extract_below(x: jax.Array, pivot: jax.Array, cap: int) -> jax.Array:
    """Unfused whole-array candidate extraction (one full HBM pass): the
    ``cap`` largest values < pivot, descending, -sentinel padded.  Kept as
    the pass-count benchmark's unfused baseline; the fused kernel replaces
    it on the hot path."""
    _tick()
    return ref.block_topk_ref(x.ravel(), pivot, cap, largest_below=True)


def extract_above(x: jax.Array, pivot: jax.Array, cap: int) -> jax.Array:
    """Unfused whole-array extraction of the ``cap`` smallest values > pivot
    (ascending, +sentinel padded).  One full HBM pass."""
    _tick()
    return ref.block_topk_ref(x.ravel(), pivot, cap, largest_below=False)


# ---------------------------------------------------------------------------
# fused single-pass band extraction
# ---------------------------------------------------------------------------


def fused_count_extract(x: jax.Array, pivot: jax.Array, cap: int, *,
                        use_pallas: bool = True, backend=None):
    """The speculative GK Select round: returns ``(counts, below, above)``
    with the semantics of ``(local_ops.count3, local_ops.extract_below,
    local_ops.extract_above)``: the same counts and the same multiset in
    each band, sorted here, where ``local_ops`` leaves a band that covers
    the whole shard in shard order (the resolve reads only ranks).

    On a Pallas backend the shard is read from HBM ONCE (ticks 1); the jnp
    backend really is count + 2x top_k streams and honestly ticks 3."""
    out, plan = dispatch.run_fused_select(
        x, pivot, cap, backend=_backend(backend, use_pallas))
    _tick(1 if plan.backend.kind == "pallas" else 3)
    return out


def fused_count_extract_multi(x: jax.Array, pivots: jax.Array, cap: int, *,
                              use_pallas: bool = True, backend=None):
    """``fused_count_extract`` against Q pivots:
    ``(counts (Q, 3), below (Q, cap), above (Q, cap))``.  A Pallas backend
    answers all Q pivots from ONE pass (ticks 1); the jnp oracle streams
    3 per pivot (ticks 3Q)."""
    out, plan = dispatch.run_fused_select_multi(
        x, pivots, cap, backend=_backend(backend, use_pallas))
    _tick(1 if plan.backend.kind == "pallas" else 3 * int(pivots.shape[0]))
    return out


def segmented_count_extract(values: jax.Array, keys: jax.Array,
                            pivots: jax.Array, cap: int, *,
                            use_pallas: bool = True, backend=None):
    """The grouped engine's phase 3: per-group counts plus both capped
    candidate bands for every (group, level) pivot — ``(counts (G, Q, 3),
    below (G, Q, cap), above (G, Q, cap))`` with the exact semantics of
    ``local_ops.grouped_count_extract``.  A Pallas backend streams the
    shard ONCE for the whole matrix (ticks 1); the jnp oracle costs 3 per
    (group, level) and ticks 3*G*Q."""
    G, Q = pivots.shape
    out, plan = dispatch.run_segmented_select(
        values, keys, pivots, cap, backend=_backend(backend, use_pallas))
    _tick(1 if plan.backend.kind == "pallas" else 3 * int(G) * int(Q))
    return out


# ---------------------------------------------------------------------------
# sortable-uint transform + radix (byte-histogram) selection
# ---------------------------------------------------------------------------


def to_sortable_u32(x: jax.Array) -> jax.Array:
    """Order-preserving map into uint32 (classic radix-sort float trick)."""
    if x.dtype == jnp.int32:
        return x.view(jnp.uint32) ^ jnp.uint32(0x80000000)
    if x.dtype in (jnp.bfloat16, jnp.float16):
        x = x.astype(jnp.float32)
    if x.dtype != jnp.float32:
        raise TypeError(f"unsupported dtype {x.dtype}")
    b = x.view(jnp.int32)
    m = (b >> 31).view(jnp.uint32) | jnp.uint32(0x80000000)
    return b.view(jnp.uint32) ^ m


def from_sortable_u32(u: jax.Array, dtype) -> jax.Array:
    """Inverse of to_sortable_u32 (f32/int32 targets)."""
    if dtype == jnp.int32:
        return (u ^ jnp.uint32(0x80000000)).view(jnp.int32)
    neg = (u & jnp.uint32(0x80000000)) == 0
    b = jnp.where(neg, ~u, u ^ jnp.uint32(0x80000000))
    return b.view(jnp.float32)


def byte_histogram(x_or_u: jax.Array, prefix, mask, *, shift: int,
                   use_pallas: bool = True, backend=None) -> jax.Array:
    """(256,) histogram of byte ``(u >> shift) & 0xFF`` among the uint32
    elements matching ``(u & mask) == prefix``.  One HBM pass on every
    backend.  The input must already be in the sortable-u32 domain."""
    _tick()
    out, _ = dispatch.run_byte_histogram(
        x_or_u, prefix, mask, shift, backend=_backend(backend, use_pallas))
    return out


RADIX_PASSES = 4   # 32 bits / 8 bits per byte-histogram pass


def radix_select_kth(x: jax.Array, k, *, use_pallas: bool = True,
                     backend=None) -> jax.Array:
    """Exact k-th smallest (1-based) of a flat array in exactly 4 streaming
    histogram passes — no sort, no top_k, no data movement.

    Each pass pins one byte of the answer: histogram the next byte within
    the prefix group fixed so far, walk the cumulative counts to the bin
    containing rank k, descend.  8 bits per pass -> 4 passes for uint32,
    vs <= 32 for the bit-at-a-time binary search it replaces
    (``radix_select_kth_bitwise``).

    The win is HBM traffic (8x fewer full-array reads), which is the TPU
    cost model; the jnp-backend histogram is also one pass, so the 4-pass
    structure holds on every backend.  Under Pallas *interpret mode* the
    256-bin one-hot histogram is emulated compute and wall-clock is worse
    than the bitwise path — see bench_fused — so benchmarking on a CPU
    container should read the pass counts, not the microseconds."""
    orig_dtype = x.dtype
    u = to_sortable_u32(x.ravel())
    bk = _backend(backend, use_pallas)

    prefix = jnp.uint32(0)
    mask = jnp.uint32(0)
    kk = jnp.asarray(k, jnp.int32)
    for shift in (24, 16, 8, 0):
        _tick()
        hist, _ = dispatch.run_byte_histogram(u, prefix, mask, shift,
                                              backend=bk)
        csum = jnp.cumsum(hist)
        byte = jnp.argmax(csum >= kk).astype(jnp.uint32)
        kk = kk - (csum[byte] - hist[byte])
        prefix = prefix | (byte << jnp.uint32(shift))
        mask = mask | jnp.uint32(0xFF << shift)

    out_dtype = jnp.int32 if orig_dtype == jnp.int32 else jnp.float32
    val = from_sortable_u32(prefix, out_dtype)
    return val.astype(orig_dtype)


@functools.partial(jax.jit, static_argnames=("n", "use_pallas", "interpret"))
def _bitwise_inner(u2d: jax.Array, u_flat: jax.Array, k, *, n: int,
                   use_pallas: bool, interpret: bool):
    def count_le(t):
        if use_pallas:
            c = partition_count(u2d, t, n_valid=n, interpret=interpret)
        else:
            c = ref.partition_count_ref(u_flat, t)
        return c[0] + c[1]

    def body(_, state):
        lo, hi = state
        mid = lo + (hi - lo) // jnp.uint32(2)
        le = count_le(mid)
        lo2 = jnp.where(le >= k, lo, mid + jnp.uint32(1))
        hi2 = jnp.where(le >= k, mid, hi)
        return lo2, hi2

    lo0 = jnp.uint32(0)
    hi0 = jnp.uint32(0xFFFFFFFF)
    lo, _ = jax.lax.fori_loop(0, 32, body, (lo0, hi0))
    return lo


def radix_select_kth_bitwise(x: jax.Array, k, *, use_pallas: bool = True,
                             backend=None) -> jax.Array:
    """The pre-fused selection: bit-at-a-time binary search over the
    sortable-u32 domain, one counting pass per bit (<= 32 passes).  Kept as
    the benchmark baseline for the 4-pass byte-histogram select."""
    _tick(32)
    bk = dispatch.resolve(_backend(backend, use_pallas))
    orig_dtype = x.dtype
    u = to_sortable_u32(x.ravel())
    u2d = pad_to_tiles(u)
    lo = _bitwise_inner(u2d, u, jnp.asarray(k, jnp.int32), n=u.size,
                        use_pallas=(bk.kind == "pallas"),
                        interpret=bk.interpret)
    out_dtype = jnp.int32 if orig_dtype == jnp.int32 else jnp.float32
    val = from_sortable_u32(lo, out_dtype)
    return val.astype(orig_dtype)


# ---------------------------------------------------------------------------
# injection hooks for core.distributed / core.select
# ---------------------------------------------------------------------------


def make_count3_fn(use_pallas: bool = True, backend=None):
    """count3 injection hook for ``gk_select_sharded`` (same signature as
    local_ops.count3).  ``backend`` is the dispatch handle the seam closes
    over (None = select per platform at trace time)."""
    def fn(x, pivot):
        return count3(x, pivot, use_pallas=use_pallas, backend=backend)
    return fn


def make_fused_fn(use_pallas: bool = True, backend=None):
    """fused_fn injection hook for ``gk_select_sharded``'s speculative
    phase (same signature as ``local_ops.fused_count_extract``): the whole
    count+extract round becomes one stream per shard on a Pallas backend;
    the closed-over ``backend`` handle replaces the old interpret booleans
    at the seam."""
    def fn(x, pivot, cap):
        return fused_count_extract(x, pivot, cap, use_pallas=use_pallas,
                                   backend=backend)
    return fn


def make_segmented_fn(use_pallas: bool = True, backend=None):
    """segmented_fn injection hook for ``gk_select_grouped_sharded``: the
    whole (G, Q)-pivot grouped count+extract phase in one dispatch
    (``(values, keys, pivots, cap) -> (counts (G,Q,3), below (G,Q,cap),
    above (G,Q,cap))``)."""
    def fn(values, keys, pivots, cap):
        return segmented_count_extract(values, keys, pivots, cap,
                                       use_pallas=use_pallas,
                                       backend=backend)
    return fn


def make_fused_multi_fn(use_pallas: bool = True, backend=None):
    """fused_fn injection hook for ``gk_select_multi_sharded``: the whole
    Q-pivot count+extract phase in one dispatch
    (``(x, pivots, cap) -> (counts (Q,3), below (Q,cap), above (Q,cap))``)."""
    def fn(x, pivots, cap):
        return fused_count_extract_multi(x, pivots, cap,
                                         use_pallas=use_pallas,
                                         backend=backend)
    return fn
