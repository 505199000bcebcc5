"""Backend dispatch for the kernel layer (docs/PERFORMANCE.md).

Every kernel in this package has three implementations of the same
semantics (defined by the oracles in ``ref.py``):

  * Pallas compiled   — the TPU (and, speculatively, Triton-GPU) lowering
                        of the streaming kernels: one HBM->VMEM sweep with
                        double-buffered tiles and SMEM/VMEM accumulators.
  * Pallas interpret  — the identical jaxpr executed on CPU; bit-exact with
                        the compiled kernel, but every "VMEM" tile merge is
                        emulated compute, so wall-clock is MUCH slower than
                        plain jnp on this path.  Its job is CI parity, not
                        speed.
  * jnp fallback      — the jitted oracle.  On CPU this is the fast path
                        (XLA:CPU vectorizes it); it streams the array once
                        per logical pass (3 per pivot for the fused trio),
                        which the HBM-pass counter in ``ops.py`` reports
                        honestly.

This module is the registry that picks between them *per platform at trace
time* and sizes the Pallas grid/BlockSpec tiling from dtype + array size:

  ``select_backend()``      platform -> Backend (env-overridable)
  ``plan(...)``             (backend, kernel, dtype, n, footprint) ->
                            LaunchPlan: lanes, block_rows, VMEM-budget
                            check (a compiled backend refuses a kernel
                            that does not fit; the interpreter warns and
                            falls back to jnp)
  ``run_<kernel>(...)``     execute under a plan, returning
                            ``(outputs, plan)`` so callers can account
                            passes and record tile configs

Selection rules (see docs/PERFORMANCE.md for the tables):

  platform "tpu"            -> pallas_tpu   (compiled, 16 MiB VMEM budget)
  platform "gpu"/"cuda"/...  -> pallas_gpu  (compiled; falls back to jnp at
                                            first launch failure — the
                                            Triton lowering of these
                                            TPU-flavoured kernels is gated,
                                            not assumed)
  platform "cpu"            -> jnp          (the wall-clock winner there)

Env overrides: ``REPRO_BACKEND`` in {"pallas_tpu", "pallas_gpu",
"pallas_interpret", "interpret", "pallas", "native", "jnp", "auto"};
the legacy ``REPRO_PALLAS_NATIVE=1`` maps to "pallas".  Overrides are read
at trace time — flip them before the first call, not between jit replays.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import os
import warnings

import jax
import jax.numpy as jnp

from . import ref
from .partition_count import partition_count
from .band_count import band_count as _band_count_kernel
from .fused_select import (fused_select, fused_select_multi,
                           select_vmem_bytes,
                           byte_histogram as _byte_histogram_kernel)
from .segmented_select import segmented_select

KiB = 1024
MiB = 1024 * KiB


@dataclasses.dataclass(frozen=True)
class Backend:
    """One executable target for the kernel layer.

    name         registry key (also what ``plan.backend.name`` reports)
    kind         "pallas" (real kernels) or "jnp" (jitted oracles)
    interpret    pallas_call(interpret=...) flag for pallas kinds
    compiled     True when the backend runs machine code worth timing —
                 the bench's wall-clock-win assertion only fires here
    vmem_budget  bytes of fast memory the plan may assume for tiles +
                 resident accumulators (TPU VMEM / GPU shared-memory-ish)
    tile_bytes   target size of one streamed input tile (the BlockSpec
                 sizing knob; actual tiles shrink to fit the budget)
    """
    name: str
    kind: str
    interpret: bool
    compiled: bool
    vmem_budget: int
    tile_bytes: int


PALLAS_TPU = Backend("pallas_tpu", "pallas", interpret=False, compiled=True,
                     vmem_budget=16 * MiB, tile_bytes=512 * KiB)
PALLAS_GPU = Backend("pallas_gpu", "pallas", interpret=False, compiled=True,
                     vmem_budget=8 * MiB, tile_bytes=128 * KiB)
PALLAS_INTERPRET = Backend("pallas_interpret", "pallas", interpret=True,
                           compiled=False, vmem_budget=16 * MiB,
                           tile_bytes=512 * KiB)
JNP = Backend("jnp", "jnp", interpret=False, compiled=True,
              vmem_budget=1 << 62, tile_bytes=1 << 62)

BACKENDS = {b.name: b for b in (PALLAS_TPU, PALLAS_GPU, PALLAS_INTERPRET,
                                JNP)}

_GPU_PLATFORMS = ("gpu", "cuda", "rocm")

# kernels whose pallas_gpu launch failed once: gated to jnp from then on
_GPU_BROKEN: dict = {}

# the open ``record_plans`` list, if any
_RECORD = None


@contextlib.contextmanager
def record_plans():
    """Collect ``(kernel, LaunchPlan)`` of every run_* call made inside
    the block, from any thread; a call traced inside a jit records once,
    at trace time.  ``chip_smoke.py`` checks with it that a kernel phase
    really ran a Pallas plan.  Outside the block nothing is kept."""
    global _RECORD
    outer, _RECORD = _RECORD, []
    try:
        yield _RECORD
    finally:
        _RECORD = outer


def _platform(platform: str | None) -> str:
    return (platform or jax.default_backend()).lower()


def _resolve_spec(spec: str, platform: str) -> Backend:
    spec = spec.strip().lower()
    if spec in BACKENDS:
        return BACKENDS[spec]
    if spec == "interpret":
        return PALLAS_INTERPRET
    if spec in ("pallas", "native"):
        # the pallas kernels, compiled where the platform can, interpret
        # elsewhere — what kernel-contract tests and benches pin
        if platform == "tpu":
            return PALLAS_TPU
        if platform in _GPU_PLATFORMS:
            return PALLAS_GPU
        return PALLAS_INTERPRET
    if spec in ("auto", ""):
        return _platform_default(platform)
    raise ValueError(
        f"unknown backend {spec!r}: expected one of "
        f"{sorted(BACKENDS)} or an alias in "
        f"('pallas', 'native', 'interpret', 'auto')")


def _platform_default(platform: str) -> Backend:
    if platform == "tpu":
        return PALLAS_TPU
    if platform in _GPU_PLATFORMS:
        return PALLAS_GPU
    return JNP


def select_backend(platform: str | None = None) -> Backend:
    """The backend the kernel layer uses when the caller names none.

    Honors ``REPRO_BACKEND`` (and the legacy ``REPRO_PALLAS_NATIVE=1``,
    which means "run the pallas kernels natively"); otherwise maps the
    platform: tpu -> pallas_tpu, gpu -> pallas_gpu, cpu -> jnp.
    """
    platform = _platform(platform)
    spec = os.environ.get("REPRO_BACKEND", "").strip()
    if not spec and os.environ.get("REPRO_PALLAS_NATIVE", "0") == "1":
        spec = "pallas"
    if spec:
        return _resolve_spec(spec, platform)
    return _platform_default(platform)


def resolve(backend=None, platform: str | None = None) -> Backend:
    """Normalize a user-facing backend spec (None | str | Backend)."""
    if backend is None:
        return select_backend(platform)
    if isinstance(backend, Backend):
        return backend
    return _resolve_spec(str(backend), _platform(platform))


# ---------------------------------------------------------------------------
# tiling: dtype-specialized lanes + VMEM-budgeted block rows
# ---------------------------------------------------------------------------

LANE_MULTIPLE = 128     # VREG lane width every trailing dim must respect


def lanes_for(dtype) -> int:
    """Trailing-dim width of the streamed layout for this dtype.

    A TPU vector register row is 512 bytes wide per sublane group
    (128 lanes x 4 B); 2-byte dtypes pack two elements per f32 lane slot,
    so bf16/f16/int16 stream 2048-element rows and stop paying the f32
    path's padding (1-byte dtypes would pack 4096).
    """
    itemsize = jnp.dtype(dtype).itemsize
    if itemsize == 1:
        return 4096
    if itemsize == 2:
        return 2048
    return 1024


def sublanes_for(dtype) -> int:
    """Rows of one native (sublanes, 128) tile: 8 for 32-bit dtypes, 16
    for 2-byte, 32 for 1-byte.  Streamed row counts and block rows are
    multiples of it."""
    return 8 * max(1, 4 // jnp.dtype(dtype).itemsize)


def pad_to_lanes(x: jax.Array, lanes: int, sublanes: int = None) -> jax.Array:
    """Flat -> (rows, lanes) row-major, rows rounded up to ``sublanes``
    (default: the dtype's sublane tile), zero-padded at the tail (pad
    values are masked by ``n_valid`` inside the kernels)."""
    n = x.size
    sub = sublanes or sublanes_for(x.dtype)
    rows = max(1, -(-n // lanes))
    rows = -(-rows // sub) * sub
    pad = rows * lanes - n
    if pad:
        x = jnp.concatenate([x.ravel(), jnp.zeros((pad,), x.dtype)])
    return x.reshape(rows, lanes)


@dataclasses.dataclass(frozen=True)
class LaunchPlan:
    """The resolved execution recipe for one kernel call.

    ``backend`` is the backend that will actually run (it may differ from
    the requested one when the interpreter's VMEM-budget check or the GPU
    gate fell back to jnp — ``reason`` says why, and a warning is raised).
    ``lanes``/``block_rows`` are 0 on jnp plans (no tiling).
    ``vmem_bytes`` is the footprint the plan budgeted: residents plus
    double-buffered streamed tiles (plus the select kernels' work
    buffers).  Kernels run under the compiler's default VMEM limit.
    """
    backend: Backend
    lanes: int = 0
    block_rows: int = 0
    vmem_bytes: int = 0
    reason: str = ""


def plan(backend, kernel: str, dtype, n: int, *,
         footprint=None) -> LaunchPlan:
    """Size the grid for one kernel call.

    ``footprint(block_rows, lanes)`` is the kernel's VMEM need in bytes;
    the default is one double-buffered streamed tile (the counting
    kernels).  If even the minimum tile does not fit the backend's VMEM
    budget, a compiled backend raises ``ValueError`` naming the footprint
    and the budget (the caller asked for the kernel; running jnp instead
    would hide that it did not run), and the interpreter, which has no
    VMEM, warns and falls back to jnp — ``reason`` records the verdict.
    """
    backend = resolve(backend)
    if backend.kind == "jnp":
        return LaunchPlan(JNP)
    if backend.name == "pallas_gpu" and kernel in _GPU_BROKEN:
        return LaunchPlan(JNP, reason=_GPU_BROKEN[kernel])

    itemsize = jnp.dtype(dtype).itemsize
    lanes = lanes_for(dtype)
    min_rows = sublanes_for(dtype)
    rows = -(-max(1, -(-int(n) // lanes)) // min_rows) * min_rows
    row_bytes = lanes * itemsize

    if footprint is None:
        def footprint(block_rows: int, lanes_: int) -> int:
            return 2 * block_rows * row_bytes

    need = footprint(min_rows, lanes)
    if need > backend.vmem_budget:
        verdict = (f"{kernel}: the minimum tile needs {need}B and would "
                   f"exceed {backend.name} VMEM budget "
                   f"{backend.vmem_budget}B")
        if backend.compiled:
            raise ValueError(
                f"{verdict}; lower the candidate cap (a smaller eps) or "
                f"pass backend='jnp'")
        reason = f"{verdict} — fell back to jnp"
        warnings.warn(reason, RuntimeWarning, stacklevel=2)
        return LaunchPlan(JNP, reason=reason)

    target_rows = max(min_rows, backend.tile_bytes // row_bytes)
    block_rows = 1 << (int(target_rows).bit_length() - 1)   # pow2 floor
    while block_rows > min_rows and \
            footprint(block_rows, lanes) > backend.vmem_budget:
        block_rows //= 2
    block_rows = min(block_rows, rows)
    return LaunchPlan(backend, lanes=lanes, block_rows=block_rows,
                      vmem_bytes=footprint(block_rows, lanes))


def _select_footprint(dtype, num_rows: int, cap_pad: int, streams: int = 1):
    """``plan`` footprint of the band-select kernels (fused_select*,
    segmented_select): their running buffers and work buffer scale with
    the pivot rows, ``cap_pad`` and the tile."""
    def footprint(block_rows: int, lanes: int) -> int:
        return select_vmem_bytes(dtype, num_rows, cap_pad, block_rows,
                                 lanes, streams)
    return footprint


def cap_pad_for(cap: int) -> int:
    """Candidate-buffer lanes rounded up to the VREG lane multiple."""
    return max(LANE_MULTIPLE, -(-cap // LANE_MULTIPLE) * LANE_MULTIPLE)


def _gate(plan_: LaunchPlan, kernel: str, pallas_thunk, jnp_thunk):
    """Run the planned implementation; gate pallas_gpu failures to jnp.

    The pallas kernels here are written against the TPU memory spaces
    (SMEM scalars, revisited VMEM output blocks).  On a GPU the Triton
    lowering of that flavour may simply not exist in this jax version, so
    the first failure per kernel is caught, memoized (future ``plan()``
    calls return a jnp plan directly), and the jnp oracle answers instead.
    TPU/interpret failures are real bugs and propagate.
    """
    if _RECORD is not None:
        _RECORD.append((kernel, plan_))
    if plan_.backend.kind != "pallas":
        return jnp_thunk()
    try:
        return pallas_thunk()
    except Exception as e:  # noqa: BLE001 — the lowering can fail anywhere
        if plan_.backend.name == "pallas_gpu":
            _GPU_BROKEN[kernel] = (f"{kernel}: pallas_gpu launch failed "
                                   f"({type(e).__name__}); gated to jnp")
            warnings.warn(_GPU_BROKEN[kernel], RuntimeWarning, stacklevel=3)
            return jnp_thunk()
        raise


# ---------------------------------------------------------------------------
# jitted jnp fallbacks (the oracles, compiled once per shape/cap)
# ---------------------------------------------------------------------------

_jnp_partition_count = jax.jit(ref.partition_count_ref)
_jnp_band_count = jax.jit(ref.band_count_ref)


@functools.partial(jax.jit, static_argnames=("cap",))
def _jnp_fused_select(x, pivot, cap):
    return ref.fused_select_ref(x, pivot, cap)


@functools.partial(jax.jit, static_argnames=("cap",))
def _jnp_fused_select_multi(x, pivots, cap):
    counts, below, above = jax.vmap(
        lambda p: ref.fused_select_ref(x, p, cap))(pivots)
    return counts, below, above


@functools.partial(jax.jit, static_argnames=("cap",))
def _jnp_segmented_select(values, keys, pivots, cap):
    return ref.segmented_select_ref(values, keys, pivots, cap)


@functools.partial(jax.jit, static_argnames=("shift",))
def _jnp_byte_histogram(u, prefix, mask, shift):
    # bincount scatter-add — ref.byte_histogram_ref semantics without the
    # oracle's (n, 256) one-hot, which is ~5x slower than even the
    # interpret-mode kernel on CPU; non-matching elements land in the
    # overflow bin 256, which the slice drops
    u = u.ravel()
    match = (u & jnp.uint32(mask)) == jnp.uint32(prefix)
    byte = ((u >> jnp.uint32(shift)) & jnp.uint32(0xFF)).astype(jnp.int32)
    byte = jnp.where(match, byte, jnp.int32(256))
    return jnp.bincount(byte, length=257)[:256].astype(jnp.int32)


# ---------------------------------------------------------------------------
# per-kernel entry points: plan, execute, return (outputs, plan)
# ---------------------------------------------------------------------------


def run_partition_count(x: jax.Array, pivot, *, backend=None):
    """(lt, eq, gt) int32 counts of flat ``x`` vs ``pivot``."""
    x = x.ravel()
    p = plan(backend, "partition_count", x.dtype, x.size)
    pivot = jnp.asarray(pivot, x.dtype)

    def _pallas():
        x2d = pad_to_lanes(x, p.lanes)
        return partition_count(x2d, pivot, n_valid=x.size,
                               block_rows=p.block_rows,
                               interpret=p.backend.interpret)

    return _gate(p, "partition_count", _pallas,
                 lambda: _jnp_partition_count(x, pivot)), p


def run_band_count(x: jax.Array, lo, hi, *, backend=None):
    """int32 count of flat ``x`` inside the open band (lo, hi)."""
    x = x.ravel()
    p = plan(backend, "band_count", x.dtype, x.size)
    lo = jnp.asarray(lo, x.dtype)
    hi = jnp.asarray(hi, x.dtype)

    def _pallas():
        x2d = pad_to_lanes(x, p.lanes)
        return _band_count_kernel(x2d, lo, hi, n_valid=x.size,
                                  block_rows=p.block_rows,
                                  interpret=p.backend.interpret)

    return _gate(p, "band_count", _pallas,
                 lambda: _jnp_band_count(x, lo, hi)), p


def run_fused_select(x: jax.Array, pivot, cap: int, *, backend=None):
    """One-pivot fused count+extract: ``(counts, below (cap,), above
    (cap,))`` with ``ref.fused_select_ref`` semantics."""
    x = x.ravel()
    cap_pad = cap_pad_for(cap)
    p = plan(backend, "fused_select", x.dtype, x.size,
             footprint=_select_footprint(x.dtype, 1, cap_pad))
    pivot = jnp.asarray(pivot, x.dtype)

    def _pallas():
        x2d = pad_to_lanes(x, p.lanes)
        counts, below, above = fused_select(
            x2d, pivot, n_valid=x.size, cap_pad=cap_pad,
            block_rows=p.block_rows, interpret=p.backend.interpret)
        return counts, below[:cap], above[:cap]

    return _gate(p, "fused_select", _pallas,
                 lambda: _jnp_fused_select(x, pivot, cap)), p


def run_fused_select_multi(x: jax.Array, pivots: jax.Array, cap: int, *,
                           backend=None):
    """Q-pivot fused count+extract: ``(counts (Q,3), below (Q,cap),
    above (Q,cap))``."""
    x = x.ravel()
    Q = int(pivots.shape[0])
    cap_pad = cap_pad_for(cap)
    p = plan(backend, "fused_select_multi", x.dtype, x.size,
             footprint=_select_footprint(x.dtype, Q, cap_pad))
    pivots = jnp.asarray(pivots, x.dtype)

    def _pallas():
        x2d = pad_to_lanes(x, p.lanes)
        counts, below, above = fused_select_multi(
            x2d, pivots, n_valid=x.size, cap_pad=cap_pad,
            block_rows=p.block_rows, interpret=p.backend.interpret)
        return counts, below[:, :cap], above[:, :cap]

    return _gate(p, "fused_select_multi", _pallas,
                 lambda: _jnp_fused_select_multi(x, pivots, cap)), p


def run_segmented_select(values: jax.Array, keys: jax.Array,
                         pivots: jax.Array, cap: int, *, backend=None):
    """(G, Q)-pivot grouped count+extract: ``(counts (G,Q,3),
    below (G,Q,cap), above (G,Q,cap))``."""
    values = values.ravel()
    G, Q = (int(d) for d in pivots.shape)
    cap_pad = cap_pad_for(cap)
    p = plan(backend, "segmented_select", values.dtype, values.size,
             footprint=_select_footprint(values.dtype, G * Q, cap_pad,
                                         streams=2))
    pivots = jnp.asarray(pivots, values.dtype)
    keys = keys.ravel().astype(jnp.int32)

    def _pallas():
        x2d = pad_to_lanes(values, p.lanes)
        k2d = pad_to_lanes(keys, p.lanes, sublanes_for(values.dtype))
        counts, below, above = segmented_select(
            x2d, k2d, pivots, n_valid=values.size, cap_pad=cap_pad,
            num_groups=G, block_rows=p.block_rows,
            interpret=p.backend.interpret)
        return counts, below[:, :, :cap], above[:, :, :cap]

    return _gate(p, "segmented_select", _pallas,
                 lambda: _jnp_segmented_select(values, keys, pivots,
                                               cap)), p


def run_byte_histogram(u: jax.Array, prefix, mask, shift: int, *,
                       backend=None):
    """(256,) histogram of byte ``(u >> shift) & 0xFF`` among elements
    matching ``(u & mask) == prefix`` (sortable-u32 domain)."""
    u = u.ravel()
    if u.dtype != jnp.uint32:
        raise TypeError(f"byte_histogram wants sortable uint32, got "
                        f"{u.dtype}")
    p = plan(backend, "byte_histogram", u.dtype, u.size)
    prefix = jnp.asarray(prefix, jnp.uint32)
    mask = jnp.asarray(mask, jnp.uint32)

    def _pallas():
        u2d = pad_to_lanes(u, p.lanes)
        return _byte_histogram_kernel(u2d, prefix, mask, n_valid=u.size,
                                      shift=shift, block_rows=p.block_rows,
                                      interpret=p.backend.interpret)

    return _gate(p, "byte_histogram", _pallas,
                 lambda: _jnp_byte_histogram(u, prefix, mask, shift)), p
