"""Pallas TPU kernels: single-pass fused band extraction.

The paper's speed claim hinges on Step 7 being *one* linear scan per
partition: "extract all values within the error bound around this pivot
in each partition in linear time".  The unfused executor pipeline streams
each shard three times (``count3`` + two whole-array ``top_k`` extractions);
on a bandwidth-bound workload HBM passes *are* the cost model, so this
module collapses the trio into one HBM->VMEM sweep:

``fused_select``        — one grid pass emits the 3-way (lt, eq, gt) counts
                          AND both capped candidate buffers (the ``cap``
                          largest values < pivot and ``cap`` smallest
                          > pivot).  3 passes -> 1.
``fused_select_multi``  — the same sweep answering Q pivots at once: the
                          tile is loaded into VMEM once and scored against
                          every pivot.  3Q passes -> 1.
``byte_histogram``      — 256-bin histogram of one byte of the sortable-u32
                          transform, restricted to a value-prefix group;
                          turns ``ops.radix_select_kth`` from <=32
                          bit-at-a-time passes into 4 byte passes.

Selection strategy (DESIGN.md §2): each output buffer is a running
selection of ``C`` lanes (``cap_pad`` rounded up to a power of two of at
least one vreg) kept sorted descending in the revisited VMEM output block.
A grid step that has a candidate able to enter the buffer copies the
tile's masked candidates into a VMEM work buffer, bitonic-sorts it
ascending in chunks of ``min(tile, C)``, and folds each chunk in: the
elementwise max of the buffer's tail (descending) and the chunk
(ascending) is the top of their union as a bitonic sequence, which one
bitonic merge sorts again.  The network is built from ``minimum`` /
``maximum`` and lane/sublane rolls only, which Mosaic lowers; interpret
mode runs the same jaxpr on CPU.  The "smallest above" side is kept as the
largest of order-reversed values (``-x`` for floats, ``~x`` for ints), so
one network serves both sides.  HBM traffic stays one read of the shard
plus O(C) writeback.

Layout contract is shared with ``partition_count``: callers pad the flat
shard to (rows, lanes) row-major — lanes any positive multiple of 128,
rows a multiple of the dtype's sublane tile (``dispatch.pad_to_lanes``) —
and pass the true length as ``n_valid``; ``cap_pad`` must be a positive
multiple of 128 (the dispatch layer rounds up and slices back down).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .partition_count import (DEFAULT_BLOCK_ROWS, check_lanes,
                              COMPILER_PARAMS, work_dtype)

SUBLANES = 8
LANE = 128
VREG = SUBLANES * LANE       # 32-bit elements in one vector register


def _sentinels(dtype):
    """(lowest, highest) padding sentinels matching local_ops semantics."""
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.array(-jnp.inf, dtype), jnp.array(jnp.inf, dtype)
    info = jnp.iinfo(dtype)
    return jnp.array(info.min, dtype), jnp.array(info.max, dtype)


def _flip(v):
    """Order-reversing bijection of the work dtype (maps each sentinel onto
    the other): the smallest values above a pivot are the largest flipped
    values."""
    if jnp.issubdtype(v.dtype, jnp.floating):
        return -v
    return ~v


def _pow2_at_least(n: int) -> int:
    return 1 << max(0, int(n) - 1).bit_length()


def buffer_lanes(cap_pad: int) -> int:
    """Lanes of one running candidate buffer: ``cap_pad`` rounded up to a
    power of two of at least one vreg (the bitonic network's size)."""
    return max(VREG, _pow2_at_least(cap_pad))


def _log2(n: int) -> int:
    return int(n).bit_length() - 1


# ---------------------------------------------------------------------------
# bitonic network on a (rows, 128) VMEM ref; element i sits at row i // 128,
# lane i % 128.  A stage (j, kk, desc) compare-exchanges i with i ^ j; the
# pair is ordered ascending when bit kk of i (0 = no bit) XOR desc is 0.
# ---------------------------------------------------------------------------


def _vreg_pass(buf, nrows: int, stages):
    """Apply stages with j < VREG (partners inside one vreg) to every vreg
    of ``buf[:nrows]``: lane rolls for j < 128, sublane rolls above."""
    idx = (jax.lax.broadcasted_iota(jnp.int32, (SUBLANES, LANE), 0) * LANE
           + jax.lax.broadcasted_iota(jnp.int32, (SUBLANES, LANE), 1))

    def body(g, carry):
        r0 = pl.multiple_of(g * SUBLANES, SUBLANES)
        v = buf[pl.ds(r0, SUBLANES), :]
        flat = idx + g * VREG
        for j, kk, desc in stages:
            if j < LANE:
                shifts, axis = (LANE - j, j), 1
            else:
                shifts, axis = (SUBLANES - j // LANE, j // LANE), 0
            up = pltpu.roll(v, shifts[0], axis)
            down = pltpu.roll(v, shifts[1], axis)
            # the roll that brings element i ^ j to position i, found by
            # rolling the index itself: correct for either rotate direction
            from_up = pltpu.roll(idx, shifts[0], axis) == (idx ^ j)
            partner = jnp.where(from_up, up, down)
            bit_j = (flat >> _log2(j)) & 1
            order = (bit_j ^ ((flat >> _log2(kk)) & 1)) if kk else bit_j
            take_min = order == int(desc)
            v = jnp.where(take_min, jnp.minimum(v, partner),
                          jnp.maximum(v, partner))
        buf[pl.ds(r0, SUBLANES), :] = v
        return carry

    jax.lax.fori_loop(0, nrows // SUBLANES, body, 0)


def _cross_pass(buf, nrows: int, j: int, kk: int, desc: bool):
    """One stage with j >= VREG: whole vregs compare-exchange in pairs."""
    jg = j // VREG

    def body(p, carry):
        g = (p // jg) * (2 * jg) + p % jg
        r0 = pl.multiple_of(g * SUBLANES, SUBLANES)
        r1 = pl.multiple_of((g + jg) * SUBLANES, SUBLANES)
        a = buf[pl.ds(r0, SUBLANES), :]
        b = buf[pl.ds(r1, SUBLANES), :]
        small, big = jnp.minimum(a, b), jnp.maximum(a, b)
        if kk:
            order = (((g * VREG) >> _log2(kk)) & 1) ^ int(desc)
            swap = jnp.full((SUBLANES, LANE), order, jnp.int32) == 1
            small, big = (jnp.where(swap, big, small),
                          jnp.where(swap, small, big))
        elif desc:
            small, big = big, small
        buf[pl.ds(r0, SUBLANES), :] = small
        buf[pl.ds(r1, SUBLANES), :] = big
        return carry

    jax.lax.fori_loop(0, nrows // (2 * SUBLANES), body, 0)


def _halvings(j: int):
    out = []
    while j >= 1:
        out.append(j)
        j //= 2
    return out


def _sort_chunks_ascending(buf, nrows: int, chunk: int):
    """Bitonic-sort every ``chunk``-element block of ``buf[:nrows]``
    ascending (chunk a power of two, VREG <= chunk <= nrows * 128)."""
    def kk_of(k):
        return k & (chunk - 1)          # 0 at k == chunk: all ascending

    first = []
    k = 2
    while k <= VREG:
        first += [(j, kk_of(k), False) for j in _halvings(k // 2)]
        k *= 2
    _vreg_pass(buf, nrows, first)
    k = 2 * VREG
    while k <= chunk:
        for j in _halvings(k // 2):
            if j < VREG:
                break
            _cross_pass(buf, nrows, j, kk_of(k), False)
        _vreg_pass(buf, nrows,
                   [(j, kk_of(k), False) for j in _halvings(VREG // 2)])
        k *= 2


def _merge_descending(buf, nrows: int):
    """Sort the bitonic sequence ``buf[:nrows]`` descending."""
    for j in _halvings(nrows * LANE // 2):
        if j < VREG:
            break
        _cross_pass(buf, nrows, j, 0, True)
    _vreg_pass(buf, nrows, [(j, 0, True) for j in _halvings(VREG // 2)])


def _fold_into(buf, buf_rows: int, work, work_rows: int, chunk_rows: int):
    """Merge the ascending chunks of ``work`` into the descending ``buf``,
    keeping its ``buf_rows * 128`` largest values."""
    tail = buf_rows - chunk_rows

    def chunk_body(ch, carry):
        def vmax(i, c):
            rb = pl.multiple_of(tail + i * SUBLANES, SUBLANES)
            rw = pl.multiple_of(ch * chunk_rows + i * SUBLANES, SUBLANES)
            buf[pl.ds(rb, SUBLANES), :] = jnp.maximum(
                buf[pl.ds(rb, SUBLANES), :], work[pl.ds(rw, SUBLANES), :])
            return c

        jax.lax.fori_loop(0, chunk_rows // SUBLANES, vmax, 0)
        _merge_descending(buf, buf_rows)
        return carry

    jax.lax.fori_loop(0, work_rows // chunk_rows, chunk_body, 0)


# ---------------------------------------------------------------------------
# the one band-select kernel: R pivot rows (optionally keyed to groups)
# ---------------------------------------------------------------------------


def _band_select_kernel(pivots_ref, x_ref, *refs, n_valid: int,
                        block_rows: int, num_rows: int, num_levels: int,
                        keyed: bool, buf_rows: int, work_rows: int,
                        chunk_rows: int):
    """One grid step: the tile is resident once; every pivot row r scores
    it (restricted to ``keys == r // num_levels`` when keyed), adds its
    3-way counts to SMEM and folds its candidates into row r of both
    running buffers.  A side with no candidate able to enter its buffer
    skips the network."""
    if keyed:
        keys_ref, count_ref, below_ref, above_ref, work = refs
    else:
        keys_ref = None
        count_ref, below_ref, above_ref, work = refs
    step = pl.program_id(0)
    wd = below_ref.dtype
    lowest = _sentinels(wd)[0]
    lanes = x_ref.shape[1]
    slabs = lanes // LANE

    @pl.when(step == 0)
    def _init():
        below_ref[...] = jnp.full(below_ref.shape, lowest, wd)
        above_ref[...] = jnp.full(above_ref.shape, lowest, wd)

        def zero(r, carry):
            for c in range(3):
                count_ref[r, c] = jnp.int32(0)
            return carry

        jax.lax.fori_loop(0, num_rows, zero, 0)

    def slab(c, group):
        """Column slab c of the tile in the work dtype + its member mask."""
        x = x_ref[:, c * LANE:(c + 1) * LANE].astype(wd)
        row = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
        col = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
        member = (step * block_rows * lanes + row * lanes + c * LANE
                  + col) < n_valid
        if keyed:
            member = member & (keys_ref[:, c * LANE:(c + 1) * LANE] == group)
        return x, member

    def total(mask):
        return jnp.sum(jnp.where(mask, 1, 0), dtype=jnp.int32)

    def side(buf, pivot, group, below: bool):
        floor = jnp.min(buf[pl.ds(buf_rows - SUBLANES, SUBLANES), :])

        def candidates(c):
            x, member = slab(c, group)
            band = (x < pivot) if below else (x > pivot)
            key = x if below else _flip(x)
            return member & band & (key > floor), key

        entering = sum(total(candidates(c)[0]) for c in range(slabs))

        @pl.when(entering > 0)
        def _merge():
            for c in range(slabs):
                take, key = candidates(c)
                work[c * block_rows:(c + 1) * block_rows, :] = jnp.where(
                    take, key, lowest)
            if slabs * block_rows < work_rows:
                work[slabs * block_rows:, :] = jnp.full(
                    (work_rows - slabs * block_rows, LANE), lowest, wd)
            _sort_chunks_ascending(work, work_rows, chunk_rows * LANE)
            _fold_into(buf, buf_rows, work, work_rows, chunk_rows)

    def row_body(r, carry):
        pivot = pivots_ref[r]
        group = r // num_levels
        lt = eq = gt = jnp.int32(0)
        for c in range(slabs):
            x, member = slab(c, group)
            lt += total(member & (x < pivot))
            eq += total(member & (x == pivot))
            gt += total(member & (x > pivot))
        count_ref[r, 0] += lt
        count_ref[r, 1] += eq
        count_ref[r, 2] += gt
        side(below_ref.at[r], pivot, group, True)
        side(above_ref.at[r], pivot, group, False)
        return carry

    jax.lax.fori_loop(0, num_rows, row_body, 0)


def select_vmem_bytes(dtype, num_rows: int, cap_pad: int, block_rows: int,
                      lanes: int, streams: int = 1) -> int:
    """VMEM the band-select kernel needs: double-buffered input tiles, the
    two double-buffered (rows, C) running buffers, the work buffer, and
    room for two tile-sized temporaries — all in the 32-bit work dtype
    except the streamed input."""
    w = max(4, jnp.dtype(work_dtype(dtype)).itemsize)
    tile = block_rows * lanes
    work = max(VREG, _pow2_at_least(tile))
    return (2 * streams * tile * jnp.dtype(dtype).itemsize
            + 2 * 2 * num_rows * buffer_lanes(cap_pad) * w
            + work * w + 2 * tile * w)


def band_select(x2d: jax.Array, pivots: jax.Array, keys2d=None, *,
                n_valid: int, cap_pad: int, num_levels: int,
                interpret: bool, block_rows: int = DEFAULT_BLOCK_ROWS):
    """Shared launcher: one streaming pass answering every pivot in the
    flat ``pivots`` (R,) — row r restricted to ``keys2d == r // num_levels``
    when keys are given.  Returns ``(counts (R, 3), below (R, cap_pad),
    above (R, cap_pad))`` in ``x2d``'s dtype."""
    rows, lanes = x2d.shape
    check_lanes(lanes)
    if cap_pad <= 0 or cap_pad % 128:
        raise ValueError(f"cap_pad must be a positive multiple of 128, "
                         f"got {cap_pad}")
    dtype = x2d.dtype
    wd = work_dtype(dtype)
    num_rows = int(pivots.shape[0])
    block_rows = min(block_rows, rows)
    slab_rows = block_rows * (lanes // LANE)
    buf_rows = buffer_lanes(cap_pad) // LANE
    work_rows = max(SUBLANES, _pow2_at_least(slab_rows))
    chunk_rows = min(work_rows, buf_rows)
    grid = (pl.cdiv(rows, block_rows),)
    keyed = keys2d is not None
    kernel = functools.partial(
        _band_select_kernel, n_valid=n_valid, block_rows=block_rows,
        num_rows=num_rows, num_levels=num_levels, keyed=keyed,
        buf_rows=buf_rows, work_rows=work_rows, chunk_rows=chunk_rows)
    tile = pl.BlockSpec((block_rows, lanes), lambda i: (i, 0))
    buf = pl.BlockSpec((num_rows, buf_rows, LANE), lambda i: (0, 0, 0))
    buf_shape = jax.ShapeDtypeStruct((num_rows, buf_rows, LANE), wd)
    counts, below, above = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), tile]
                 + ([tile] if keyed else []),
        out_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), buf, buf],
        out_shape=[jax.ShapeDtypeStruct((num_rows, 3), jnp.int32),
                   buf_shape, buf_shape],
        scratch_shapes=[pltpu.VMEM((work_rows, LANE), wd)],
        interpret=interpret,
        compiler_params=COMPILER_PARAMS,
    )(pivots.astype(dtype).astype(wd), x2d, *([keys2d] if keyed else []))
    below = below.reshape(num_rows, -1)[:, :cap_pad].astype(dtype)
    above = _flip(above.reshape(num_rows, -1)[:, :cap_pad]).astype(dtype)
    return counts, below, above


@functools.partial(jax.jit, static_argnames=("n_valid", "cap_pad",
                                             "block_rows", "interpret"))
def fused_select(x2d: jax.Array, pivot: jax.Array, *, n_valid: int,
                 cap_pad: int, interpret: bool,
                 block_rows: int = DEFAULT_BLOCK_ROWS):
    """One streaming pass over the (rows, lanes) shard: returns
    ``(counts, below, above)`` where counts is the int32 (lt, eq, gt)
    triple, below is the (cap_pad,) largest values < pivot (descending,
    -sentinel padded) and above the (cap_pad,) smallest values > pivot
    (ascending, +sentinel padded).  ``select_vmem_bytes`` is its VMEM
    footprint."""
    counts, below, above = band_select(
        x2d, jnp.reshape(pivot, (1,)), n_valid=n_valid, cap_pad=cap_pad,
        num_levels=1, interpret=interpret, block_rows=block_rows)
    return counts[0], below[0], above[0]


@functools.partial(jax.jit, static_argnames=("n_valid", "cap_pad",
                                             "block_rows", "interpret"))
def fused_select_multi(x2d: jax.Array, pivots: jax.Array, *, n_valid: int,
                       cap_pad: int, interpret: bool,
                       block_rows: int = DEFAULT_BLOCK_ROWS):
    """``fused_select`` against Q pivots in the same single data pass:
    returns ``(counts (Q, 3), below (Q, cap_pad), above (Q, cap_pad))``."""
    return band_select(x2d, pivots, n_valid=n_valid, cap_pad=cap_pad,
                       num_levels=int(pivots.shape[0]), interpret=interpret,
                       block_rows=block_rows)


# ---------------------------------------------------------------------------
# 256-bin byte histogram: the 4-pass radix-select primitive
# ---------------------------------------------------------------------------

HIST_BINS = 256


def _byte_histogram_kernel(params_ref, u_ref, hist_ref, *, n_valid: int,
                           block_rows: int, shift: int):
    """Histogram of byte ``(u >> shift) & 0xFF`` over the elements whose
    masked high bits equal the running prefix.

    Each bin is one masked count of the tile, accumulated into the SMEM
    output; the bin loop keeps the tile's lane-dense layout (no one-hot
    expansion, no relayout).
    """
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        def zero(b, carry):
            hist_ref[b] = jnp.int32(0)
            return carry

        jax.lax.fori_loop(0, HIST_BINS, zero, 0)

    u = u_ref[...]
    lanes = u.shape[1]
    row = jax.lax.broadcasted_iota(jnp.int32, u.shape, 0)
    col = jax.lax.broadcasted_iota(jnp.int32, u.shape, 1)
    valid = (step * block_rows * lanes + row * lanes + col) < n_valid
    match = valid & ((u & params_ref[1]) == params_ref[0])
    byte = ((u >> jnp.uint32(shift)) & jnp.uint32(0xFF)).astype(jnp.int32)
    byte = jnp.where(match, byte, -1)          # parked outside every bin

    def count_bin(b, carry):
        hist_ref[b] += jnp.sum(jnp.where(byte == b, 1, 0), dtype=jnp.int32)
        return carry

    jax.lax.fori_loop(0, HIST_BINS, count_bin, 0)


@functools.partial(jax.jit, static_argnames=("n_valid", "shift",
                                             "block_rows", "interpret"))
def byte_histogram(u2d: jax.Array, prefix: jax.Array, mask: jax.Array, *,
                   n_valid: int, shift: int, interpret: bool,
                   block_rows: int = DEFAULT_BLOCK_ROWS) -> jax.Array:
    """(256,) int32 histogram of the ``shift``-positioned byte among the
    first ``n_valid`` elements matching ``(u & mask) == prefix``."""
    rows, lanes = u2d.shape
    check_lanes(lanes)
    if u2d.dtype != jnp.uint32:
        raise TypeError(f"byte_histogram wants uint32, got {u2d.dtype}")
    block_rows = min(block_rows, rows)
    grid = (pl.cdiv(rows, block_rows),)
    kernel = functools.partial(_byte_histogram_kernel, n_valid=n_valid,
                               block_rows=block_rows, shift=shift)
    params = jnp.stack([jnp.asarray(prefix, jnp.uint32),
                        jnp.asarray(mask, jnp.uint32)])
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((block_rows, lanes), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.SMEM),
        out_shape=jax.ShapeDtypeStruct((HIST_BINS,), jnp.int32),
        interpret=interpret,
        compiler_params=COMPILER_PARAMS,
    )(params, u2d)
