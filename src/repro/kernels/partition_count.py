"""Pallas TPU kernel: 3-way Dutch partition counts (paper ``firstPass``).

GK Select Round 2 is a pure streaming pass: every shard counts elements
(<, ==, >) the pivot.  Arithmetic intensity is ~3 flop-equivalents per 4
bytes, so the kernel is HBM-bandwidth-bound; the job of the kernel is to
stream HBM->VMEM in MXU-aligned (block_rows, 1024) tiles and keep the
accumulator in SMEM across sequential grid steps.

Layout contract (see kernels.dispatch): the caller pads the flat shard to
rows*lanes and reshapes to (rows, lanes) row-major, where lanes is any
positive multiple of 128 (1024 for 4-byte dtypes, 2048 for 2-byte —
``dispatch.lanes_for``); padding lanes are masked by global index against
the true length (static at trace time).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 1024          # 8 sublanes x 128 lanes, one VREG row of f32
DEFAULT_BLOCK_ROWS = 128
LANE_MULTIPLE = 128


def check_lanes(lanes: int) -> None:
    """The streamed layout's trailing dim must be VREG-aligned."""
    if lanes <= 0 or lanes % LANE_MULTIPLE:
        raise ValueError(f"trailing dim must be a positive multiple of "
                         f"{LANE_MULTIPLE}, got {lanes}")


def work_dtype(dtype):
    """The dtype a kernel compares in.  v5e's VPU has no 16-bit compare,
    so 2-byte floats are upcast (exactly) to f32 inside the kernel; every
    other dtype compares as itself."""
    dtype = jnp.dtype(dtype)
    if jnp.issubdtype(dtype, jnp.floating) and dtype.itemsize < 4:
        return jnp.dtype(jnp.float32)
    return dtype


# Sequential grid: the accumulators are carried across steps.  VMEM runs
# under the compiler's default limit; ``dispatch.plan`` sizes tiles to fit
# its budget.  Interpret mode ignores the parameters.
COMPILER_PARAMS = pltpu.CompilerParams(dimension_semantics=("arbitrary",))


def _count3_kernel(pivot_ref, x_ref, out_ref, *, n_valid: int,
                   block_rows: int):
    """One grid step: accumulate (lt, eq, gt-valid) for a
    (block_rows, lanes) tile into the SMEM accumulator."""
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        out_ref[0] = jnp.int32(0)
        out_ref[1] = jnp.int32(0)
        out_ref[2] = jnp.int32(0)

    x = x_ref[...].astype(pivot_ref.dtype)
    pivot = pivot_ref[0]
    lanes = x.shape[1]
    base = step * block_rows * lanes
    row = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    col = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    valid = (base + row * lanes + col) < n_valid
    lt = jnp.sum(jnp.where(valid & (x < pivot), 1, 0), dtype=jnp.int32)
    eq = jnp.sum(jnp.where(valid & (x == pivot), 1, 0), dtype=jnp.int32)
    nv = jnp.sum(jnp.where(valid, 1, 0), dtype=jnp.int32)
    out_ref[0] += lt
    out_ref[1] += eq
    out_ref[2] += nv - lt - eq


@functools.partial(jax.jit, static_argnames=("n_valid", "block_rows",
                                             "interpret"))
def partition_count(x2d: jax.Array, pivot: jax.Array, *, n_valid: int,
                    interpret: bool,
                    block_rows: int = DEFAULT_BLOCK_ROWS) -> jax.Array:
    """(lt, eq, gt) int32 counts of the first ``n_valid`` elements of the
    row-major (rows, lanes) array vs the scalar pivot.

    VMEM footprint per step: block_rows * lanes * itemsize
    (128 x 1024 x 4B = 512 KiB f32), double-buffered.  2-byte floats are
    compared in f32 (``work_dtype``).
    """
    rows, lanes = x2d.shape
    check_lanes(lanes)
    block_rows = min(block_rows, rows)
    grid = (pl.cdiv(rows, block_rows),)
    kernel = functools.partial(_count3_kernel, n_valid=n_valid,
                               block_rows=block_rows)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((block_rows, lanes), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.SMEM),
        out_shape=jax.ShapeDtypeStruct((3,), jnp.int32),
        interpret=interpret,
        compiler_params=COMPILER_PARAMS,
    )(pivot.astype(work_dtype(x2d.dtype)).reshape(1), x2d)
