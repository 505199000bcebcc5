"""Pallas TPU kernel: open-band counts  #{ lo < x < hi }.

The building block of the TPU-native QuickSelect replacement
(``ops.radix_select_kth``): exact k-th statistics fall out of ~32 monotone
band counts over the sortable-uint transform of the value domain, with zero
data-dependent control flow — the hardware-adaptation answer to the paper's
in-place QuickSelect (DESIGN.md §2).

Same streaming layout contract as ``partition_count``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .partition_count import (DEFAULT_BLOCK_ROWS, check_lanes,
                              COMPILER_PARAMS, work_dtype)


def _band_count_kernel(bounds_ref, x_ref, out_ref, *, n_valid: int,
                       block_rows: int):
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        out_ref[0] = jnp.int32(0)

    x = x_ref[...].astype(bounds_ref.dtype)
    lo = bounds_ref[0]
    hi = bounds_ref[1]
    lanes = x.shape[1]
    base = step * block_rows * lanes
    row = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    col = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    valid = (base + row * lanes + col) < n_valid
    out_ref[0] += jnp.sum(jnp.where(valid & (x > lo) & (x < hi), 1, 0),
                          dtype=jnp.int32)


@functools.partial(jax.jit, static_argnames=("n_valid", "block_rows",
                                             "interpret"))
def band_count(x2d: jax.Array, lo: jax.Array, hi: jax.Array, *, n_valid: int,
               interpret: bool,
               block_rows: int = DEFAULT_BLOCK_ROWS) -> jax.Array:
    """int32 count of elements of the first n_valid lanes inside (lo, hi).
    2-byte floats are compared in f32 (``work_dtype``)."""
    rows, lanes = x2d.shape
    check_lanes(lanes)
    block_rows = min(block_rows, rows)
    grid = (pl.cdiv(rows, block_rows),)
    kernel = functools.partial(_band_count_kernel, n_valid=n_valid,
                               block_rows=block_rows)
    bounds = jnp.stack([lo, hi]).astype(x2d.dtype).astype(
        work_dtype(x2d.dtype))
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((block_rows, lanes), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.SMEM),
        out_shape=jax.ShapeDtypeStruct((1,), jnp.int32),
        interpret=interpret,
        compiler_params=COMPILER_PARAMS,
    )(bounds, x2d)
    return out[0]
