"""Pallas TPU kernel: single-pass SEGMENTED band extraction (DESIGN.md §7).

The grouped engine's phase 3 needs, for every group g in [0, G) and every
level's pivot p_{g,q}: the 3-way counts of the group's elements vs p_{g,q}
AND both capped candidate bands — restricted to ``keys == g``.  The unfused
pipeline streams the shard 3*G*Q times; per-group HBM passes *are* the cost
of the group-by workload, so this kernel collapses them into ONE sweep:

values and keys tiles are loaded into VMEM once per grid step; every
(group, level) pair re-scores the resident tile against its own membership
mask and pivot, scatter-accumulating into its row of the revisited output
blocks — (G*Q, 3) counts in SMEM and two (G*Q, C) running candidate
selections in VMEM (the same bitonic fold as ``fused_select``, whose
launcher this kernel shares).  Extra groups cost VPU compare/select work, never HBM
reads.

VMEM budget (``fused_select.select_vmem_bytes``): tiles + 2 * (G*Q, C)
candidate blocks + the work buffer.  The (group, level) loop targets the
O(10-100) group counts of telemetry/per-channel workloads (beyond that a
bin-scatter layout wins; see DESIGN.md §7).

Layout contract matches ``fused_select``: flat shards padded to
(rows, lanes) row-major (lanes any positive multiple of 128), true length
in ``n_valid``, ``cap_pad`` a positive multiple of 128.  Keys are int32;
pad lanes are masked by n_valid so their key content is irrelevant.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .partition_count import DEFAULT_BLOCK_ROWS
from .fused_select import band_select


@functools.partial(jax.jit, static_argnames=("n_valid", "cap_pad",
                                             "block_rows", "num_groups",
                                             "interpret"))
def segmented_select(x2d: jax.Array, keys2d: jax.Array, pivots: jax.Array, *,
                     n_valid: int, cap_pad: int, num_groups: int,
                     interpret: bool, block_rows: int = DEFAULT_BLOCK_ROWS):
    """One streaming pass over the (rows, lanes) shard for every group and
    level: ``pivots`` is (G, Q); returns ``(counts (G, Q, 3),
    below (G, Q, cap_pad), above (G, Q, cap_pad))`` with per-row semantics
    identical to ``fused_select`` restricted to ``keys == g``."""
    if keys2d.shape != x2d.shape:
        raise ValueError(f"keys shape {keys2d.shape} != values {x2d.shape}")
    if keys2d.dtype != jnp.int32:
        raise TypeError(f"keys must be int32, got {keys2d.dtype}")
    G, Q = pivots.shape
    if G != num_groups:
        raise ValueError(f"pivots leading dim {G} != num_groups {num_groups}")
    counts, below, above = band_select(
        x2d, pivots.reshape(-1), keys2d, n_valid=n_valid, cap_pad=cap_pad,
        num_levels=Q, interpret=interpret, block_rows=block_rows)
    return (counts.reshape(G, Q, 3), below.reshape(G, Q, cap_pad),
            above.reshape(G, Q, cap_pad))
