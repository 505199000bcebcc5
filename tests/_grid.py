"""Shared fixture module for the engine-wide oracle grid (ISSUE 5).

One place defines the test surface every quantile engine must survive:

  DTYPES         float32, bfloat16, int32, float64 (float64 needs x64 —
                 cells enable it via ``x64_ctx`` (``jax.enable_x64(True)``
                 scoped to the cell) or a subprocess-global switch)
  DISTRIBUTIONS  uniform            wide continuous range
                 zipf               heavy-duplicate small support (Zipf-ish
                                    mass: collisions everywhere, including
                                    at the pivot)
                 all_equal          one repeated value (lt == gt == 0 at
                                    every pivot; rank arithmetic only)
                 sorted             globally sorted -> contiguous per-shard
                                    bands (worst case for shuffle baselines,
                                    maximal sketch skew)
                 ties               adversarial near-pivot ties: half the
                                    mass IS the median value, the rest sits
                                    one representable step away — candidate
                                    bands full of duplicates
  SHARD_COUNTS   1, 3, 6 (includes the non-power-of-two butterfly paths)

Oracles are ``np.partition`` based and BIT-exact: engines must return the
k-th smallest element, not an approximation of it.  bfloat16 data is
compared in its own dtype (ranked via the injective upcast to float32).

A new engine gets the whole grid by adding one runner to
``test_oracle_grid.py`` — the cases, oracles and rank rules live here.
"""
import contextlib
import math
import zlib

import numpy as np

DTYPES = ("float32", "bfloat16", "int32", "float64")
DISTRIBUTIONS = ("uniform", "zipf", "all_equal", "sorted", "ties")
SHARD_COUNTS = (1, 3, 6)
QS = (0.001, 0.5, 0.999)


def needs_x64(dtype: str) -> bool:
    return dtype == "float64"


def x64_ctx(dtype: str):
    """Context for one cell: x64 enabled for float64 cells only."""
    if needs_x64(dtype):
        import jax
        return jax.enable_x64(True)
    return contextlib.nullcontext()


def _np_dtype(dtype: str):
    if dtype == "bfloat16":
        import ml_dtypes   # shipped with jax
        return np.dtype(ml_dtypes.bfloat16)
    return np.dtype(dtype)


def make_case(dist: str, dtype: str, n: int, seed: int = 0) -> np.ndarray:
    """One (distribution, dtype) data case as a flat numpy array."""
    # crc32, not hash(): string hashing is randomized per process, which
    # would make a failing grid cell irreproducible
    rng = np.random.default_rng(
        (zlib.crc32(f"{dist}-{dtype}-{n}".encode()) ^ seed) & 0x7FFFFFFF)
    dt = _np_dtype(dtype)
    if dist == "uniform":
        base = rng.uniform(-1e6, 1e6, size=n)
    elif dist == "zipf":
        # heavy-duplicate small support: ~30 distinct values, Zipf-ish mass
        ranks = rng.zipf(1.5, size=n) % 30
        base = (ranks.astype(np.float64) - 7.0) * 3.0
    elif dist == "all_equal":
        base = np.full(n, 7.0 if dtype == "int32" else 3.25)
    elif dist == "sorted":
        base = np.sort(rng.uniform(-1e6, 1e6, size=n))
    elif dist == "ties":
        # adversarial near-pivot ties: half the mass at the median value m,
        # the rest one representable step below/above it
        m = 13.0
        step = 1.0 if dtype == "int32" else (0.125 if dtype == "bfloat16"
                                             else 1e-3)
        choice = rng.choice([0, 1, 2], size=n, p=[0.25, 0.5, 0.25])
        base = m + (choice - 1) * step
    else:
        raise ValueError(f"unknown distribution {dist!r}")
    if dtype == "int32":
        return np.round(base).astype(np.int32)
    return base.astype(dt)


def target_rank(n: int, q: float) -> int:
    """The engine-wide host rank rule (mirrors local_ops.target_rank)."""
    return int(min(n, max(1, math.ceil(q * n))))


def exact_target_rank(n: int, q: float) -> int:
    """The grouped engine's exact-rational rank rule (mirrors
    local_ops.exact_target_rank)."""
    a, b = float(q).as_integer_ratio()
    return int(min(max(n, 1), max(1, -((-a * n) // b))))


def oracle_kth(x: np.ndarray, k: int):
    """Bit-exact k-th smallest (1-based) via np.partition.  bfloat16 is
    ranked through its injective monotonic upcast to float32 and the winner
    is returned in the original dtype."""
    flat = np.asarray(x).ravel()
    if flat.dtype.kind not in "fiu":          # ml_dtypes.bfloat16
        up = flat.astype(np.float32)
        return np.partition(up, k - 1)[k - 1].astype(flat.dtype)
    return np.partition(flat, k - 1)[k - 1]


def oracle_quantile(x: np.ndarray, q: float):
    return oracle_kth(x, target_rank(np.asarray(x).size, q))


def grouped_oracle(values: np.ndarray, keys: np.ndarray, q: float, g: int,
                   hi_sentinel):
    """Per-group oracle under the grouped engine's exact-rational rank rule;
    empty groups yield the dtype's high sentinel."""
    vals = np.asarray(values).ravel()[np.asarray(keys).ravel() == g]
    if vals.size == 0:
        return hi_sentinel
    return oracle_kth(vals, exact_target_rank(vals.size, q))


def ragged_chunks(x: np.ndarray, parts: int, seed: int = 0):
    """Split a flat case into ``parts`` uneven chunks (service ingest)."""
    n = x.size
    if parts == 1:
        return [x]
    rng = np.random.default_rng(seed)
    cuts = np.sort(rng.choice(np.arange(1, n), size=parts - 1,
                              replace=False))
    return np.split(x, cuts)
