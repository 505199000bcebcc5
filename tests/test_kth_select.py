"""The resolve's k-th selection (``local_ops.kth_smallest`` /
``kth_largest``): order-key bisection and the sort of order keys against
a sort oracle, and the selector the paper's job takes.

The oracle is numpy's sort of the same values, which ranks subnormals as
IEEE does; ``jnp.sort`` on XLA's CPU ties them with zero."""
import contextlib
import math
import re
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import gk_select, gk_select_multi, local_ops, select

DTYPES = ["float32", "bfloat16", "float16", "int32", "int16", "int8",
          "uint32", "uint16", "uint8", "float64", "int64", "uint64"]
CASES = ["random", "sentinels", "ties", "zeros", "extremes"]
SIZE = (3, 257)


def _values(dtype, case, rng):
    """A (3, 257) buffer of ``dtype`` for one case."""
    n = math.prod(SIZE)
    dt = jnp.dtype(dtype)
    if jnp.issubdtype(dt, jnp.integer):
        info = np.iinfo(dt)
        lo, hi = int(info.min), int(info.max)
        x = rng.integers(lo, hi, size=n, endpoint=True, dtype=dt)
        if case == "sentinels":                 # mostly -/+ padding
            x[: n // 10] = lo
            x[n // 5:] = hi
        elif case in ("ties", "zeros"):
            x = rng.choice(np.array([lo, 0, 1, 2, hi], dtype=dt), size=n)
        elif case == "extremes":
            x[::7] = lo
            x[3::11] = hi
            x[5::13] = lo + 1
            x[6::17] = hi - 1
    else:
        with np.errstate(over="ignore"):
            x = rng.standard_normal(n) * 10.0 ** rng.integers(-40, 38, n)
            finfo = jnp.finfo(dt)
            if case == "sentinels":
                x[: n // 10] = -np.inf
                x[n // 5:] = np.inf
            elif case == "ties":
                x = rng.choice(np.array([-np.inf, -2.5, -0.0, 0.0, 1.5, 1.5,
                                         np.inf]), size=n)
            elif case == "zeros":
                x = rng.choice(np.array([-0.0, 0.0, -1.0, 1.0]), size=n)
                x[:5] = float(finfo.smallest_subnormal)
                x[5:10] = -float(finfo.smallest_subnormal)
            elif case == "extremes":
                x[::7] = float(finfo.min)
                x[3::11] = float(finfo.max)
                x[5::13] = -np.inf
                x[6::17] = np.inf
                x[8::19] = float(finfo.smallest_subnormal)
            x = np.asarray(jnp.asarray(x, dt))
    return x.reshape(SIZE)


def _has_subnormals(x):
    if x.dtype.kind in "iu":
        return False
    tiny = float(jnp.finfo(x.dtype).smallest_normal)
    wide = x.astype(np.float64)
    return bool(np.any((wide != 0) & (np.abs(wide) < tiny)))


def _same(got, want):
    """``==``, in the dtype, and bit for bit but for the sign of a zero."""
    got, want = np.asarray(got), np.asarray(want)
    assert got.dtype == want.dtype
    assert got == want, (got, want)
    if got.dtype.kind not in "iu" and float(want) == 0:
        return
    assert got.tobytes() == want.tobytes(), (got, want)


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("selector", ["bisect", "sort"])
def test_kth_matches_sort(selector, dtype, case, monkeypatch):
    """Both selectors, both sides, k from 1 to the size, on each case:
    mostly sentinel padding, heavy ties, -0.0 with +0.0 (and subnormals),
    the dtype's extremes.  Where ``jnp.sort`` is exact it agrees."""
    monkeypatch.setattr(local_ops, "BISECT_MIN_LANES",
                        0 if selector == "bisect" else 2 ** 31)
    wide = dtype.endswith("64")
    with jax.enable_x64(True) if wide else contextlib.nullcontext():
        rng = np.random.default_rng(zlib.crc32(f"{dtype}/{case}".encode()))
        x = jnp.asarray(_values(dtype, case, rng))
        assert local_ops._bisects(x) == (selector == "bisect")
        n = x.size
        oracle = np.sort(np.asarray(x).ravel())
        smallest = jax.jit(lambda c, k: local_ops.kth_smallest(c, k, 0))
        largest = jax.jit(lambda c, k: local_ops.kth_largest(c, k, 0))
        for k in sorted({1, 2, n // 10, n // 5, n // 2, n - 1, n}):
            _same(smallest(x, k), oracle[k - 1])
            _same(largest(x, k), oracle[n - k])
        # out-of-range ranks clamp to the ends, as the sort's index did
        _same(smallest(x, 0), oracle[0])
        _same(largest(x, n + 5), oracle[0])
        if not _has_subnormals(oracle):
            np.testing.assert_array_equal(jnp.sort(x.ravel()), oracle)


def test_selector_rule_reads_shape_and_dtype():
    lanes = local_ops.BISECT_MIN_LANES
    f32 = jax.ShapeDtypeStruct((lanes,), jnp.float32)
    assert local_ops._bisects(f32)
    assert local_ops._bisects(jax.ShapeDtypeStruct((120, 1 << 23), "float32"))
    assert not local_ops._bisects(jax.ShapeDtypeStruct((lanes - 1,),
                                                       jnp.float32))
    assert not local_ops._bisects(jax.ShapeDtypeStruct((lanes,), jnp.bool_))
    assert not local_ops._bisects(jax.ShapeDtypeStruct((lanes,),
                                                       jnp.complex64))


@pytest.mark.parametrize("side", ["bisect", "sort"])
@pytest.mark.parametrize("entry", ["default", "speculative", "multi"])
@pytest.mark.parametrize("dtype", ["float32", "int32"])
def test_gk_select_across_the_crossover(side, entry, dtype):
    """``gk_select`` end to end on (P, n_i) inputs whose candidate buffers
    fall on either side of ``BISECT_MIN_LANES``."""
    P = 4
    n_i = (local_ops.BISECT_MIN_LANES // 2 if side == "bisect"
           else local_ops.BISECT_MIN_LANES // 16)
    eps = 0.5                                   # cap = n_i: (P, n_i) buffers
    cap = local_ops.candidate_cap(P * n_i, eps, n_i)
    assert local_ops._bisects(
        jax.ShapeDtypeStruct((P, cap), dtype)) == (side == "bisect")
    rng = np.random.default_rng(7)
    x = (rng.standard_normal((P, n_i)) * 1e4).astype(dtype)
    x[0, : n_i // 3] = x[1, 0]                  # a run of ties
    flat = np.sort(x.ravel())
    qs = (0.001, 0.5, 0.999)
    want = [flat[local_ops.target_rank(x.size, q) - 1] for q in qs]
    parts = jnp.asarray(x)
    if entry == "multi":
        got = list(np.asarray(gk_select_multi(parts, qs, eps=eps)))
    else:
        got = [np.asarray(gk_select(parts, q, eps=eps,
                                    speculative=entry == "speculative"))
               for q in qs]
    np.testing.assert_array_equal(got, want)


SORT = re.compile(r"^\s*(?:ROOT )?%\S+ = \(?(\w+)\[([\d,]*)\]\S* sort\(")
OP_NAME = re.compile(r'op_name="([^"]*)"')


def test_job_program_resolves_without_a_large_sort():
    """The paper's job, (120, 2^23) float32 at q = 0.99, lowered from
    shapes alone: no sort of 2^20 lanes or more runs under
    ``phase_resolve``, and the ``kth_bisect`` loop is there."""
    x = jax.ShapeDtypeStruct((120, 1 << 23), jnp.float32)
    text = select._gk_select_jit.lower(x, 0.99).compile().as_text()
    resolve_sorts, bisect_ops = [], 0
    for line in text.splitlines():
        path = OP_NAME.search(line)
        path = path.group(1) if path else ""
        bisect_ops += "kth_bisect" in path
        m = SORT.match(line)
        if not m:
            continue
        lanes = math.prod(int(d) for d in m.group(2).split(",") if d)
        phases = re.findall(r"phase_\w+", path)
        if lanes >= 1 << 20 and phases and phases[-1] == "phase_resolve":
            resolve_sorts.append(line.strip()[:120])
    assert not resolve_sorts
    assert bisect_ops > 0
