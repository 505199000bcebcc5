"""The extraction when the candidate cap covers a partition
(``local_ops.whole_band``): the band is the masked partition, with no
``top_k``.  Exact answers on both sides of the switch, and the programs
that take it or bypass it.

The oracle is numpy's sort of the same values; the data hold no
subnormals, which XLA's CPU compares may flush to zero."""
import contextlib
import math
import re
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import gk_select, gk_select_multi, local_ops, select
from test_distributed import run_sub
from test_kth_select import _same

EPS = 0.1
N_I = 512
# partitions at EPS: 12 >= 1/EPS, so the cap (617 clamped to 512) covers a
# partition; at 4 it is 207 of 512 lanes
PARTITIONS = {"covers": 12, "below": 4}
# ranks 1 and n, either side of a run of ties at the median, the tail
QS = (1e-12, 0.3, 0.5, 0.7, 0.99, 1.0)


def _job(dtype, case, P, rng):
    """A (P, N_I) input of ``dtype``.  ``specials``: a run of ties at the
    median (half of them -0.0 for floats), the dtype's extremes, and an
    all-equal partition; ``constant``: one value everywhere."""
    n = P * N_I
    dt = jnp.dtype(dtype)
    if jnp.issubdtype(dt, jnp.integer):
        info = np.iinfo(dt)
        x = rng.integers(-1000, 1000, size=n).astype(dt)
        ends = [info.min, info.min + 1, info.max - 1, info.max]
        tie, neg_tie = 3, 3
    else:
        x = rng.standard_normal(n) * 100.0
        finfo = jnp.finfo(dt)
        ends = [-np.inf, float(finfo.min), float(finfo.max), np.inf]
        tie, neg_tie = 0.0, -0.0
    if case == "constant":
        x[:] = ends[1]
    else:
        run = rng.choice(n, n // 4, replace=False)
        x[run[: n // 8]] = tie
        x[run[n // 8:]] = neg_tie
        for i, v in enumerate(ends):
            x[i::97] = v
        x = x.reshape(P, N_I)
        x[P // 2] = x[0, 5]                     # an all-equal partition
    return np.asarray(jnp.asarray(x, dt)).reshape(P, N_I)


@pytest.mark.parametrize("case", ["specials", "constant"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16", "float64", "int32"])
@pytest.mark.parametrize("side", ["covers", "below"])
@pytest.mark.parametrize("entry", ["default", "speculative", "multi"])
def test_answers_match_sort_on_both_sides_of_the_switch(entry, side, dtype,
                                                         case):
    P = PARTITIONS[side]
    cap = local_ops.candidate_cap(P * N_I, EPS, N_I)
    assert local_ops.whole_band(cap, N_I) == (side == "covers")
    wide = dtype.endswith("64")
    with jax.enable_x64(True) if wide else contextlib.nullcontext():
        rng = np.random.default_rng(
            zlib.crc32(f"{entry}/{side}/{dtype}/{case}".encode()))
        x = _job(dtype, case, P, rng)
        parts = jnp.asarray(x)
        flat = np.sort(x.ravel())
        want = [flat[local_ops.target_rank(x.size, q) - 1] for q in QS]
        obs.reset(local_ops.WHOLE_BAND)
        if entry == "multi":
            got = list(np.asarray(gk_select_multi(parts, QS, eps=EPS)))
        else:
            got = [np.asarray(gk_select(parts, q, eps=EPS,
                                        speculative=entry == "speculative"))
                   for q in QS]
        for g, w in zip(got, want):
            _same(g, w)
        jobs = 1 if entry == "multi" else len(QS)
        assert obs.counters().get(local_ops.WHOLE_BAND, 0) == (
            jobs if side == "covers" else 0)


@pytest.mark.parametrize("dtype", ["float32", "int32"])
def test_a_band_that_covers_the_shard_is_the_top_k_multiset(dtype):
    """Lane for lane up to order, the masked shard is what ``top_k`` of
    every lane returns."""
    rng = np.random.default_rng(11)
    x = jnp.asarray((rng.standard_normal(1000) * 50).astype(dtype))
    pivot = x[17]
    below = local_ops.extract_below(x, pivot, x.size)
    above = local_ops.extract_above(x, pivot, x.size)
    lo, hi = local_ops._sentinels(x.dtype)
    np.testing.assert_array_equal(
        np.sort(below), np.sort(jax.lax.top_k(jnp.where(x < pivot, x, lo),
                                              x.size)[0]))
    np.testing.assert_array_equal(
        np.sort(above), np.sort(-jax.lax.top_k(-jnp.where(x > pivot, x, hi),
                                               x.size)[0]))
    # below the shard's size the band is still the sorted top_k
    np.testing.assert_array_equal(
        local_ops.extract_above(x, pivot, 100), np.sort(above)[:100])
    np.testing.assert_array_equal(
        local_ops.extract_below(x, pivot, 100), np.sort(below)[::-1][:100])


INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%\S+ = \(?(\w+)\[([\d,]*)\]\S*"
                         r"(?:, [^=]*?)? ([a-z][\w-]*)\(")
OP_NAME = re.compile(r'op_name="([^"]*)"')


def test_job_program_extracts_without_a_large_sort():
    """The paper's job, (120, 2^23) float32 at q = 0.99, lowered from
    shapes alone: no sort or top-k of 2^20 lanes or more runs under
    ``phase_extract``, and both branches' masks run under
    ``whole_band``."""
    x = jax.ShapeDtypeStruct((120, 1 << 23), jnp.float32)
    text = select._gk_select_jit.lower(x, 0.99).compile().as_text()
    extract_selects, band_ops = [], set()
    for line in text.splitlines():
        path = OP_NAME.search(line)
        path = path.group(1) if path else ""
        m = INSTRUCTION.match(line)
        if not m:
            continue
        if "whole_band" in path:
            band_ops.add(path.split("/")[2])        # the cond's branch
        lanes = math.prod(int(d) for d in m.group(2).split(",") if d)
        phases = re.findall(r"phase_\w+", path)
        selects = (m.group(3) in ("sort", "topk")
                   or path.endswith("/top_k"))
        if (selects and lanes >= 1 << 20 and phases
                and phases[-1] == "phase_extract"):
            extract_selects.append(line.strip()[:120])
    assert not extract_selects
    assert band_ops == {"branch_0_fun", "branch_1_fun"}


def test_sharded_job_program_keeps_its_top_k():
    """The four-chip cell's program, lowered at its shapes (10^9 float32
    values over a 4-device mesh): a cap of 10,066,332 of a chip's
    251,658,240 lanes, so its extraction is still the ``top_k`` under
    ``phase_extract``, and nothing runs under ``whole_band``."""
    out = run_sub("""
        import re
        from jax.sharding import NamedSharding, PartitionSpec
        from repro.core import local_ops, lowering
        n = 120 << 23
        cap = local_ops.candidate_cap(n, 0.01, n // P)
        assert cap == 10_066_332 and not local_ops.whole_band(cap, n // P)
        x = jax.ShapeDtypeStruct((n,), jnp.float32, sharding=NamedSharding(
            mesh, PartitionSpec("data")))
        text = lowering.lower(distributed_quantile, x, 0.99,
                              mesh).as_text(debug_info=True)
        locs = dict(re.findall(r'^(#loc\\d+) = loc\\("([^"]*)"', text, re.M))
        top_ks = re.findall(
            r"chlo\\.top_k\\(\\S+, k = (\\d+)\\) : tensor<(\\d+)xf32>"
            r".*loc\\((#loc\\d+)\\)", text)
        extract = [(int(k), int(lanes)) for k, lanes, loc in top_ks
                   if locs.get(loc, "").startswith("phase_extract/")]
        assert extract == [(cap, n // P)], top_ks
        assert "whole_band" not in text
        print("BYPASS-OK")
    """, devices=4)
    assert "BYPASS-OK" in out
