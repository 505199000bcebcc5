"""Fused band-extraction kernel suite: interpret-mode bit-parity against the
ref.py oracles across dtypes and edge cases, the 4-pass byte-histogram radix
select, HBM pass accounting, and end-to-end fused gk_select exactness."""
import math

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels import ops
from repro.kernels.ref import (fused_select_ref, byte_histogram_ref,
                               partition_count_ref, block_topk_ref)

SHAPES = [7, 100, 1024, 1025, 4096, 65536]
DTYPES = [np.float32, np.int32, "bfloat16"]


def _make(rng, n, dtype):
    if dtype is np.int32:
        return jnp.asarray(rng.integers(-10 ** 6, 10 ** 6, size=n)
                           .astype(np.int32))
    x = rng.normal(size=n).astype(np.float32)
    if dtype == "bfloat16":
        return jnp.asarray(x, jnp.bfloat16)
    return jnp.asarray(x)


def _assert_fused_matches_oracle(x, pivot, cap):
    got_c, got_b, got_a = ops.fused_count_extract(x, pivot, cap)
    want_c, want_b, want_a = fused_select_ref(x, pivot, cap)
    np.testing.assert_array_equal(np.asarray(got_c), np.asarray(want_c))
    np.testing.assert_array_equal(np.asarray(got_b), np.asarray(want_b))
    np.testing.assert_array_equal(np.asarray(got_a), np.asarray(want_a))


class TestFusedSelectParity:
    @pytest.mark.parametrize("n", SHAPES)
    @pytest.mark.parametrize("dtype", DTYPES)
    def test_sweep_vs_oracle(self, n, dtype):
        rng = np.random.default_rng(n)
        x = _make(rng, n, dtype)
        cap = max(1, min(n, n // 50 + 2))
        _assert_fused_matches_oracle(x, x[n // 2], cap)

    @pytest.mark.parametrize("dtype", DTYPES)
    def test_pivot_at_extremes(self, dtype):
        rng = np.random.default_rng(7)
        x = _make(rng, 3000, dtype)
        xa = np.asarray(x.astype(jnp.float32) if dtype == "bfloat16" else x)
        for pivot in [x[int(np.argmin(xa))], x[int(np.argmax(xa))]]:
            _assert_fused_matches_oracle(x, pivot, 64)

    def test_pivot_outside_range(self):
        rng = np.random.default_rng(8)
        x = jnp.asarray(rng.normal(size=2048).astype(np.float32))
        _assert_fused_matches_oracle(x, jnp.float32(1e9), 32)   # all below
        _assert_fused_matches_oracle(x, jnp.float32(-1e9), 32)  # all above

    def test_all_equal(self):
        x = jnp.full((4096,), 3.5, jnp.float32)
        got_c, got_b, got_a = ops.fused_count_extract(x, jnp.float32(3.5), 16)
        assert np.asarray(got_c).tolist() == [0, 4096, 0]
        assert np.all(np.asarray(got_b) == -np.inf)   # empty band -> sentinels
        assert np.all(np.asarray(got_a) == np.inf)
        _assert_fused_matches_oracle(x, jnp.float32(3.5), 16)

    def test_cap_overflow_band(self):
        """cap smaller than the band population: only the cap best survive;
        cap larger: sentinel padding matches the oracle exactly."""
        rng = np.random.default_rng(9)
        x = jnp.asarray(rng.normal(size=4096).astype(np.float32))
        pivot = jnp.float32(0.0)   # ~2048 on each side
        for cap in [4, 4096]:
            _assert_fused_matches_oracle(x, pivot, cap)

    def test_block_rows_invariance(self):
        from repro.kernels.fused_select import fused_select
        rng = np.random.default_rng(10)
        x = jnp.asarray(rng.normal(size=300_000).astype(np.float32))
        pivot = x[17]
        want = fused_select_ref(x, pivot, 128)
        for br in [8, 64, 256]:
            x2d = ops.pad_to_tiles(x)
            c, b, a = fused_select(x2d, pivot, n_valid=x.size, cap_pad=128,
                                   block_rows=br, interpret=True)
            np.testing.assert_array_equal(np.asarray(c), np.asarray(want[0]))
            np.testing.assert_array_equal(np.asarray(b), np.asarray(want[1]))
            np.testing.assert_array_equal(np.asarray(a), np.asarray(want[2]))

    def test_buffer_wider_than_tile(self):
        """A running buffer longer than one tile: every tile is folded
        into the buffer's tail only (the large-cap path)."""
        from repro.kernels.fused_select import fused_select
        rng = np.random.default_rng(14)
        x = jnp.asarray(rng.normal(size=40_000).astype(np.float32))
        pivot = x[5]
        want = fused_select_ref(x, pivot, 16_384)
        c, b, a = fused_select(ops.pad_to_tiles(x), pivot, n_valid=x.size,
                               cap_pad=16_384, block_rows=8, interpret=True)
        for got, w in zip((c, b, a), want):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(w))

    @settings(max_examples=20, deadline=None)
    @given(st.integers(2, 5000), st.integers(0, 2 ** 31 - 1))
    def test_property_parity(self, n, seed):
        rng = np.random.default_rng(seed)
        x = jnp.asarray(rng.integers(-50, 50, size=n).astype(np.int32))
        pivot = x[int(rng.integers(0, n))]
        cap = int(rng.integers(1, n + 1))
        _assert_fused_matches_oracle(x, pivot, cap)


class TestFusedSelectMulti:
    @pytest.mark.parametrize("dtype", [np.float32, np.int32])
    def test_multi_vs_single(self, dtype):
        rng = np.random.default_rng(11)
        x = _make(rng, 20000, dtype)
        idx = [3, 777, 5000, 19999]
        pivots = jnp.stack([x[i] for i in idx])
        cap = 128
        mc, mb, ma = ops.fused_count_extract_multi(x, pivots, cap)
        for qi in range(len(idx)):
            want_c, want_b, want_a = fused_select_ref(x, pivots[qi], cap)
            np.testing.assert_array_equal(np.asarray(mc[qi]),
                                          np.asarray(want_c))
            np.testing.assert_array_equal(np.asarray(mb[qi]),
                                          np.asarray(want_b))
            np.testing.assert_array_equal(np.asarray(ma[qi]),
                                          np.asarray(want_a))

    def test_duplicate_pivots(self):
        rng = np.random.default_rng(12)
        x = jnp.asarray(rng.normal(size=3000).astype(np.float32))
        pivots = jnp.stack([x[5], x[5]])
        mc, mb, ma = ops.fused_count_extract_multi(x, pivots, 32)
        np.testing.assert_array_equal(np.asarray(mc[0]), np.asarray(mc[1]))
        np.testing.assert_array_equal(np.asarray(mb[0]), np.asarray(mb[1]))
        np.testing.assert_array_equal(np.asarray(ma[0]), np.asarray(ma[1]))


class TestByteHistogram:
    @pytest.mark.parametrize("shift", [24, 16, 8, 0])
    def test_vs_oracle(self, shift):
        rng = np.random.default_rng(13 + shift)
        u = jnp.asarray(rng.integers(0, 2 ** 32, size=50_000,
                                     dtype=np.uint64).astype(np.uint32))
        prefix = jnp.uint32(0)
        mask = jnp.uint32(0)
        got = np.asarray(ops.byte_histogram(u, prefix, mask, shift=shift))
        want = np.asarray(byte_histogram_ref(u, prefix, mask, shift))
        np.testing.assert_array_equal(got, want)
        assert got.sum() == u.size

    def test_prefix_restriction(self):
        rng = np.random.default_rng(17)
        u = jnp.asarray(rng.integers(0, 2 ** 32, size=20_000,
                                     dtype=np.uint64).astype(np.uint32))
        top = np.asarray(u) >> 24
        byte_val = int(np.bincount(top, minlength=256).argmax())
        prefix = jnp.uint32(byte_val << 24)
        mask = jnp.uint32(0xFF000000)
        got = np.asarray(ops.byte_histogram(u, prefix, mask, shift=16))
        want = np.asarray(byte_histogram_ref(u, prefix, mask, 16))
        np.testing.assert_array_equal(got, want)
        assert got.sum() == (top == byte_val).sum()


class TestRadixSelect4Pass:
    @pytest.mark.parametrize("dtype", DTYPES)
    def test_exact_kth(self, dtype):
        rng = np.random.default_rng(2)
        x = _make(rng, 4096, dtype)
        srt = np.sort(np.asarray(x, np.float32 if dtype == "bfloat16"
                                 else None))
        for k in [1, 5, 2048, 4096]:
            got = ops.radix_select_kth(x, jnp.int32(k))
            assert np.float32(got) == np.float32(srt[k - 1]), (dtype, k)

    def test_exactly_four_passes(self):
        rng = np.random.default_rng(3)
        x = jnp.asarray(rng.normal(size=10_000).astype(np.float32))
        ops.reset_hbm_passes()
        got = ops.radix_select_kth(x, jnp.int32(5000))
        assert ops.hbm_passes() == ops.RADIX_PASSES == 4
        assert float(got) == np.sort(np.asarray(x))[4999]

    def test_matches_bitwise_baseline(self):
        rng = np.random.default_rng(4)
        x = jnp.asarray(rng.normal(size=5000).astype(np.float32))
        for k in [1, 777, 5000]:
            a = float(ops.radix_select_kth(x, jnp.int32(k)))
            b = float(ops.radix_select_kth_bitwise(x, jnp.int32(k)))
            assert a == b == np.sort(np.asarray(x))[k - 1]

    @settings(max_examples=20, deadline=None)
    @given(st.integers(2, 2000), st.integers(0, 2 ** 31 - 1))
    def test_property_exact(self, n, seed):
        rng = np.random.default_rng(seed)
        x = jnp.asarray(rng.normal(size=n).astype(np.float32))
        k = int(rng.integers(1, n + 1))
        got = float(ops.radix_select_kth(x, jnp.int32(k)))
        assert got == np.sort(np.asarray(x))[k - 1]


class TestPassAccounting:
    def test_speculative_round_is_one_pass(self):
        rng = np.random.default_rng(5)
        x = jnp.asarray(rng.normal(size=30_000).astype(np.float32))
        pivot = x[0]
        cap = 64
        # backend="pallas" pins the kernel contract: the dispatch default
        # on CPU is the jnp oracle, which honestly ticks 3 streams
        ops.reset_hbm_passes()
        ops.fused_count_extract(x, pivot, cap, backend="pallas")
        assert ops.hbm_passes() == 1
        ops.reset_hbm_passes()
        ops.count3(x, pivot)
        ops.extract_below(x, pivot, cap)
        ops.extract_above(x, pivot, cap)
        assert ops.hbm_passes() == 3

    def test_jnp_backend_ticks_honestly(self):
        rng = np.random.default_rng(5)
        x = jnp.asarray(rng.normal(size=30_000).astype(np.float32))
        ops.reset_hbm_passes()
        ops.fused_count_extract(x, x[0], 64, backend="jnp")
        assert ops.hbm_passes() == 3
        pivots = jnp.stack([x[1], x[2], x[3]])
        ops.reset_hbm_passes()
        ops.fused_count_extract_multi(x, pivots, 64, backend="jnp")
        assert ops.hbm_passes() == 9

    def test_multi_pivot_is_one_pass(self):
        rng = np.random.default_rng(6)
        x = jnp.asarray(rng.normal(size=30_000).astype(np.float32))
        pivots = jnp.stack([x[1], x[2], x[3]])
        ops.reset_hbm_passes()
        ops.fused_count_extract_multi(x, pivots, 64, backend="pallas")
        assert ops.hbm_passes() == 1


class TestFusedGKSelect:
    """End-to-end: gk_select/gk_select_multi with block_select=True route
    the count+extract phases through the fused kernel and stay exact."""

    def test_matches_unfused_and_truth(self):
        from repro.core import gk_select
        rng = np.random.default_rng(20)
        parts = rng.normal(size=(4, 2048)).astype(np.float32)
        flat = np.sort(parts.ravel())
        for q in [0.1, 0.5, 0.9]:
            k = min(parts.size, max(1, math.ceil(q * parts.size)))
            want = flat[k - 1]
            fused = float(gk_select(jnp.asarray(parts), q, block_select=True))
            spec = float(gk_select(jnp.asarray(parts), q, speculative=True))
            assert fused == spec == want

    def test_multi_quantile_fused(self):
        from repro.core import gk_select_multi
        rng = np.random.default_rng(21)
        parts = rng.normal(size=(4, 4096)).astype(np.float32)
        flat = np.sort(parts.ravel())
        qs = (0.05, 0.25, 0.5, 0.75, 0.95)
        got = np.asarray(gk_select_multi(jnp.asarray(parts), qs,
                                         block_select=True))
        for q, g in zip(qs, got):
            k = min(parts.size, max(1, math.ceil(q * parts.size)))
            assert g == flat[k - 1]

    def test_int32_and_ties(self):
        from repro.core import gk_select
        rng = np.random.default_rng(22)
        parts = rng.integers(-5, 5, size=(4, 1024)).astype(np.int32)
        flat = np.sort(parts.ravel())
        for q in [0.3, 0.5, 0.8]:
            k = min(parts.size, max(1, math.ceil(q * parts.size)))
            got = gk_select(jnp.asarray(parts), q, block_select=True)
            assert int(got) == flat[k - 1]

    @settings(max_examples=20, deadline=None)
    @given(st.integers(2, 8), st.integers(64, 1024), st.floats(0.0, 1.0),
           st.integers(0, 2 ** 31 - 1))
    def test_property_matches_sorted_rank(self, P, n_i, q, seed):
        """Fused gk_select == the k=ceil(q*n) entry of the sorted array —
        the same rank convention as jnp.quantile with a 'nearest-above'
        interpolation; checked against the explicit sorted-rank oracle."""
        from repro.core import gk_select
        rng = np.random.default_rng(seed)
        parts = rng.normal(size=(P, n_i)).astype(np.float32)
        k = min(parts.size, max(1, math.ceil(q * parts.size)))
        want = np.sort(parts.ravel())[k - 1]
        got = float(gk_select(jnp.asarray(parts), q, block_select=True))
        assert got == want
