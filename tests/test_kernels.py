"""Per-kernel validation: shape/dtype sweeps against the pure-jnp ref.py
oracles (interpret=True executes the Pallas kernel bodies on CPU), plus the
radix-select composition and hypothesis property tests."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels import ops
from repro.kernels.ref import (partition_count_ref, band_count_ref,
                               block_topk_ref)

SHAPES = [7, 100, 1024, 1025, 4096, 65536]
DTYPES = [np.float32, np.int32, "bfloat16"]


def _make(rng, n, dtype):
    if dtype is np.int32:
        return rng.integers(-10 ** 6, 10 ** 6, size=n).astype(np.int32)
    x = rng.normal(size=n).astype(np.float32)
    if dtype == "bfloat16":
        return jnp.asarray(x, jnp.bfloat16)
    return x


class TestPartitionCount:
    @pytest.mark.parametrize("n", SHAPES)
    @pytest.mark.parametrize("dtype", DTYPES)
    def test_sweep_vs_oracle(self, n, dtype):
        rng = np.random.default_rng(n)
        x = jnp.asarray(_make(rng, n, dtype))
        pivot = x[n // 2]
        got = np.asarray(ops.count3(x, pivot))
        want = np.asarray(partition_count_ref(x, pivot))
        assert np.array_equal(got, want), (n, dtype)
        assert got.sum() == n

    def test_block_rows_sweep(self):
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=300_000).astype(np.float32))
        want = np.asarray(partition_count_ref(x, x[17]))
        from repro.kernels.partition_count import partition_count
        for br in [8, 64, 256]:
            x2d = ops.pad_to_tiles(x)
            got = np.asarray(partition_count(x2d, x[17], n_valid=x.size,
                                             block_rows=br, interpret=True))
            assert np.array_equal(got, want), br

    @settings(max_examples=20, deadline=None)
    @given(st.integers(1, 5000), st.integers(0, 2 ** 31 - 1))
    def test_property(self, n, seed):
        rng = np.random.default_rng(seed)
        x = jnp.asarray(rng.integers(-50, 50, size=n).astype(np.int32))
        pivot = x[rng.integers(0, n)]
        got = np.asarray(ops.count3(x, pivot))
        xa = np.asarray(x)
        p = int(pivot)
        assert got[0] == (xa < p).sum()
        assert got[1] == (xa == p).sum()
        assert got[2] == (xa > p).sum()


class TestBandCount:
    @pytest.mark.parametrize("n", SHAPES)
    @pytest.mark.parametrize("dtype", [np.float32, np.int32])
    def test_sweep_vs_oracle(self, n, dtype):
        rng = np.random.default_rng(n + 1)
        x = jnp.asarray(_make(rng, n, dtype))
        xa = np.asarray(x, np.float64)
        lo = jnp.asarray(np.quantile(xa, 0.25).astype(x.dtype))
        hi = jnp.asarray(np.quantile(xa, 0.75).astype(x.dtype))
        got = int(ops.band_count(x, lo, hi))
        want = int(band_count_ref(x, lo, hi))
        assert got == want


class TestRadixSelect:
    @pytest.mark.parametrize("dtype", DTYPES)
    def test_exact_kth(self, dtype):
        rng = np.random.default_rng(2)
        x = jnp.asarray(_make(rng, 4096, dtype))
        srt = np.sort(np.asarray(x, np.float32 if dtype == "bfloat16"
                                 else None))
        for k in [1, 5, 2048, 4096]:
            got = ops.radix_select_kth(x, jnp.int32(k))
            assert np.float32(got) == np.float32(srt[k - 1]), (dtype, k)

    def test_sortable_transform_roundtrip(self):
        rng = np.random.default_rng(3)
        x = jnp.asarray(rng.normal(size=1000).astype(np.float32))
        u = ops.to_sortable_u32(x)
        back = ops.from_sortable_u32(u, jnp.float32)
        assert np.array_equal(np.asarray(back), np.asarray(x))
        # order preservation
        xa = np.asarray(x)
        ua = np.asarray(u)
        order_x = np.argsort(xa, kind="stable")
        order_u = np.argsort(ua, kind="stable")
        assert np.array_equal(xa[order_x], xa[order_u])

    @settings(max_examples=20, deadline=None)
    @given(st.integers(2, 2000), st.integers(0, 2 ** 31 - 1))
    def test_property_exact(self, n, seed):
        rng = np.random.default_rng(seed)
        x = jnp.asarray(rng.normal(size=n).astype(np.float32))
        k = int(rng.integers(1, n + 1))
        got = float(ops.radix_select_kth(x, jnp.int32(k)))
        assert got == np.sort(np.asarray(x))[k - 1]


class TestBlockTopkOracle:
    """ref.block_topk semantics used by candidate extraction."""

    def test_below_above(self):
        rng = np.random.default_rng(4)
        x = jnp.asarray(rng.normal(size=512).astype(np.float32))
        pivot = x[100]
        below = np.asarray(block_topk_ref(x, pivot, 16, largest_below=True))
        above = np.asarray(block_topk_ref(x, pivot, 16, largest_below=False))
        xa = np.asarray(x)
        want_b = np.sort(xa[xa < float(pivot)])[::-1][:16]
        want_a = np.sort(xa[xa > float(pivot)])[:16]
        assert np.array_equal(below[:len(want_b)], want_b)
        assert np.array_equal(above[:len(want_a)], want_a)


class TestKernelInjectedSelect:
    def test_gk_select_with_pallas_count(self):
        """End-to-end: distributed GK Select body with the Pallas count3."""
        from repro.core import gk_select
        from repro.core import local_ops
        rng = np.random.default_rng(5)
        parts = rng.normal(size=(4, 2048)).astype(np.float32)
        want = float(gk_select(jnp.asarray(parts), 0.5))
        # vmapped pallas count matches local count on each row
        for row in parts:
            a = np.asarray(ops.count3(jnp.asarray(row), jnp.float32(want)))
            b = np.asarray(local_ops.count3(jnp.asarray(row), jnp.float32(want)))
            assert np.array_equal(a, b)
