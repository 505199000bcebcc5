"""Backend-dispatch layer suite: platform selection rules, env overrides,
dtype-specialized tiling, VMEM-budget planning (including the clean
fall-back-to-jnp rejection path), and bit-parity of the jnp fallback vs the
Pallas-interpret kernels for all four kernel entry points."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import dispatch, ops
from repro.kernels.dispatch import (Backend, JNP, PALLAS_GPU,
                                    PALLAS_INTERPRET, PALLAS_TPU)

DTYPES = [np.float32, np.int32, "bfloat16"]


def _make(rng, n, dtype):
    if dtype is np.int32:
        return jnp.asarray(rng.integers(-10 ** 6, 10 ** 6, size=n)
                           .astype(np.int32))
    x = rng.normal(size=n).astype(np.float32)
    if dtype == "bfloat16":
        return jnp.asarray(x, jnp.bfloat16)
    return jnp.asarray(x)


class TestBackendSelection:
    def test_platform_defaults(self):
        assert dispatch.select_backend("tpu") is PALLAS_TPU
        assert dispatch.select_backend("gpu") is PALLAS_GPU
        assert dispatch.select_backend("cuda") is PALLAS_GPU
        assert dispatch.select_backend("rocm") is PALLAS_GPU
        assert dispatch.select_backend("cpu") is JNP

    def test_pallas_alias_is_platform_native(self):
        assert dispatch.resolve("pallas", "tpu") is PALLAS_TPU
        assert dispatch.resolve("native", "gpu") is PALLAS_GPU
        assert dispatch.resolve("pallas", "cpu") is PALLAS_INTERPRET

    def test_named_and_alias_specs(self):
        assert dispatch.resolve("jnp", "tpu") is JNP
        assert dispatch.resolve("interpret", "tpu") is PALLAS_INTERPRET
        assert dispatch.resolve("pallas_interpret", "cpu") is PALLAS_INTERPRET
        assert dispatch.resolve("auto", "cpu") is JNP
        assert dispatch.resolve("auto", "tpu") is PALLAS_TPU

    def test_backend_instance_passes_through(self):
        bk = Backend("custom", "jnp", False, True, 1, 1)
        assert dispatch.resolve(bk) is bk

    def test_unknown_spec_raises(self):
        with pytest.raises(ValueError, match="unknown backend"):
            dispatch.resolve("cudnn", "cpu")

    def test_env_override(self, monkeypatch):
        monkeypatch.setenv("REPRO_BACKEND", "pallas_interpret")
        assert dispatch.select_backend("tpu") is PALLAS_INTERPRET
        monkeypatch.setenv("REPRO_BACKEND", "jnp")
        assert dispatch.select_backend("tpu") is JNP

    def test_legacy_native_env(self, monkeypatch):
        monkeypatch.delenv("REPRO_BACKEND", raising=False)
        monkeypatch.setenv("REPRO_PALLAS_NATIVE", "1")
        assert dispatch.select_backend("cpu") is PALLAS_INTERPRET
        assert dispatch.select_backend("tpu") is PALLAS_TPU


class TestTiling:
    def test_lanes_for_dtype(self):
        assert dispatch.lanes_for(jnp.float32) == 1024
        assert dispatch.lanes_for(jnp.int32) == 1024
        assert dispatch.lanes_for(jnp.bfloat16) == 2048
        assert dispatch.lanes_for(jnp.float16) == 2048
        assert dispatch.lanes_for(jnp.int8) == 4096

    def test_pad_to_lanes_shapes(self):
        x = jnp.arange(1500, dtype=jnp.float32)
        x2d = dispatch.pad_to_lanes(x, 1024)
        assert x2d.shape == (8, 1024)        # rows: one f32 sublane tile
        assert dispatch.pad_to_lanes(x.astype(jnp.bfloat16),
                                     2048).shape == (16, 2048)
        np.testing.assert_array_equal(np.asarray(x2d.ravel()[:1500]),
                                      np.asarray(x))

    def test_plan_jnp_has_no_tiling(self):
        p = dispatch.plan(JNP, "fused_select", jnp.float32, 1 << 20)
        assert p.backend is JNP and p.lanes == 0 and p.block_rows == 0

    def test_plan_block_rows_pow2_and_budgeted(self):
        p = dispatch.plan(PALLAS_INTERPRET, "partition_count",
                          jnp.float32, 1 << 22)
        assert p.backend is PALLAS_INTERPRET
        assert p.lanes == 1024
        assert p.block_rows & (p.block_rows - 1) == 0      # power of two
        assert p.vmem_bytes <= PALLAS_INTERPRET.vmem_budget

    def test_plan_bf16_gets_wide_lanes(self):
        p = dispatch.plan(PALLAS_INTERPRET, "partition_count",
                          jnp.bfloat16, 1 << 20)
        assert p.lanes == 2048

    def test_plan_clamps_to_rows(self):
        p = dispatch.plan(PALLAS_INTERPRET, "partition_count",
                          jnp.float32, 100)
        assert p.block_rows == 8             # one sublane tile of rows


class TestVMEMRejection:
    TINY = Backend("tiny", "pallas", interpret=True, compiled=False,
                   vmem_budget=4096, tile_bytes=512)

    def test_plan_falls_back_to_jnp_with_reason(self):
        p = dispatch.plan(self.TINY, "fused_select", jnp.float32, 1 << 16,
                          footprint=dispatch._select_footprint(
                              jnp.float32, 1, 128))
        assert p.backend is JNP
        assert "VMEM budget" in p.reason and "fell back to jnp" in p.reason

    def test_huge_residents_reject_even_on_tpu_budget(self):
        # 1280 (group, level) rows x two double-buffered 1024-lane f32
        # buffers are 21 MB of residents: past the whole 16 MiB budget.
        # A compiled backend refuses instead of running jnp in its place.
        with pytest.raises(ValueError, match="exceed pallas_tpu VMEM"):
            dispatch.plan(PALLAS_TPU, "segmented_select", jnp.float32,
                          1 << 20, footprint=dispatch._select_footprint(
                              jnp.float32, 1280, 1024, streams=2))

    def test_oversized_tile_runs_clean_end_to_end(self):
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=10_000).astype(np.float32))
        got, p = dispatch.run_fused_select(x, x[0], 64, backend=self.TINY)
        assert p.backend is JNP     # rejected the tiny budget, ran jnp
        want, _ = dispatch.run_fused_select(x, x[0], 64, backend="jnp")
        for g, w in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


class TestBackendParity:
    """Bit-parity of the jnp fallback vs the Pallas-interpret kernels for
    all four kernel entry points, across the oracle-grid dtypes."""

    @pytest.mark.parametrize("dtype", DTYPES)
    @pytest.mark.parametrize("n", [100, 4096, 5000])
    def test_partition_and_band_count(self, n, dtype):
        rng = np.random.default_rng(n)
        x = _make(rng, n, dtype)
        pivot = x[n // 2]
        cp, pp = dispatch.run_partition_count(x, pivot, backend="interpret")
        cj, pj = dispatch.run_partition_count(x, pivot, backend="jnp")
        assert pp.backend.kind == "pallas" and pj.backend is JNP
        np.testing.assert_array_equal(np.asarray(cp), np.asarray(cj))
        lo, hi = (x[n // 3], pivot) if bool(x[n // 3] < pivot) \
            else (pivot, x[n // 3])
        bp, _ = dispatch.run_band_count(x, lo, hi, backend="interpret")
        bj, _ = dispatch.run_band_count(x, lo, hi, backend="jnp")
        assert int(bp) == int(bj)

    @pytest.mark.parametrize("dtype", DTYPES)
    @pytest.mark.parametrize("n", [100, 4096, 5000])
    def test_fused_select_single_and_multi(self, n, dtype):
        rng = np.random.default_rng(n + 1)
        x = _make(rng, n, dtype)
        cap = max(1, n // 50)
        fp, _ = dispatch.run_fused_select(x, x[n // 2], cap,
                                          backend="interpret")
        fj, _ = dispatch.run_fused_select(x, x[n // 2], cap, backend="jnp")
        for g, w in zip(fp, fj):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
        pivots = jnp.stack([x[1], x[n // 2], x[n - 1]])
        mp, _ = dispatch.run_fused_select_multi(x, pivots, cap,
                                                backend="interpret")
        mj, _ = dispatch.run_fused_select_multi(x, pivots, cap,
                                                backend="jnp")
        for g, w in zip(mp, mj):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))

    @pytest.mark.parametrize("dtype", DTYPES)
    def test_segmented_select(self, dtype):
        rng = np.random.default_rng(3)
        n, G, Q = 4096, 5, 2
        x = _make(rng, n, dtype)
        keys = jnp.asarray(rng.integers(0, G, size=n).astype(np.int32))
        pivots = jnp.stack([x[:G], x[G:2 * G]], axis=1)
        sp, _ = dispatch.run_segmented_select(x, keys, pivots, 64,
                                              backend="interpret")
        sj, _ = dispatch.run_segmented_select(x, keys, pivots, 64,
                                              backend="jnp")
        for g, w in zip(sp, sj):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))

    def test_byte_histogram(self):
        rng = np.random.default_rng(4)
        u = ops.to_sortable_u32(
            jnp.asarray(rng.normal(size=4096).astype(np.float32)))
        z = jnp.uint32(0)
        for shift, prefix, mask in [(24, z, z),
                                    (16, u[0] & jnp.uint32(0xFF000000),
                                     jnp.uint32(0xFF000000))]:
            hp, _ = dispatch.run_byte_histogram(u, prefix, mask, shift,
                                                backend="interpret")
            hj, _ = dispatch.run_byte_histogram(u, prefix, mask, shift,
                                                backend="jnp")
            np.testing.assert_array_equal(np.asarray(hp), np.asarray(hj))


class TestOpsDispatch:
    def test_use_pallas_false_is_jnp_alias(self):
        rng = np.random.default_rng(5)
        x = jnp.asarray(rng.normal(size=2048).astype(np.float32))
        ops.reset_hbm_passes()
        ops.fused_count_extract(x, x[0], 32, use_pallas=False)
        assert ops.hbm_passes() == 3     # the jnp oracle's honest count

    def test_backend_threads_through_jit(self):
        # str / Backend / None specs are all hashable static args
        from repro.core import gk_select
        rng = np.random.default_rng(6)
        parts = jnp.asarray(rng.normal(size=(4, 1024)).astype(np.float32))
        want = float(np.sort(np.asarray(parts).ravel())[2047])
        for bk in [None, "jnp", "interpret", JNP]:
            got = gk_select(parts, 0.5, block_select=True, backend=bk)
            assert float(got) == want, bk

    def test_run_entry_points_slice_to_cap(self):
        rng = np.random.default_rng(7)
        x = jnp.asarray(rng.normal(size=4096).astype(np.float32))
        cap = 37                         # deliberately not a lane multiple
        (c, b, a), _ = dispatch.run_fused_select(x, x[0], cap,
                                                 backend="interpret")
        assert b.shape == (cap,) and a.shape == (cap,)
