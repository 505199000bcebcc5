"""Launch-layer units: HLO analyzer parsing/trip counts, roofline math and
peak table, chip set-up (compile cache, device check), sharding
divisibility rules, dry-run shape applicability, multi-bit radix."""
import functools
import math

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import REGISTRY
from repro.launch import hlo_analysis as ha
from repro.launch import roofline as rf
from repro.launch.steps import SHAPES, shape_applicable
from repro.optim.quantile_ops import pytree_radix_quantile


class TestHloAnalyzer:
    def test_matmul_flops_exact(self):
        A = jax.ShapeDtypeStruct((256, 256), jnp.float32)
        c = jax.jit(lambda a, b: a @ b).lower(A, A).compile()
        r = ha.analyze(c.as_text())
        assert r["flops"] == 2 * 256 ** 3

    def test_scan_trip_count_multiplication(self):
        W = jax.ShapeDtypeStruct((7, 64, 64), jnp.float32)
        x0 = jax.ShapeDtypeStruct((4, 64), jnp.float32)

        def f(ws, x):
            def body(h, w):
                return jnp.tanh(h @ w), None
            return jax.lax.scan(body, x, ws)[0]

        c = jax.jit(f).lower(W, x0).compile()
        r = ha.analyze(c.as_text())
        assert r["flops"] == 7 * 2 * 4 * 64 * 64
        # XLA's own analysis under-counts (while body once)
        ca = c.cost_analysis()
        if isinstance(ca, list):   # older jax returns [dict]
            ca = ca[0]
        assert ca["flops"] < r["flops"]

    def test_type_bytes(self):
        assert ha._type_bytes("bf16[2,3]") == 12
        assert ha._type_bytes("f32[10]{0}") == 40
        assert ha._type_bytes("(f32[2], s32[4])") == 24
        assert ha._type_bytes("pred[]") == 1


class TestRoofline:
    def test_terms_and_dominance(self):
        t = rf.roofline_terms(flops=197e12, bytes_accessed=819e9,
                              collective_bytes_per_chip=25e9, chips=256)
        assert abs(t["compute_s"] - 1.0) < 1e-9
        assert abs(t["memory_s"] - 1.0) < 1e-9
        assert abs(t["collective_s"] - 0.5) < 1e-9
        assert t["dominant"] in ("compute", "memory")

    def test_model_flops(self):
        cfg = REGISTRY["olmoe-1b-7b"]
        train = rf.model_flops(cfg, tokens=1000, kind="train")
        serve = rf.model_flops(cfg, tokens=1000, kind="decode")
        assert train == 3 * serve
        # MoE: active < total params
        assert cfg.active_param_count() < cfg.param_count()


    def test_peak_keyed_by_device_kind(self):
        assert rf.peak_hbm_bandwidth("TPU v5 lite") == 819e9
        kr = rf.kernel_roofline(819e9, 2.0, "TPU v5 lite")
        assert kr["frac_of_peak"] == 0.5

    def test_unknown_device_has_no_peak(self):
        with pytest.raises(KeyError, match="no peak HBM bandwidth"):
            rf.peak_hbm_bandwidth("cpu")


class TestChipSetup:
    def test_compile_cache_env_dir_is_kept(self, monkeypatch):
        from repro.launch import chip
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert chip.enable_compile_cache() == "/elsewhere"

    def test_compile_cache_default_is_fixed_repo_path(self, monkeypatch):
        from repro.launch import chip
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        was = jax.config.jax_compilation_cache_dir
        try:
            assert chip.enable_compile_cache() == str(chip.CACHE_DIR)
            assert jax.config.jax_compilation_cache_dir == str(chip.CACHE_DIR)
            assert chip.CACHE_DIR.parent == chip.REPO
        finally:
            jax.config.update("jax_compilation_cache_dir", was)

    def test_no_accelerator_is_refused(self):
        from repro.launch import chip
        platform = jax.devices()[0].platform
        assert chip.require_accelerator(platform)
        other = "tpu" if platform != "tpu" else "cpu"
        with pytest.raises(RuntimeError, match=f"no {other} device"):
            chip.require_accelerator(other)
        rec = chip.device_record()
        assert rec["platform"] == platform
        assert rec["count"] == len(jax.devices())


class TestShapeRules:
    def test_all_cells_defined(self):
        assert set(SHAPES) == {"train_4k", "prefill_32k", "decode_32k",
                               "long_500k"}

    def test_long_500k_gating(self):
        ok, _ = shape_applicable(REGISTRY["mamba2-1.3b"], "long_500k")
        assert ok
        ok, why = shape_applicable(REGISTRY["granite-8b"], "long_500k")
        assert not ok and "sub-quadratic" in why

    def test_param_spec_divisibility_guard(self):
        """Non-divisible dims (vocab 50280 over 16) must drop the axis."""
        import os
        from repro.launch import sharding as shd
        from repro.models import model
        # fabricate a mesh-like object with .shape mapping
        class FakeMesh:
            shape = {"data": 16, "model": 16}
        leaf = jax.ShapeDtypeStruct((50280, 2048), jnp.bfloat16)
        path = (jax.tree_util.DictKey("embed"),)
        spec = shd.param_spec(path, leaf, FakeMesh())
        assert spec[0] is None            # 50280 % 16 != 0 -> replicated
        assert spec[1] == "data"          # 2048 % 16 == 0 -> sharded


class TestMultiBitRadix:
    @pytest.mark.parametrize("bits", [1, 4, 8])
    def test_exact_all_widths(self, bits):
        rng = np.random.default_rng(bits)
        tree = {"g": jnp.asarray(rng.normal(size=2048).astype(np.float32))}
        srt = np.sort(np.abs(np.asarray(tree["g"])))
        for q in [0.25, 0.9, 0.999]:
            k = min(2048, max(1, math.ceil(q * 2048)))
            got = float(jax.jit(functools.partial(
                pytree_radix_quantile, q=q, bits_per_pass=bits))(tree))
            assert got == srt[k - 1], (bits, q)
