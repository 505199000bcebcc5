"""Checkpoint round-trips for sketch/service state (ISSUE 8): SketchState
and stacked service snapshots across the dtype grid (f32, bf16, i32, and
f64 under x64), restored-warm ``exact()`` bit-parity vs the never-restarted
service, and ``latest_step`` retention with service snapshots interleaved
with model checkpoints.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from _grid import x64_ctx

from repro.checkpoint import (latest_step, restore_checkpoint,
                              restore_checkpoint_flat,
                              restore_service_snapshot, save_checkpoint,
                              save_service_snapshot)
from repro.core import (sketch_init, sketch_stack, sketch_unstack,
                        sketch_update)
from repro.launch import QuantileService

DTYPES = ("float32", "bfloat16", "int32", "float64")


def _case(dtype, n, seed=0):
    rng = np.random.default_rng(seed)
    base = rng.uniform(-1e3, 1e3, size=n)
    if dtype == "int32":
        return np.round(base).astype(np.int32)
    if dtype == "bfloat16":
        import ml_dtypes
        return base.astype(ml_dtypes.bfloat16)
    return base.astype(dtype)


def _leaves_equal(a, b):
    return (np.asarray(a).dtype == np.asarray(b).dtype
            and np.asarray(a).tobytes() == np.asarray(b).tobytes())


class TestSketchStateRoundTrip:
    @pytest.mark.parametrize("dtype", DTYPES)
    def test_single_state_bit_exact(self, dtype, tmp_path):
        with x64_ctx(dtype):
            st = sketch_update(sketch_init(64, jnp.dtype(dtype)),
                               jnp.asarray(_case(dtype, 500)))
            save_checkpoint(str(tmp_path), 1, st)
            back, _ = restore_checkpoint(str(tmp_path), st)
            for a, b in zip(jax.tree.leaves(st), jax.tree.leaves(back)):
                assert _leaves_equal(a, b)

    @pytest.mark.parametrize("dtype", DTYPES)
    def test_stacked_states_bit_exact(self, dtype, tmp_path):
        with x64_ctx(dtype):
            states = [sketch_update(sketch_init(32, jnp.dtype(dtype)),
                                    jnp.asarray(_case(dtype, 200, seed=i)))
                      for i in range(3)]
            stacked = sketch_stack(states)
            save_checkpoint(str(tmp_path), 2, stacked)
            back, _ = restore_checkpoint(str(tmp_path), stacked)
            for orig, rest in zip(states, sketch_unstack(back)):
                for a, b in zip(jax.tree.leaves(orig), jax.tree.leaves(rest)):
                    assert _leaves_equal(a, b)


class TestServiceSnapshotRoundTrip:
    @pytest.mark.parametrize("dtype", DTYPES)
    def test_restored_warm_exact_bit_parity(self, dtype, tmp_path):
        """Restore must be indistinguishable from never restarting: same
        streams, same counts, same warm exact() bits — across the dtype
        grid (bf16 leaves round-trip through the uint16 view; f64 needs
        x64 enabled on both sides)."""
        with x64_ctx(dtype):
            svc = QuantileService(eps=0.05, dtype=jnp.dtype(dtype))
            streams = {f"s{i}": [_case(dtype, 150 + 31 * i, seed=10 * i + t)
                                 for t in range(2)] for i in range(3)}
            for t in range(2):
                names = sorted(streams)
                svc.ingest_batch(names, [streams[n][t] for n in names])
            save_service_snapshot(str(tmp_path), 5, svc)
            restored = restore_service_snapshot(str(tmp_path))

            assert restored.streams() == svc.streams()
            assert restored.dtype == svc.dtype
            for n in streams:
                assert restored.stream_count(n) == svc.stream_count(n)
                assert restored.rank_bound(n) == svc.rank_bound(n)
                for q in (0.001, 0.5, 0.999):
                    a = np.asarray(restored.exact(n, q))
                    b = np.asarray(svc.exact(n, q))
                    assert a.dtype == b.dtype and a.tobytes() == b.tobytes()

    def test_grouped_streams_ride_the_snapshot(self, tmp_path):
        rng = np.random.default_rng(3)
        svc = QuantileService(eps=0.05)
        vals = rng.normal(size=600).astype(np.float32)
        keys = rng.integers(0, 4, size=600).astype(np.int32)
        svc.ingest_grouped("g", vals[:300], keys[:300])
        svc.ingest_grouped("g", vals[300:], keys[300:])
        want = np.asarray(svc.grouped("g", (0.5, 0.9), 4))
        save_service_snapshot(str(tmp_path), 1, svc)
        restored = restore_service_snapshot(str(tmp_path))
        assert restored.grouped_stream_count("g") == 600
        got = np.asarray(restored.grouped("g", (0.5, 0.9), 4))
        assert got.tobytes() == want.tobytes()

    def test_restore_flag_overrides(self, tmp_path):
        svc = QuantileService(eps=0.05, fused=False)
        svc.ingest("s", np.arange(256, dtype=np.float32))
        want = float(svc.exact("s", 0.75))
        save_service_snapshot(str(tmp_path), 1, svc)
        restored = restore_service_snapshot(str(tmp_path), fused=True,
                                            backend="pallas")
        assert restored.fused and restored.backend == "pallas"
        assert float(restored.exact("s", 0.75)) == want


class TestRetentionInterleaving:
    def test_latest_step_and_pruning_with_mixed_snapshots(self, tmp_path):
        """Service snapshots share the step_<N> namespace: interleaved
        model checkpoints and sketch snapshots prune as one sequence and
        ``latest_step`` always names the newest surviving step."""
        d = str(tmp_path)
        svc = QuantileService(eps=0.1, budget=64)
        svc.ingest("s", np.arange(64, dtype=np.float32))
        model = {"w": jnp.arange(8, dtype=jnp.float32)}

        save_checkpoint(d, 1, model, keep=3)
        save_service_snapshot(d, 2, svc, keep=3)
        save_checkpoint(d, 3, model, keep=3)
        assert latest_step(d) == 3
        save_service_snapshot(d, 4, svc, keep=3)
        # keep=3 pruned step_1; the three newest (2, 3, 4) survive
        assert latest_step(d) == 4
        with pytest.raises(FileNotFoundError):
            restore_checkpoint_flat(d, step=1)
        restored = restore_service_snapshot(d, step=2)
        assert restored.stream_count("s") == 64
        back, _ = restore_checkpoint(d, model, step=3)
        assert np.array_equal(np.asarray(back["w"]),
                              np.asarray(model["w"]))
        assert float(restore_service_snapshot(d).exact("s", 0.5)) == 31.0

    def test_model_checkpoint_is_not_a_service_snapshot(self, tmp_path):
        save_checkpoint(str(tmp_path), 1, {"w": jnp.zeros(3)})
        with pytest.raises(ValueError, match="service snapshot"):
            restore_service_snapshot(str(tmp_path))
