"""Slot-table multi-tenant service (ISSUE 8): batched ingest parity vs the
per-stream loop across the oracle-grid axes, O(1) device dispatches per
tick, ``exact_all`` one-job parity + fused pass counts, Quancurrent-style
fold, capacity growth/recycling, snapshot→kill→restore through the
preemption path, and the warm grouped sharded engine on a real mesh.
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from _grid import (DTYPES, DISTRIBUTIONS, QS, make_case, x64_ctx,
                   oracle_kth, ragged_chunks, target_rank)

from repro.core import reset_sketch_sorts, sketch_sorts
from repro.launch import QuantileService
from repro.launch.quantile_service import (ingest_dispatches,
                                           reset_ingest_dispatches)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))



def _batched_and_loop(streams, eps=0.05, **kw):
    """Feed the same {name: [chunks]} once through ingest_batch ticks and
    once through the S=1 per-stream loop; return both services."""
    batched = QuantileService(eps=eps, **kw)
    ticks = max(len(cs) for cs in streams.values())
    for t in range(ticks):
        names = sorted(n for n, cs in streams.items() if t < len(cs))
        batched.ingest_batch(names, [streams[n][t] for n in names])
    loop = QuantileService(eps=eps, **kw)
    for n in sorted(streams):
        for c in streams[n]:
            loop.ingest(n, c)
    return batched, loop


class TestBatchedIngestParity:
    @pytest.mark.parametrize("dtype", DTYPES)
    @pytest.mark.parametrize("dist", DISTRIBUTIONS)
    def test_batched_equals_per_stream_loop_and_oracle(self, dtype, dist):
        """Grid cell: ragged per-tick batches through the slot table must
        answer bit-identically to the pre-refactor-shaped per-stream loop
        AND to the np.partition oracle."""
        with x64_ctx(dtype):
            streams = {
                f"t{i}": ragged_chunks(make_case(dist, dtype, 512, seed=i),
                                       3, seed=i)
                for i in range(4)
            }
            batched, loop = _batched_and_loop(streams, dtype=dtype)
            for name, chunks in streams.items():
                full = np.concatenate(chunks)
                for q in QS:
                    want = oracle_kth(full, target_rank(full.size, q))
                    got_b = np.asarray(batched.exact(name, q))
                    got_l = np.asarray(loop.exact(name, q))
                    assert got_b.tobytes() == got_l.tobytes()
                    assert got_b.tobytes() == np.asarray(want).tobytes(), \
                        (name, q, got_b, want)

    def test_ragged_tick_includes_empty_rows(self):
        """A tick may carry empty batches for some streams — those rows
        must leave their sketch rows and counts bit-untouched."""
        svc = QuantileService(eps=0.05)
        a = np.arange(100, dtype=np.float32)
        svc.ingest_batch(["a", "b"], [a, np.array([], np.float32)])
        assert svc.stream_count("a") == 100
        assert svc.stream_count("b") == 0
        with pytest.raises(ValueError, match="empty"):
            svc.exact("b", 0.5)
        assert float(svc.exact("a", 0.5)) == 49.0

    def test_duplicate_names_in_tick_rejected(self):
        svc = QuantileService()
        with pytest.raises(ValueError, match="duplicate"):
            svc.ingest_batch(["x", "x"], [np.ones(3), np.ones(3)])


class TestDispatchScaling:
    def test_tick_dispatches_constant_in_stream_count(self):
        """The refactor's structural claim: one tick = O(1) jitted device
        calls whether it touches 2 streams or 200 (the dict-of-streams
        design paid O(S))."""
        rng = np.random.default_rng(0)

        def tick(svc, s):
            names = [f"s{i}" for i in range(s)]
            batches = [rng.normal(size=64).astype(np.float32)
                       for _ in range(s)]
            svc.ingest_batch(names, batches)   # registration tick
            reset_ingest_dispatches()
            svc.ingest_batch(names, batches)   # steady-state tick
            return ingest_dispatches()

        d_small = tick(QuantileService(eps=0.1, budget=64), 2)
        d_large = tick(QuantileService(eps=0.1, budget=64), 200)
        assert d_small == d_large, (d_small, d_large)
        assert d_large <= 3

    def test_tick_sorts_once(self):
        """One batched sketch sort per tick, not one per stream."""
        svc = QuantileService(eps=0.1, budget=64)
        rng = np.random.default_rng(1)
        names = [f"s{i}" for i in range(32)]
        reset_sketch_sorts()
        svc.ingest_batch(names, [rng.normal(size=32).astype(np.float32)
                                 for _ in names])
        assert sketch_sorts() == 1


class TestExactAll:
    def test_one_job_matches_per_stream_exact(self):
        rng = np.random.default_rng(2)
        svc = QuantileService(eps=0.05)
        sizes = {f"s{i}": int(rng.integers(40, 300)) for i in range(6)}
        for t in range(3):
            names = sorted(sizes)
            svc.ingest_batch(names, [rng.normal(size=sizes[n]).astype(
                np.float32) for n in names])
        out = svc.exact_all(QS)
        assert sorted(out) == sorted(sizes)
        for name in sizes:
            for j, q in enumerate(QS):
                a = np.asarray(out[name][j])
                b = np.asarray(svc.exact(name, q))
                assert a.tobytes() == b.tobytes(), (name, q)

    def test_warm_and_fused_pass_counts(self):
        """exact_all is the warm path for the whole tenant population: zero
        sketch sorts, and with the fused kernel exactly one HBM pass per
        tick record."""
        from repro.kernels import ops as kernel_ops
        rng = np.random.default_rng(3)
        svc = QuantileService(eps=0.05, fused=True, backend="pallas")
        n_ticks = 4
        for _ in range(n_ticks):
            svc.ingest_batch(["a", "b", "c"],
                             [rng.normal(size=256).astype(np.float32)
                              for _ in range(3)])
        reset_sketch_sorts()
        kernel_ops.reset_hbm_passes()
        out = svc.exact_all((0.5, 0.99))
        assert sketch_sorts() == 0
        assert kernel_ops.hbm_passes() == n_ticks
        for name in ("a", "b", "c"):
            for j, q in enumerate((0.5, 0.99)):
                assert (np.asarray(out[name][j]).tobytes()
                        == np.asarray(svc.exact(name, q)).tobytes())

    def test_empty_service(self):
        assert QuantileService().exact_all((0.5,)) == {}


class TestFold:
    def test_worker_buffers_fold_to_global_answers(self):
        """Quancurrent shape: workers ingest privately, fold merges their
        slot rows in one batched call; folded exact answers match one
        service that saw everything."""
        rng = np.random.default_rng(4)
        chunks = {n: [rng.normal(size=rng.integers(50, 150)).astype(
            np.float32) for _ in range(4)] for n in ("x", "y", "z")}
        shared = QuantileService(eps=0.05)
        w1, w2 = shared.local_buffer(), shared.local_buffer()
        w1.ingest_batch(["x", "y"], [chunks["x"][0], chunks["y"][0]])
        w1.ingest_batch(["x"], [chunks["x"][1]])
        w2.ingest_batch(["y", "z"], [chunks["y"][1], chunks["z"][0]])
        reset_ingest_dispatches()
        shared.fold(w1)
        assert ingest_dispatches() <= 3   # slot growth + one batched merge
        shared.fold(w2)

        ref = QuantileService(eps=0.05)
        for n, cs in (("x", chunks["x"][:2]), ("y", chunks["y"][:2]),
                      ("z", chunks["z"][:1])):
            for c in cs:
                ref.ingest(n, c)
        for n in ("x", "y", "z"):
            assert shared.stream_count(n) == ref.stream_count(n)
            for q in QS:
                assert (np.asarray(shared.exact(n, q)).tobytes()
                        == np.asarray(ref.exact(n, q)).tobytes())

    def test_fold_rejects_mismatched_config(self):
        base = dict(budget=64, eps=0.05)
        mismatches = [
            dict(base, budget=128),
            dict(base, eps=0.01),          # would corrupt cap sizing
            dict(base, fused=not QuantileService(**base).fused),
        ]
        for kwargs in mismatches:
            with pytest.raises(ValueError, match="config mismatch"):
                QuantileService(**base).fold(QuantileService(**kwargs))


class TestSlotTableLifecycle:
    def test_capacity_doubles_and_reads_survive_growth(self):
        svc = QuantileService(eps=0.1, budget=64)
        rng = np.random.default_rng(5)
        kept = rng.normal(size=128).astype(np.float32)
        svc.ingest("keeper", kept)
        want = float(svc.exact("keeper", 0.5))
        for i in range(40):       # force several doublings past capacity 4
            svc.ingest(f"g{i}", rng.normal(size=16).astype(np.float32))
        assert svc._capacity >= 41
        assert float(svc.exact("keeper", 0.5)) == want

    def test_dropped_slot_is_recycled_clean(self):
        svc = QuantileService(eps=0.1, budget=64)
        rng = np.random.default_rng(6)
        svc.ingest("old", rng.normal(size=200).astype(np.float32))
        slot = svc._names["old"]
        svc.drop_stream("old")
        data = rng.normal(size=77).astype(np.float32)
        svc.ingest("new", data)
        assert svc._names["new"] == slot      # slot reused...
        k = target_rank(77, 0.5)
        assert float(svc.exact("new", 0.5)) == float(
            oracle_kth(data, k))              # ...with no leftover state
        assert svc.rank_bound("new") == svc.rank_bound("new")


class TestPreemptionSnapshotRestore:
    def test_snapshot_kill_restore_warm_bit_parity_zero_replay(self, tmp_path):
        """The acceptance path: preemption flag -> snapshot -> process gone
        -> restore -> warm exact() answers bit-identical with ZERO history
        replay (no sketch sort, no re-ingest)."""
        from repro.checkpoint import (restore_service_snapshot,
                                      save_service_snapshot)
        from repro.distributed import PreemptionHandler

        rng = np.random.default_rng(7)
        svc = QuantileService(eps=0.05)
        streams = {f"s{i}": [rng.normal(size=rng.integers(60, 200)).astype(
            np.float32) for _ in range(3)] for i in range(5)}
        for t in range(3):
            names = sorted(streams)
            svc.ingest_batch(names, [streams[n][t] for n in names])
        want = {(n, q): np.asarray(svc.exact(n, q)).tobytes()
                for n in streams for q in QS}

        handler = PreemptionHandler()
        handler.preempt()                      # SIGTERM arrived
        assert handler.should_stop
        save_service_snapshot(str(tmp_path), 11, svc)

        del svc                                # the process is gone
        restored = restore_service_snapshot(str(tmp_path))
        reset_sketch_sorts()
        reset_ingest_dispatches()
        for n in streams:
            for q in QS:
                assert np.asarray(
                    restored.exact(n, q)).tobytes() == want[(n, q)]
        assert sketch_sorts() == 0             # warm: no sketch rebuild
        assert ingest_dispatches() == 0        # zero replayed ingest

    def test_straggler_monitor_rides_the_service_snapshot(self, tmp_path):
        """StragglerMonitor state lives on a service stream, so the
        preemption path restores its decision function exactly."""
        from repro.checkpoint import (restore_service_snapshot,
                                      save_service_snapshot)
        from repro.distributed import StragglerMonitor

        mon = StragglerMonitor(min_samples=10)
        for _ in range(20):
            mon.record({f"h{i}": 1.0 + 0.01 * i for i in range(8)})
        probe = {"h0": 1.0, "h1": 9.0, "h2": 1.05}
        want = mon.decide(probe)
        assert want == ["h1"]

        save_service_snapshot(str(tmp_path), step=3, service=mon.service)
        mon2 = StragglerMonitor(min_samples=10,
                                service=restore_service_snapshot(
                                    str(tmp_path)))
        assert mon2.decide(probe) == want
        assert mon2.service.stream_count(StragglerMonitor.STREAM) == 160


class TestWarmGroupedSharded:
    def test_warm_pivots_on_six_device_mesh(self):
        """The grouped engine's new warm path (pivots= / cap=) on a real
        non-power-of-two mesh: bit-identical to the cold job, zero
        sketch-phase work."""
        prog = textwrap.dedent("""
            import os
            os.environ["XLA_FLAGS"] = \
                "--xla_force_host_platform_device_count=6"
            import numpy as np, jax, jax.numpy as jnp
            from jax.sharding import Mesh
            from repro.core import local_ops
            from repro.core.grouped import distributed_quantile_grouped

            mesh = Mesh(np.array(jax.devices()[:6]), ("data",))
            rng = np.random.default_rng(0)
            n, G = 6 * 512, 4
            vals = rng.normal(size=n).astype(np.float32)
            keys = rng.integers(0, G, size=n).astype(np.int32)
            qs = (0.1, 0.5, 0.999)
            cold = np.asarray(distributed_quantile_grouped(
                jnp.asarray(vals), jnp.asarray(keys), qs, mesh,
                num_groups=G))
            kmat = np.zeros((G, len(qs)), np.int32)
            piv = np.zeros((G, len(qs)), np.float32)
            for g in range(G):
                gv = np.sort(vals[keys == g])
                for j, q in enumerate(qs):
                    k = local_ops.exact_target_rank(gv.size, q)
                    kmat[g, j] = k
                    piv[g, j] = gv[max(0, k - 3)]
            warm = np.asarray(distributed_quantile_grouped(
                jnp.asarray(vals), jnp.asarray(keys), qs, mesh,
                num_groups=G, pivots=jnp.asarray(piv),
                ks=jnp.asarray(kmat), cap=128))
            assert np.array_equal(cold, warm), (cold, warm)
            # warm without ks must refuse
            try:
                distributed_quantile_grouped(
                    jnp.asarray(vals), jnp.asarray(keys), qs, mesh,
                    num_groups=G, pivots=jnp.asarray(piv), cap=128)
            except ValueError as e:
                assert "ks" in str(e)
            else:
                raise AssertionError("warm path without ks must raise")
            print("WARM_GROUPED_OK")
        """)
        paths = [os.path.join(REPO, "src")]
        if os.environ.get("PYTHONPATH"):
            paths.append(os.environ["PYTHONPATH"])
        env = dict(os.environ, PYTHONPATH=os.pathsep.join(paths))
        out = subprocess.run([sys.executable, "-c", prog], env=env,
                             capture_output=True, text=True, timeout=600)
        assert out.returncode == 0, out.stderr
        assert "WARM_GROUPED_OK" in out.stdout
