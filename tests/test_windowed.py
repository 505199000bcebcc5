"""Windowed & time-decayed quantiles (ISSUE 10, DESIGN.md §11).

The tentpole claims, each pinned here:

  * ``windowed(name, q, window=...)`` is BIT-exact against the sort of the
    raw window population — across the dtype × distribution grid, for both
    tick- and count-based windows, warm (sub-window merge pivot) and on an
    unwindowed service (cold per-window pivot).
  * Window boundaries are exact to the tick: expiry off-by-one, window
    covering all history == unwindowed ``exact()``, window past the
    retention horizon raises (unless full history is still resident).
  * Windowed memory is bounded by the window, not by history: the ring
    holds <= window_ticks records and a stream parks at most
    ``window_subs + 1`` sub-window rows, forever.
  * The warm windowed query dispatches ZERO sketch-phase sorts.
  * Window state rides the snapshot: a restored service answers
    bit-identically, resumes warm, and continued ingest stays bit-parity
    with a never-restarted twin.
  * ``approx_decayed`` weights recent sub-windows up: after a regime
    change, a small halflife tracks the new regime, a huge one the
    all-history mix.
"""
import numpy as np
import pytest

from repro.core import reset_sketch_sorts, sketch_sorts
from repro.launch import QuantileService, Window

from _grid import (DTYPES, DISTRIBUTIONS, QS, make_case, x64_ctx,
                   oracle_kth, target_rank)


def _tick_chunks(dist, dtype, ticks, seed=0):
    """One grid case split into ``ticks`` ragged per-tick batches (some
    small, none empty)."""
    rng = np.random.default_rng(seed)
    sizes = rng.integers(3, 40, size=ticks)
    return [make_case(dist, dtype, int(s), seed=seed * 1000 + t)
            for t, s in enumerate(sizes)]


def _assert_bits(got, want, msg):
    assert np.asarray(got).tobytes() == np.asarray(want).tobytes(), \
        (msg, got, want)


class TestWindowedOracleGrid:
    """Acceptance criterion: bit-exact vs the sorted raw window across the
    dtype/distribution grid."""

    @pytest.mark.parametrize("dtype", DTYPES)
    @pytest.mark.parametrize("dist", DISTRIBUTIONS)
    def test_tick_windows_bit_exact(self, dist, dtype):
        with x64_ctx(dtype):
            chunks = _tick_chunks(dist, dtype, ticks=14, seed=3)
            svc = QuantileService(eps=0.05, dtype=dtype,
                                  window_ticks=8, window_subs=4)
            for c in chunks:
                svc.ingest("s", c)
            for w in (1, 3, 8):
                vals = np.concatenate(chunks[-w:])
                for q in QS:
                    want = oracle_kth(vals, target_rank(vals.size, q))
                    _assert_bits(svc.windowed("s", q, window=w), want,
                                 (dist, dtype, w, q))

    @pytest.mark.parametrize("dtype", DTYPES)
    @pytest.mark.parametrize("dist", DISTRIBUTIONS)
    def test_count_windows_bit_exact(self, dist, dtype):
        with x64_ctx(dtype):
            chunks = _tick_chunks(dist, dtype, ticks=14, seed=7)
            svc = QuantileService(eps=0.05, dtype=dtype,
                                  window_ticks=8, window_subs=4)
            for c in chunks:
                svc.ingest("s", c)
            retained = sum(c.size for c in chunks[-8:])
            full = np.concatenate(chunks)
            for n_want in (1, 5, retained // 2, retained):
                vals = full[-n_want:]
                for q in QS:
                    want = oracle_kth(vals, target_rank(vals.size, q))
                    _assert_bits(
                        svc.windowed("s", q, window=Window(values=n_want)),
                        want, (dist, dtype, n_want, q))

    @pytest.mark.parametrize("dist", DISTRIBUTIONS)
    def test_unwindowed_service_cold_window(self, dist):
        """windowed() works on a plain service too (everything retained,
        cold per-window pivot) — same oracle."""
        chunks = _tick_chunks(dist, "float32", ticks=10, seed=11)
        svc = QuantileService(eps=0.05)
        for c in chunks:
            svc.ingest("s", c)
        for w in (2, 10):           # any width: nothing is ever retired
            vals = np.concatenate(chunks[-w:])
            for q in QS:
                want = oracle_kth(vals, target_rank(vals.size, q))
                _assert_bits(svc.windowed("s", q, window=w), want,
                             (dist, w, q))

    def test_multi_stream_windows_independent(self):
        """Per-stream windows slice only that stream's rows out of shared
        tick records."""
        rng = np.random.default_rng(5)
        svc = QuantileService(eps=0.05, window_ticks=6, window_subs=3)
        host = {n: [] for n in ("a", "b", "c")}
        for t in range(15):
            names = [n for n in host if rng.random() < 0.8] or ["a"]
            batches = [rng.normal(size=rng.integers(4, 20)
                                  ).astype(np.float32) for _ in names]
            svc.ingest_batch(names, batches)
            for n, b in zip(names, batches):
                host[n].append((t, b))
        for n, fed in host.items():
            for w in (2, 6):
                vals = np.concatenate(
                    [b for t, b in fed if t >= 15 - w] or
                    [np.array([], np.float32)])
                if vals.size == 0:
                    with pytest.raises(ValueError, match="no values"):
                        svc.windowed(n, 0.5, window=w)
                    continue
                want = oracle_kth(vals, target_rank(vals.size, 0.5))
                _assert_bits(svc.windowed(n, 0.5, window=w), want, (n, w))


class TestWindowBoundaries:
    """Satellite: expiry off-by-one, window > retained, window == all
    history, warm restore."""

    def test_expiry_off_by_one(self):
        """Tick t's batch is [t]*3: a window of w ticks after T ticks must
        see exactly values T-w..T-1 — min and max pin both edges."""
        svc = QuantileService(eps=0.05, window_ticks=5, window_subs=2)
        T = 12
        for t in range(T):
            svc.ingest("s", np.full(3, float(t), np.float32))
        for w in (1, 2, 5):
            lo = float(svc.windowed("s", 0.001, window=w))
            hi = float(svc.windowed("s", 0.999, window=w))
            assert lo == float(T - w), (w, lo)
            assert hi == float(T - 1), (w, hi)
            assert svc.window_count("s", window=w) == 3 * w

    def test_window_past_retention_raises(self):
        svc = QuantileService(eps=0.05, window_ticks=4, window_subs=2)
        for t in range(9):
            svc.ingest("s", np.full(2, float(t), np.float32))
        with pytest.raises(ValueError, match="retention horizon"):
            svc.windowed("s", 0.5, window=5)
        with pytest.raises(ValueError, match="retention horizon"):
            svc.windowed("s", 0.5, window=Window(values=9))
        # the widest retained window still answers
        assert float(svc.windowed("s", 0.999, window=4)) == 8.0
        assert float(
            svc.windowed("s", 0.999, window=Window(values=8))) == 8.0

    def test_window_covering_all_history_matches_exact(self):
        """While nothing has been retired, ANY window >= history is the
        all-history answer — bit-identical to unwindowed exact()."""
        chunks = _tick_chunks("uniform", "float32", ticks=6, seed=2)
        svc = QuantileService(eps=0.05, window_ticks=8, window_subs=4)
        for c in chunks:
            svc.ingest("s", c)
        n = sum(c.size for c in chunks)
        for q in QS:
            want = svc.exact("s", q)            # history < window: allowed
            _assert_bits(svc.windowed("s", q, window=6), want, q)
            _assert_bits(svc.windowed("s", q, window=8), want, q)
            _assert_bits(svc.windowed("s", q, window=Window(values=n)),
                         want, q)

    def test_exact_raises_after_retention_kicks_in(self):
        svc = QuantileService(eps=0.05, window_ticks=3, window_subs=3)
        for t in range(3):
            svc.ingest("s", np.ones(4, np.float32))
        svc.exact("s", 0.5)                     # all resident: still fine
        svc.exact_all((0.5,))
        svc.ingest("s", np.ones(4, np.float32))
        with pytest.raises(ValueError, match="windowed"):
            svc.exact("s", 0.5)
        with pytest.raises(ValueError, match="windowed"):
            svc.exact_all((0.5,))
        float(svc.approx("s", 0.5))             # approx stays available

    def test_window_spec_validation(self):
        svc = QuantileService(eps=0.05, window_ticks=4)
        svc.ingest("s", np.ones(4, np.float32))
        with pytest.raises(ValueError, match="exactly one"):
            Window()
        with pytest.raises(ValueError, match="exactly one"):
            Window(ticks=2, values=3)
        with pytest.raises(ValueError, match="positive"):
            svc.windowed("s", 0.5, window=0)
        with pytest.raises(ValueError, match="window_ticks"):
            QuantileService(window_ticks=0)
        with pytest.raises(ValueError, match="window_subs"):
            QuantileService(window_ticks=4, window_subs=0)

    def test_warm_windowed_query_skips_sketch_sorts(self):
        svc = QuantileService(eps=0.05, window_ticks=8, window_subs=4)
        for t in range(12):
            svc.ingest("s", np.arange(10, dtype=np.float32) + t)
        reset_sketch_sorts()
        float(svc.windowed("s", 0.5, window=4))
        assert sketch_sorts() == 0, "windowed warm path must not re-sort"


class TestWindowSnapshot:
    """Satellite: snapshot/restore of window state resumes warm."""

    def test_restore_answers_and_resumes_warm(self):
        rng = np.random.default_rng(9)
        svc = QuantileService(eps=0.05, window_ticks=6, window_subs=3)
        twin = QuantileService(eps=0.05, window_ticks=6, window_subs=3)
        feed = [rng.normal(size=rng.integers(5, 25)).astype(np.float32)
                for _ in range(15)]
        for c in feed:
            svc.ingest("s", c)
            twin.ingest("s", c)
        leaves, extra = svc.snapshot()
        assert extra["format"] == 2
        restored = QuantileService.from_snapshot(leaves, extra)
        assert restored.window_ticks == 6
        # warm: the restored windowed query must not re-sort anything
        reset_sketch_sorts()
        for w in (2, 6):
            _assert_bits(restored.windowed("s", 0.5, window=w),
                         svc.windowed("s", 0.5, window=w), w)
        assert sketch_sorts() == 0
        _assert_bits(restored.approx_decayed("s", 0.9, halflife=3.0),
                     svc.approx_decayed("s", 0.9, halflife=3.0), "decay")
        # continued ingest: restored twin stays bit-parity with the
        # never-restarted one, including sub-window rotation + retirement
        more = [rng.normal(size=rng.integers(5, 25)).astype(np.float32)
                for _ in range(8)]
        for c in more:
            restored.ingest("s", c)
            twin.ingest("s", c)
        for w in (1, 4, 6):
            for q in QS:
                _assert_bits(restored.windowed("s", q, window=w),
                             twin.windowed("s", q, window=w), (w, q))
        assert restored.window_count("s", window=6) == \
            twin.window_count("s", window=6)

    def test_format1_snapshot_still_restores(self):
        """A pre-window snapshot (format 1) restores as an unwindowed
        service; windowed() still answers via the cold path."""
        svc = QuantileService(eps=0.05)
        for t in range(4):
            svc.ingest("s", np.arange(6, dtype=np.float32) + 10 * t)
        leaves, extra = svc.snapshot()
        extra = {k: v for k, v in extra.items()
                 if k not in ("window_ticks", "window_subs", "tick",
                              "ring_ticks", "retained", "subs")}
        extra["format"] = 1
        restored = QuantileService.from_snapshot(leaves, extra)
        assert restored.window_ticks is None
        _assert_bits(restored.exact("s", 0.5), svc.exact("s", 0.5), "exact")
        _assert_bits(restored.windowed("s", 0.5, window=2),
                     svc.windowed("s", 0.5, window=2), "windowed")


class TestWindowedMemoryBound:
    """Acceptance criterion: memory bounded by W × sketch budget,
    independent of total history length."""

    def test_resident_footprint_flat_in_history(self):
        stats = {}
        for ticks in (16, 64, 256):
            svc = QuantileService(eps=0.1, budget=64,
                                  window_ticks=8, window_subs=4)
            for t in range(ticks):
                svc.ingest("s", np.full(16, float(t), np.float32))
            stats[ticks] = svc.memory_stats()
        flat = {k: {m["resident_values"] for m in stats.values()}
                for k in ("resident_values",)}
        assert len(flat["resident_values"]) == 1, stats
        m = stats[256]
        assert m["ring_records"] <= 8
        # one main row + at most window_subs + 1 sub rows
        assert m["live_rows"] <= 1 + 4 + 1

    def test_idle_stream_parks_bounded_sub_rows(self):
        """A stream that stops being fed keeps <= window_subs + 1 sub rows
        parked (lazy retirement never exceeds the rotation bound)."""
        svc = QuantileService(eps=0.1, budget=64,
                              window_ticks=8, window_subs=4)
        for t in range(20):
            svc.ingest("idle" if t < 10 else "hot",
                       np.full(4, float(t), np.float32))
        assert len(svc._subs[svc._names["idle"]]) <= 5


class TestDecay:
    def test_decay_tracks_regime_change(self):
        """Early regime ~100, late regime ~1: a short halflife pulls the
        decayed median toward the recent regime; a huge halflife stays
        near the undecayed (mixed) median."""
        svc = QuantileService(eps=0.02, window_ticks=32, window_subs=8)
        rng = np.random.default_rng(4)
        for _ in range(24):
            svc.ingest("s", (100 + rng.random(16)).astype(np.float32))
        for _ in range(8):
            svc.ingest("s", (1 + rng.random(16)).astype(np.float32))
        fast = float(svc.approx_decayed("s", 0.5, halflife=2.0))
        slow = float(svc.approx_decayed("s", 0.5, halflife=10_000.0))
        mixed = float(svc.windowed("s", 0.5, window=32))
        assert fast < 3.0, fast              # recent regime dominates
        assert abs(slow - mixed) < 60.0, (slow, mixed)
        assert slow > fast

    def test_decay_needs_window_and_data(self):
        svc = QuantileService(eps=0.05)
        svc.ingest("s", np.ones(4, np.float32))
        with pytest.raises(ValueError, match="windowed service"):
            svc.approx_decayed("s", 0.5, halflife=4.0)
        wsvc = QuantileService(eps=0.05, window_ticks=4)
        wsvc.ingest("s", np.ones(4, np.float32))
        with pytest.raises(ValueError, match="halflife"):
            wsvc.approx_decayed("s", 0.5, halflife=0.0)


class TestWindowedMonitor:
    """StragglerMonitor on a windowed p99 reacts to regime changes the
    all-history monitor is blind to."""

    def test_regime_change_detection(self):
        from repro.distributed import StragglerMonitor
        windowed = StragglerMonitor(min_samples=16, window=64)
        blind = StragglerMonitor(min_samples=16, window=None)
        slow = {f"h{i}": 10.0 + 0.01 * i for i in range(8)}
        fast = {f"h{i}": 0.10 + 0.001 * i for i in range(8)}
        for _ in range(150):
            windowed.record(slow)
            blind.record(slow)
        for _ in range(100):
            windowed.record(fast)
            blind.record(fast)
        probe = {"ok": 0.11, "laggard": 0.9}
        assert windowed.decide(probe) == ["laggard"]
        assert blind.decide(probe) == []     # drowned in the old regime

    def test_monitor_uses_bounded_memory(self):
        from repro.distributed import StragglerMonitor
        mon = StragglerMonitor(min_samples=8, window=16, window_subs=4)
        for t in range(200):
            mon.record({f"h{i}": 1.0 for i in range(4)})
        stats = mon.service.memory_stats()
        assert stats["ring_records"] <= 16, stats
