"""``repro.obs``: one counter registry, and spans that land in a profiler
trace nested as the entry points open them; the engine's rounds named in
the compiled program."""
import glob
import re
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import (gk_select, gk_select_multi, lowering,
                        record_sketch_sort, reset_sketch_sorts, sketch_sorts)
from repro.kernels import ops
from repro.launch import QuantileService, Window
from repro.launch import quantile_service as qs_mod

PHASES = ("phase_sketch", "phase_pivot", "phase_count", "phase_extract",
          "phase_count_extract", "phase_resolve")


# -- counters ------------------------------------------------------------------

def test_counts_from_threads_sum_exactly():
    obs.reset("test.a", "test.b")
    barrier = threading.Barrier(8)

    def work():
        barrier.wait()
        for _ in range(2000):
            obs.count("test.a")
            obs.count("test.b", 3)

    threads = [threading.Thread(target=work) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    snap = obs.counters()
    assert snap["test.a"] == 8 * 2000 and snap["test.b"] == 8 * 2000 * 3
    snap["test.a"] = -1                      # a snapshot, not the registry
    assert obs.counters()["test.a"] == 8 * 2000
    obs.reset("test.a")
    assert "test.a" not in obs.counters()
    assert obs.counters()["test.b"] == 8 * 2000 * 3
    obs.reset("test.b")


def test_reset_with_no_name_clears_every_counter():
    obs.count("test.c", 2)
    obs.count("test.d")
    obs.reset()
    assert obs.counters() == {}


@pytest.mark.parametrize("name,tick,read,reset", [
    ("sketch.sorts", record_sketch_sort, sketch_sorts, reset_sketch_sorts),
    ("kernels.hbm_passes", ops._tick, ops.hbm_passes, ops.reset_hbm_passes),
    ("service.ingest_dispatches", qs_mod.record_ingest_dispatch,
     qs_mod.ingest_dispatches, qs_mod.reset_ingest_dispatches),
])
def test_legacy_counter_functions_are_views_of_the_registry(name, tick, read,
                                                            reset):
    reset()
    assert read() == 0 and name not in obs.counters()
    tick()
    tick(4)
    assert read() == 5 and obs.counters()[name] == 5
    obs.count(name, 2)
    assert read() == 7
    reset()
    assert read() == 0 and name not in obs.counters()


def test_cap_programs_counts_programs_built_for_a_new_cap():
    qs_mod._chunk_fn.cache_clear()
    qs_mod._resolve_fn.cache_clear()
    svc = QuantileService(window_ticks=4, window_subs=2)
    rng = np.random.default_rng(0)
    for _ in range(3):
        svc.ingest_batch(["a", "b"], list(rng.normal(size=(2, 64))
                                          .astype(np.float32)))
    obs.reset(qs_mod.CAP_PROGRAMS)
    svc.windowed("a", 0.5, window=Window(ticks=3))
    assert obs.counters()[qs_mod.CAP_PROGRAMS] == 2    # a chunk, a resolve
    svc.windowed("a", 0.9, window=Window(ticks=3))
    assert obs.counters()[qs_mod.CAP_PROGRAMS] == 2    # the same cap


# -- spans ---------------------------------------------------------------------

def test_a_span_with_no_profiler_running_is_a_no_op():
    before = obs.counters()
    with obs.span("test.idle", request=1, where="here") as s:
        assert s is not None
    with pytest.raises(KeyError):
        with obs.span("test.raises"):
            raise KeyError("passes through")
    assert obs.counters() == before


def _host_spans(trace_dir):
    """``(name, start, end, stats, line)`` of every ``repro/`` event on the
    host plane of the one trace written under ``trace_dir``."""
    from jax.profiler import ProfileData
    [path] = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(obs.PREFIX):
                    out.append((e.name[len(obs.PREFIX):], e.start_ns,
                                e.start_ns + e.duration_ns, dict(e.stats),
                                line.name))
    return sorted(out, key=lambda s: (s[1], -s[2]))


def _children(spans, parent):
    """The spans that lie inside ``parent`` on its line, nearest first."""
    _, lo, hi, _, line = parent
    return [s for s in spans if s is not parent and s[4] == line
            and lo <= s[1] and s[2] <= hi]


def _direct(spans, parent):
    inner = _children(spans, parent)
    return [s[0] for s in inner
            if not any(o is not s and o[1] <= s[1] and s[2] <= o[2]
                       for o in inner)]


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One CPU profiler trace of a tiny ``gk_select`` and a tiny windowed
    service: 3 ticks of 3 series, then 2 windowed queries."""
    rng = np.random.default_rng(7)
    parts = jnp.asarray(rng.normal(size=(4, 512)).astype(np.float32))
    svc = QuantileService(window_ticks=4, window_subs=2)
    ticks = rng.lognormal(size=(3, 3, 32)).astype(np.float32)
    gk_select(parts, 0.99).block_until_ready()          # compile outside
    trace_dir = str(tmp_path_factory.mktemp("trace"))
    jax.profiler.start_trace(trace_dir)
    try:
        gk_select(parts, 0.99).block_until_ready()
        for t in range(3):
            svc.ingest_batch(["s0", "s1", "s2"], list(ticks[t]))
        answers = [np.asarray(svc.windowed(name, 0.9, window=Window(ticks=3)))
                   for name in ("s0", "s2")]
    finally:
        jax.profiler.stop_trace()
    return _host_spans(trace_dir), ticks, answers


def test_gk_select_spans_nest_as_the_entry_opens_them(traced):
    spans, _, _ = traced
    [root] = [s for s in spans if s[0] == "gk_select"]
    assert _direct(spans, root) == ["nan_check", "dispatch"]
    [check] = [s for s in _children(spans, root) if s[0] == "nan_check"]
    assert check[3]["where"] == "gk_select"
    assert _direct(spans, check) == ["read"]


def test_tick_spans_nest_as_ingest_batch_opens_them(traced):
    spans, _, _ = traced
    ticks = [s for s in spans if s[0] == "service.ingest_batch"]
    assert len(ticks) == 3
    for tick in ticks:
        assert _direct(spans, tick) == ["service.pack", "nan_check",
                                        "service.rotate", "service.update",
                                        "service.retire"]
        [check] = [s for s in _children(spans, tick) if s[0] == "nan_check"]
        assert check[3]["where"] == "QuantileService.ingest"


def test_query_spans_nest_as_windowed_opens_them(traced):
    spans, ticks, answers = traced
    queries = [s for s in spans if s[0] == "service.windowed"]
    assert [q[3]["request"] for q in queries] == [0, 1]
    for query in queries:
        assert _direct(spans, query) == ["service.slices", "service.pivot",
                                         "service.count_extract",
                                         "service.resolve"]
        inner = _children(spans, query)
        reads = [s for s in inner if s[0] == "read"]
        assert len(reads) == 2          # the pivot's bound, the counts
        for parent in ("service.pivot", "service.resolve"):
            [p] = [s for s in inner if s[0] == parent]
            assert _direct(spans, p) == ["read"]
    for series, answer in zip((0, 2), answers):
        flat = np.sort(ticks[:, series].ravel())
        assert answer == flat[int(np.ceil(0.9 * flat.size)) - 1]


# -- device scopes ---------------------------------------------------------------

INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%(\S+) = .*?\s([a-z][\w-]*)\(")


def _sorts_and_top_ks(hlo_text):
    """``(name, op_name path)`` of every sort and top-k instruction."""
    out = []
    for line in hlo_text.splitlines():
        m = INSTRUCTION.match(line)
        if not m:
            continue
        path = re.search(r'op_name="([^"]*)"', line)
        path = path.group(1) if path else ""
        opcode = m.group(2)
        if opcode in ("sort", "topk") or path.endswith("/top_k"):
            out.append((m.group(1), path))
    return out


@pytest.mark.parametrize("entry,q,kw", [
    (gk_select, 0.99, {}),
    (gk_select, 0.5, {"speculative": True}),
    (gk_select, 0.99, {"block_select": True}),
    (gk_select_multi, (0.5, 0.99), {}),
])
def test_every_sort_and_top_k_runs_under_a_phase_scope(entry, q, kw):
    x = jax.ShapeDtypeStruct((4, 2048), jnp.float32)
    hlo = lowering.lower(entry, x, q, **kw).compile().as_text()
    found = _sorts_and_top_ks(hlo)
    assert any(path.endswith("/top_k") for _, path in found)
    assert any(path.endswith("/sort") for _, path in found)
    for name, path in found:
        assert set(path.split("/")) & set(PHASES), (name, path)
