"""The program's spans and named scopes in a trace, checked against values
worked out by hand; and the readers of the per-layer metrics, new and
old, on a hand-built trace."""
from pathlib import Path

import pytest

from bench import program_trace as pt
from bench import run, trace

DATA = Path(__file__).with_name("data")
ROOT = Path(__file__).resolve().parents[2]


def _xspace(planes: str):
    from jax.profiler import ProfileData
    return ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(planes))


def _stat(meta_id, value):
    kind = "str_value" if isinstance(value, str) else "int64_value"
    value = f'"{value}"' if isinstance(value, str) else value
    return f"stats {{ metadata_id: {meta_id} {kind}: {value} }} "


def _line(line_id, name, events, stat_ids=None):
    """``events``: (metadata id, start ns, duration ns[, {stat: value}])."""
    body = ""
    for m, s, d, *stats in events:
        extra = "".join(_stat(stat_ids[k], v)
                        for k, v in (stats[0] if stats else {}).items())
        body += (f"events {{ metadata_id: {m} offset_ps: {s * 1000} "
                 f"duration_ps: {d * 1000} {extra}}}\n")
    return f"lines {{ id: {line_id} name: \"{name}\" timestamp_ns: 0\n{body}}}\n"


def _plane(plane_id, name, lines, names, stats=None):
    meta = "".join(f"event_metadata {{ key: {i} value {{ id: {i} "
                   f"name: \"{n}\" }} }}\n" for i, n in names.items())
    meta += "".join(f"stat_metadata {{ key: {i} value {{ id: {i} "
                    f"name: \"{n}\" }} }}\n" for n, i in (stats or {}).items())
    return f"planes {{ id: {plane_id} name: \"{name}\"\n{lines}{meta}}}\n"


# A query [1000, 10000) on the host, the program's spans inside it:
#   service.windowed [1000, 9000) request=4
#     service.pivot [2000, 4000)   holding read [3000, 3500)
#     service.count_extract [4000, 7000)
#     service.resolve [7000, 8500) holding read [8000, 8400)
# and a tick [12000, 20000) holding service.ingest_batch [12000, 19000),
# which holds nan_check [13000, 15000) holding read [13500, 14900).
# The chip runs sort.1 [1000, 2500) and a conditional cond.2
# [4500, 8000) holding sort.3 [5000, 6000) and sort.4 [6500, 7500), then
# sort.3 [15000, 16000), in a window [0, 30000).
HOST = {1: "window", 2: "query", 3: "tick", 4: "repro/service.windowed",
        5: "repro/service.pivot", 6: "repro/read",
        7: "repro/service.count_extract", 8: "repro/service.resolve",
        9: "repro/service.ingest_batch", 10: "repro/nan_check",
        11: "PJRT_LoadedExecutable_Execute linkage"}
HOST_STATS = {"request": 90, "where": 91}
HOST_EVENTS = [(1, 0, 30000), (2, 1000, 9000),
               (4, 1000, 8000, {"request": 4}), (5, 2000, 2000),
               (6, 3000, 500), (7, 4000, 3000), (11, 4100, 10),
               (11, 5100, 10), (8, 7000, 1500), (6, 8000, 400),
               (3, 12000, 8000), (9, 12000, 7000),
               (10, 13000, 2000, {"where": "QuantileService.ingest"}),
               (6, 13500, 1400)]
OPS = {1: "%sort.1 = f32[8] sort(f32[8] %p)", 2: "%cond.2 = f32[] conditional()",
       3: "%sort.3 = f32[8] sort(f32[8] %q)", 4: "%sort.4 = f32[8] sort()",
       5: "jit_step(7)"}
DEVICE = (_line(1, "XLA Ops", [(1, 1000, 1500), (2, 4500, 3500),
                               (3, 5000, 1000), (4, 6500, 1000),
                               (3, 15000, 1000)])
          + _line(2, "XLA Modules", [(5, 1000, 1500), (5, 4500, 3500),
                                     (5, 15000, 1000)]))
HLO = """HloModule jit_step, entry_computation_layout={(f32[8]{0})->f32[]}

%region (a: f32[], b: f32[]) -> pred[] {
  %sort.9 = f32[] parameter(0), metadata={op_name="sort"}
}

ENTRY %main.1 (p: f32[8]) -> f32[] {
  %sort.1 = f32[8]{0} sort(f32[8]{0} %p), metadata={op_name="jit(step)/phase_sketch/vmap(jit(sort))/sort" source_file="x.py"}
  %cond.2 = f32[] conditional(), metadata={op_name="jit(step)/cond"}
  %sort.3 = f32[8]{0} sort(f32[8]{0} %q), metadata={op_name="jit(step)/cond/branch_0_fun/phase_extract/vmap()/top_k"}
  ROOT %sort.4 = f32[8]{0} sort(), metadata={op_name="jit(step)/phase_extract/cond/branch_1_fun/phase_resolve/jit(sort)/sort"}
  %copy.5 = f32[8]{0} copy(f32[8]{0} %p)
}
"""


@pytest.fixture
def profile():
    host = _line(1, "python3", HOST_EVENTS, HOST_STATS)
    return _xspace(_plane(1, "/device:TPU:0", DEVICE, OPS)
                   + _plane(2, "/host:CPU", host, HOST, HOST_STATS))


@pytest.fixture
def reduced(profile):
    return trace.reduce(profile, ["query", "tick"])


@pytest.fixture
def spans(profile, reduced):
    return pt.read_spans(profile, reduced)


def test_program_spans_are_kept_apart_from_the_benchmark_spans(reduced, spans):
    assert [s[0] for s in reduced.spans] == ["query", "tick"]
    assert [s.name for s in spans] == [
        "service.windowed", "service.pivot", "read", "service.count_extract",
        "service.resolve", "read", "service.ingest_batch", "nan_check",
        "read"]
    assert spans[0].stats == {"request": 4} and spans[0].thread == "python3"
    assert spans[7].stats == {"where": "QuantileService.ingest"}
    assert (spans[0].start, spans[0].end) == (1000, 9000)


def test_nesting_and_self_time(spans):
    assert pt.parents(spans) == [None, 0, 1, 0, 0, 4, None, 6, 7]
    # windowed: 8000 less pivot 2000, count_extract 3000, resolve 1500
    assert pt.self_ns(spans) == [1500, 1500, 500, 3000, 1100, 400,
                                 5000, 600, 1400]
    assert pt.per_root(spans, "service.windowed", "read") == [(8000, 900)]
    assert pt.per_root(spans, "service.ingest_batch", "read") == [(7000, 1400)]
    assert [s.name for s in pt.descendants(spans, 0, "read")] == ["read"] * 2


def test_span_table_counts_self_time_and_launches(spans):
    table = pt.span_table(spans, [4100, 5100, 25000])
    assert table["service.count_extract"] == {"spans": 1, "self_ns": 3000,
                                              "launches": 2}
    assert table["read"] == {"spans": 3, "self_ns": 2300, "launches": 0}
    assert sum(row["launches"] for row in table.values()) == 2


def test_innermost_span_over_an_interval(spans):
    cover = pt.Cover(spans)
    assert cover.innermost(3100, 3200).name == "read"
    # [2500, 4500): pivot's own time 1000, read 500, count_extract 500
    assert cover.innermost(2500, 4500).name == "service.pivot"
    assert cover.innermost(4100, 4100).name == "service.count_extract"
    assert cover.innermost(21000, 22000) is None


def test_gaps_are_labelled_by_the_innermost_program_span(reduced, spans):
    # idle: [16000, 30000) where ingest_batch's own time covers 3000;
    # [8000, 15000) where the tick's read covers 1400, ingest_batch 1000,
    # windowed 500, nan_check 500; [2500, 4500) where pivot's own time
    # covers 1000, read and count_extract 500 each; [0, 1000) none
    got = pt.label_gaps(reduced, spans)
    assert [name for name, _, _ in got] == [
        "tick:service.ingest_batch", "tick:read", "query:service.pivot",
        "none"]
    assert [s for _, s, _ in got] == [s for _, s in trace.idle_gaps(reduced)]
    assert [name.split(":")[0] for name, _, _ in got] == [
        name for name, _ in trace.idle_gaps(reduced)]
    assert [s for _, s, _ in got] == pytest.approx([14e-6, 7e-6, 2e-6, 1e-6])
    assert [c for _, _, c in got] == pytest.approx([3e-6, 1.4e-6, 1e-6, 0])


def test_host_activity_in_a_gap(profile):
    # [2500, 4500): besides the program's spans, a launch at 4100 (10 ns);
    # [8000, 15000): nothing of the runtime's
    got = pt.host_activity(profile, [(2500, 4500), (8000, 15000)],
                           {"query", "tick"})
    [[(name, ms)], none] = got
    assert name == pt.LAUNCH and ms == pytest.approx(10e-6)
    assert none == []


def test_a_conditional_keeps_only_what_its_children_leave():
    ops = [(OPS[2], 4500, 8000), (OPS[3], 5000, 6000), (OPS[4], 6500, 7500)]
    assert pt.self_times(ops) == {"cond.2": 1500, "sort.3": 1000,
                                  "sort.4": 1000}
    ops.append((OPS[1], 8000, 9000))        # a sibling after it
    assert pt.self_times(ops)["sort.1"] == 1000
    assert sum(pt.self_times(ops).values()) == 4500


def test_scopes_of_an_hlo_module():
    got = pt.scopes(HLO)
    assert got["sort.1"] == "jit(step)/phase_sketch/vmap(jit(sort))/sort"
    assert got["cond.2"] == "jit(step)/cond"
    assert got["sort.9"] == "sort" and "copy.5" not in got
    assert pt.module_name(HLO) == "jit_step"
    assert pt.in_scope(got["sort.4"], "phase_resolve")
    assert not pt.in_scope(got["sort.4"], "phase_extract")
    assert not pt.in_scope(got["cond.2"], "phase_extract")


def test_scope_time_counts_each_nanosecond_once(reduced):
    within = [("query", 0, 10000)]
    got = {s: pt.scope_time_ns(reduced, pt.scopes(HLO), s, within)
           for s in ("phase_sketch", "phase_extract", "phase_resolve")}
    assert got == {"phase_sketch": 1500, "phase_extract": 1000,
                   "phase_resolve": 1000}
    everywhere = [("window", 0, 30000)]
    assert pt.scope_time_ns(reduced, pt.scopes(HLO), "phase_extract",
                            everywhere) == 2000


def test_launches_of_one_program_cut_to_spans(reduced):
    got = pt.program_launches(reduced, "jit_step", [("q", 2000, 5000)])
    assert [(s, e) for _, s, e in got] == [(2000, 2500), (4500, 5000)]
    assert pt.program_launches(reduced, "jit_other", [("q", 0, 9e9)]) == []


# -- readers ------------------------------------------------------------------

def _reading(tr, hlo=HLO):
    cell = run.load_cell(ROOT, "gkselect-1e9-p120.uniform-p99")
    logged = []
    r = run.Reading(trace=tr, spans={}, cell=cell,
                    peaks={"hbm_bytes_per_s": 819e9}, log=logged.append)
    return r, logged


@pytest.fixture
def job_trace():
    """Two jobs: [0, 10000) and [20000, 30000), each one launch of
    ``jit_step`` holding sort.1 (sketch), cond.2 holding sort.3 (extract)
    and sort.4 (resolve); the second job also runs copy.5, a program
    outside ``jit_step``."""
    ops = [(OPS[1], 1000, 3000), (OPS[2], 3000, 8000), (OPS[3], 3500, 5500),
           (OPS[4], 6000, 7000),
           (OPS[1], 21000, 23000), (OPS[2], 23000, 28000),
           (OPS[3], 23500, 25500), (OPS[4], 26000, 27000),
           ("%copy.5 = f32[8] copy()", 28500, 29500)]
    modules = [("jit_step(1)", 1000, 8000), ("jit_step(1)", 21000, 28000),
               ("jit_nan(2)", 28500, 29500)]
    return trace.Trace(window=(0, 40000), ops={"/device:TPU:0": ops},
                       modules={"/device:TPU:0": modules},
                       spans=[("job", 0, 10000), ("job", 20000, 30000)])


@pytest.fixture
def hlo_of_job(monkeypatch):
    monkeypatch.setattr(pt, "job_hlo", lambda cell: HLO)


def _reader(name):
    return run.load_module(ROOT / "bench" / "layer_metrics" / f"{name}.py")


def test_phase_readers_give_their_hand_worked_values(job_trace, hlo_of_job):
    # job device time 2 x 7000 + 1000 = 15000; sketch 2 x 2000, extract
    # 2 x 2000, resolve 2 x 1000, the conditional's own time 2 x 2000
    r, logged = _reading(job_trace)
    got = {m: _reader(m).read(r) for m in
           ("sketch_share.job", "extract_share.job", "resolve_share.job")}
    assert got == pytest.approx({"sketch_share.job": 100 * 4000 / 15000,
                                 "extract_share.job": 100 * 4000 / 15000,
                                 "resolve_share.job": 100 * 2000 / 15000})
    [line] = logged
    assert "no phase 26.6667" in line and "other programs 6.6667" in line


def test_phase_readers_give_nothing_for_a_program_without_phases(
        job_trace, monkeypatch):
    plain = "\n".join(line.split(", metadata")[0] for line in HLO.split("\n"))
    monkeypatch.setattr(pt, "job_hlo", lambda cell: plain)
    r, logged = _reading(job_trace)
    assert _reader("sketch_share.job").read(r) is None and not logged
    assert _reader("resolve_share.job").read(_reading(None)[0]) is None


def test_the_existing_readers_give_their_old_values(job_trace):
    r, _ = _reading(job_trace)
    # sort_share: sort.1, sort.3 and sort.4 by name, whole durations
    assert _reader("sort_share.job").read(r) == pytest.approx(
        100 * 2 * (2000 + 2000 + 1000) / 15000)
    assert _reader("idle_share.job").read(r) == pytest.approx(
        100 * (1 - 15000 / 40000))
    least_s = 120 * 2 ** 23 * 4 / 819e9
    assert _reader("hbm_roofline.job").read(r) == pytest.approx(
        100 * least_s / (7500e-9))
    service = trace.Trace(window=(0, 40000), ops=job_trace.ops,
                          modules=job_trace.modules,
                          spans=[("query", 0, 10000), ("query", 20000, 25000)])
    r = run.Reading(trace=service, spans={"tick": [(0.0, 0.5), (1.0, 1.1)]},
                    cell=r.cell, peaks=r.peaks)
    assert _reader("launches_per_query.service").read(r) == 1.0
    assert _reader("idle_share.service").read(r) == pytest.approx(
        100 * (1 - 15000 / 40000))
    assert _reader("tick_host_ms.service").read(r) == pytest.approx(300.0)


# -- the shared clock, on a trace recorded on the chip ---------------------------

def test_recorded_chip_trace_reads_end_after_what_they_wait_for():
    """``data/program.xplane.pb`` (``data/record_program_trace.py``, one
    v5e chip): each ``read`` span waits for the program the Python thread
    launched last before it, and ends after that program ended on the
    chip, on the host's clock."""
    from jax.profiler import ProfileData
    profile = ProfileData.from_file(str(DATA / "program.xplane.pb"))
    tr = trace.reduce(profile, ["job", "tick", "query"])
    spans = pt.read_spans(profile, tr)
    assert [s[0] for s in tr.spans] == ["job"] + ["tick"] * 2 + ["query"] * 2
    assert {"gk_select", "dispatch", "nan_check", "read",
            "service.ingest_batch", "service.pack", "service.rotate",
            "service.update", "service.retire", "service.windowed",
            "service.slices", "service.pivot", "service.count_extract",
            "service.resolve"} == {s.name for s in spans}
    # the k-th launch on the host is the program with the k-th run_id
    launched, ended = [], {}
    for plane in profile.planes:
        for line in plane.lines:
            for e in line.events:
                if e.name == pt.LAUNCH:
                    launched.append(e.start_ns)
                elif line.name == trace.MODULES_LINE:
                    ended[int(dict(e.stats)["run_id"])] = (
                        e.start_ns + e.duration_ns + tr.clock_offset_ns)
    assert len(launched) == len(ended) > 0
    ends = [ended[run_id] for run_id in sorted(ended)]
    launched.sort()
    reads = [s for s in spans if s.name == "read"]
    assert len(reads) == 1 + 2 + 2 * 2      # job, ticks, two per query
    for read in reads:
        k = sum(t < read.start for t in launched)
        assert 0 < k and ends[k - 1] <= read.end, read
    queries = [s for s in spans if s.name == "service.windowed"]
    requests = [q.stats["request"] for q in queries]
    assert requests == sorted(requests) and len(set(requests)) == 2
