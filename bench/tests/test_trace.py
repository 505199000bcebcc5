"""The trace reduction, checked against values worked out by hand."""
from pathlib import Path

import pytest

from bench import trace

DATA = Path(__file__).with_name("data")


def _xspace(planes: str):
    from jax.profiler import ProfileData
    return ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(planes))


def _line(line_id, name, events):
    body = "".join(
        f"events {{ metadata_id: {m} offset_ps: {s * 1000} "
        f"duration_ps: {d * 1000} }}\n" for m, s, d in events)
    return (f"lines {{ id: {line_id} name: \"{name}\" timestamp_ns: 0\n"
            f"{body}}}\n")


def _plane(plane_id, name, lines, names):
    meta = "".join(f"event_metadata {{ key: {i} value {{ id: {i} "
                   f"name: \"{n}\" }} }}\n" for i, n in names.items())
    return f"planes {{ id: {plane_id} name: \"{name}\"\n{lines}{meta}}}\n"


# One chip.  Operations (ns): sort.1 [1000, 6000), fusion.2 [5000, 7000),
# sort.1 [12000, 13000), copy.3 [20000, 21000); launches at 1000, 12000,
# 20000.  Host: window [0, 30000), query [500, 8000), tick [9000, 19000),
# query [19500, 22000), and a runtime event the reduction ignores.
OPS = {1: "%sort.1 = f32[8] sort(f32[8] %p)", 2: "fusion.2", 3: "copy.3",
       4: "jit_step(1)"}
HOST = {1: "window", 2: "query", 3: "tick", 4: "PjitFunction(step)"}
SPANS = ("query", "tick")   # the spans a driver recorded
DEVICE_LINES = (
    _line(1, "XLA Ops", [(1, 1000, 5000), (2, 5000, 2000), (1, 12000, 1000),
                         (3, 20000, 1000)])
    + _line(2, "XLA Modules", [(4, 1000, 6000), (4, 12000, 1000),
                               (4, 20000, 1000)]))
HOST_LINES = _line(1, "python", [(1, 0, 30000), (2, 500, 7500),
                                 (4, 600, 100), (3, 9000, 10000),
                                 (2, 19500, 2500)])


@pytest.fixture
def one_chip():
    return trace.reduce(_xspace(_plane(1, "/device:TPU:0", DEVICE_LINES, OPS)
                                + _plane(2, "/host:CPU", HOST_LINES, HOST)),
                        SPANS)


def test_window_and_spans(one_chip):
    assert one_chip.window == (0, 30000)
    assert one_chip.window_s == pytest.approx(30e-6)
    assert [s[0] for s in one_chip.spans] == ["query", "tick", "query"]


def test_spans_kept_are_those_the_run_recorded():
    """A span name trace.py has never heard of is kept when the run
    recorded it; one the run did not record is left out."""
    names = {**HOST, 3: "ingest_pool"}
    t = trace.reduce(_xspace(_plane(1, "/device:TPU:0", DEVICE_LINES, OPS)
                             + _plane(2, "/host:CPU", HOST_LINES, names)),
                     ["ingest_pool"])
    assert [s[0] for s in t.spans] == ["ingest_pool"]
    assert trace.launches_in(t, t.spans_named("ingest_pool")) == 1


def test_busy_is_the_union_of_operations(one_chip):
    # [1000, 7000) + [12000, 13000) + [20000, 21000)
    assert trace.busy_ns(one_chip) == 8000
    assert trace.idle_share(one_chip) == pytest.approx(100 * (1 - 8000 / 30000))
    queries = one_chip.spans_named("query")
    assert trace.busy_ns(one_chip, queries) == 6000 + 1000


def test_operation_time_by_name(one_chip):
    total, names = trace.op_time_ns(one_chip, lambda n: "sort" in n,
                                    one_chip.spans)
    assert total == 5000 + 1000 and names == {"sort.1"}
    top = trace.top_ops(one_chip)
    assert [name for name, _ in top] == ["sort.1", "fusion.2", "copy.3"]
    assert [s for _, s in top] == pytest.approx([6e-6, 2e-6, 1e-6])


def test_launches_inside_spans(one_chip):
    assert trace.launches_in(one_chip, one_chip.spans_named("query")) == 2
    assert trace.launches_in(one_chip, one_chip.spans_named("tick")) == 1


def test_gaps_are_named_by_the_host_span_over_them(one_chip):
    # [21000, 30000): query covers 1000 of it; [13000, 20000): tick 6000,
    # query 500; [7000, 12000): tick 3000, query 1000; [0, 1000): query 500
    got = trace.idle_gaps(one_chip)
    assert [name for name, _ in got] == ["query", "tick", "tick", "query"]
    assert [s for _, s in got] == pytest.approx([9e-6, 7e-6, 5e-6, 1e-6])
    assert trace.idle_gaps(one_chip, n=1) == got[:1]


def test_busy_is_averaged_over_chips():
    second = _line(1, "XLA Ops", [(1, 0, 2000)])
    t = trace.reduce(_xspace(_plane(1, "/device:TPU:0", DEVICE_LINES, OPS)
                             + _plane(3, "/device:TPU:1", second, OPS)
                             + _plane(2, "/host:CPU", HOST_LINES, HOST)),
                        SPANS)
    assert trace.busy_ns(t) == (8000 + 2000) / 2
    assert trace.launches_in(t, t.spans_named("query")) == 2


def _plane_with_run_ids(plane_id, name, lines, names):
    """``_plane`` whose lines' events carry a ``run_id`` stat: each event
    is (metadata id, start, duration, run id)."""
    body = ""
    for line_id, line_name, events in lines:
        evs = "".join(
            f"events {{ metadata_id: {m} offset_ps: {s * 1000} "
            f"duration_ps: {d * 1000} stats {{ metadata_id: 99 "
            f"int64_value: {run} }} }}\n" for m, s, d, run in events)
        body += (f"lines {{ id: {line_id} name: \"{line_name}\" "
                 f"timestamp_ns: 0\n{evs}}}\n")
    stat = 'stat_metadata { key: 99 value { id: 99 name: "run_id" } }\n'
    return _plane(plane_id, name, body, names)[:-2] + stat + "}\n"


def test_the_chip_clock_is_moved_to_the_host_clock():
    """Launch 1 starts on the chip at 1000 but was enqueued at 1500, launch
    2 at 12000 and 12200: the chip's events move by the larger bound, 500."""
    ops = _line(1, "XLA Ops", [(1, 1000, 5000), (3, 12000, 1000)])
    modules = _plane_with_run_ids(
        3, "/device:TPU:0", [(2, "XLA Modules", [(4, 1000, 6000, 1),
                                                 (4, 12000, 1000, 2)])], OPS)
    chip = modules.replace("lines {", ops + "lines {", 1)
    runtime = _plane_with_run_ids(
        4, "/host:CPU", [(2, "runtime", [(5, 1500, 10, 1),
                                         (5, 12200, 10, 2)])],
        {**HOST, 5: "DoEnqueueProgram"})
    host = runtime.replace("lines {", HOST_LINES + "lines {", 1)
    t = trace.reduce(_xspace(chip + host), SPANS)
    assert t.clock_offset_ns == 500
    assert t.ops["/device:TPU:0"][0][1:] == (1500, 6500)
    assert [m[1] for m in t.modules["/device:TPU:0"]] == [1500, 12500]
    assert trace.busy_ns(t) == 5000 + 1000


def test_a_trace_without_a_window_is_refused():
    host = _line(1, "python", [(2, 500, 7500)])
    with pytest.raises(ValueError, match="window"):
        trace.reduce(_xspace(_plane(2, "/host:CPU", host, HOST)), SPANS)


def test_recorded_chip_trace():
    """A trace recorded on one v5e chip: inside ``window``, three ``query``
    spans each launch two programs (a sort and a top-k), then a ``tick``
    span sleeps 50 ms before launching one, and the window ends with a
    20 ms sleep outside any span."""
    from jax.profiler import ProfileData
    t = trace.reduce(ProfileData.from_file(str(DATA / "small.xplane.pb")),
                     SPANS)
    assert list(t.ops) == ["/device:TPU:0"]
    assert [s[0] for s in t.spans] == ["query"] * 3 + ["tick"]
    assert trace.launches_in(t, t.spans_named("query")) == 6
    assert trace.launches_in(t, t.spans_named("tick")) == 1
    assert 0 < trace.busy_ns(t) < 0.2 * (t.window[1] - t.window[0])
    name, seconds = trace.idle_gaps(t, n=1)[0]
    assert name == "tick" and 0.045 < seconds < 0.1
