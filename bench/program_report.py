#!/usr/bin/env python3
"""Run one cell's measured window with the profiler on, and report what the
program's own spans, counters and named scopes show.

    python3 bench/program_report.py --workload <cell> --seed <n> \\
        --seconds <s> [--out <file.json>]

Set-up and the window are those of ``bench/run.py`` (the same driver,
data and warm-up), with the profiler on for the window.  The report reads
the trace with ``bench/program_trace.py``:

* per ``repro/`` span name: how many, their self time and the programs
  launched while such a span was the innermost open;
* the increments of ``repro.obs``'s counters across the window;
* the ten longest idle gaps of the chip, each labelled by the benchmark
  span and the program span over it, with the seconds of the gap that
  program span covers and the host events (the runtime's, the
  compiler's, the Python tracer's) that took most of the gap;
* in a job cell: each phase scope's share of the job's device time, and
  the mean ``nan_check`` span per job;
* in a service cell: per query, ``service.windowed`` less the ``read``
  spans it holds (host time) and those ``read`` spans (wait), each as
  median, mean and maximum; per tick, the ``read`` spans of
  ``service.ingest_batch``; programs built for a new candidate cap per
  query; and the share of ``query`` span time that program spans cover.

It checks the window's answers as ``bench/run.py`` does, prints one line
per reading, and writes them all as JSON to ``--out``.  A program without
``repro.obs`` gives empty readings.
"""
import argparse
import json
import statistics
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for _path in (str(ROOT), str(ROOT / "src")):
    if _path not in sys.path:
        sys.path.insert(0, _path)

from bench import device, program_trace, run, trace as tracemod  # noqa: E402


def _counters() -> dict:
    try:
        from repro import obs
    except ImportError:
        return {}
    return obs.counters()


def _ms(values) -> dict:
    values = [v * 1e-6 for v in values]
    if not values:
        return {}
    return {"median": statistics.median(values),
            "mean": statistics.fmean(values), "max": max(values),
            "count": len(values)}


def report(trace, profile, counters, reading) -> dict:
    """The readings of one traced window; see the module's docstring."""
    program = program_trace.read_spans(profile, trace)
    table = program_trace.span_table(
        program, program_trace.launch_times(profile, trace))
    gaps = program_trace.label_gaps(trace, program)
    busy = program_trace.host_activity(
        profile, program_trace.gap_intervals(trace), {s[0] for s in trace.spans})
    out = {"spans": table, "counters": counters,
           "idle_gaps": [[label, seconds, covered,
                          [[n, round(ms, 3)] for n, ms in top]]
                         for (label, seconds, covered), top in zip(gaps, busy)]}
    jobs = trace.spans_named("job")
    if jobs:
        out["phase_shares"] = program_trace.phase_shares(reading)
        checks = [s.end - s.start for s in program if s.name == "nan_check"]
        out["nan_check_ms"] = (sum(checks) * 1e-6 / len(jobs)
                               if checks else None)
    queries = trace.spans_named("query")
    if queries:
        per_query = program_trace.per_root(program, "service.windowed",
                                           program_trace.READ)
        out["query_host_ms"] = _ms([d - w for d, w in per_query])
        out["query_wait_ms"] = _ms([w for _, w in per_query])
        per_tick = program_trace.per_root(program, "service.ingest_batch",
                                          program_trace.READ)
        out["tick_wait_ms"] = _ms([w for _, w in per_tick])
        out["cap_programs_per_query"] = (
            counters.get("service.cap_programs", 0) / len(queries)
            if program else None)
        roots = [(s.start, s.end) for s in program
                 if s.name == "service.windowed"]
        inside = tracemod.merge(
            [("", max(a, q[1]), min(b, q[2]))
             for a, b in roots for q in queries if min(b, q[2]) > max(a, q[1])])
        query_ns = tracemod.covered(tracemod.merge(queries))
        out["query_span_covered_pct"] = (100.0 * tracemod.covered(inside)
                                         / query_ns)
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--out")
    p.add_argument("--platform", default="tpu")
    args = p.parse_args(argv)
    started = time.perf_counter()
    cell = run.load_cell(ROOT, args.workload)
    import jax
    from jax.profiler import ProfileData

    devices = device.require(cell.chips, args.platform)
    peaks = device.peaks(devices[0].device_kind, ROOT / "bench" / "peaks.json")
    device.enable_compile_cache(ROOT)
    driver = run.load_module(ROOT / "bench" / "drivers"
                             / f"{cell.traffic['driver']}.py")
    spans = run.Spans()
    loop = driver.Loop(run.Run(cell=cell, seed=args.seed, devices=devices,
                               span=spans))
    run.log(f"setup {time.perf_counter() - started:.3f} s")
    with tempfile.TemporaryDirectory(prefix="program-trace-") as trace_dir:
        before = _counters()
        jax.profiler.start_trace(trace_dir)
        try:
            with jax.profiler.TraceAnnotation(tracemod.WINDOW):
                loop.window(args.seconds)
        finally:
            jax.profiler.stop_trace()
        after = _counters()
        profile = ProfileData.from_file(tracemod.find_xplane(trace_dir))
        trace = tracemod.reduce(profile, spans.records)
        e2e, attempted, failed = loop.results()
        loop.release()
        counters = {k: v - before.get(k, 0) for k, v in after.items()
                    if v != before.get(k, 0)}
        reading = run.Reading(trace=trace, spans=dict(spans.records),
                              cell=cell, peaks=peaks)
        out = report(trace, profile, counters, reading)
    out.update(end_to_end=e2e, attempted=attempted, failed=failed,
               busy_s=tracemod.busy_ns(trace) * 1e-9,
               window_s=trace.window_s)
    checks = loop.check()
    out["correct"] = all(v <= limit for v, limit in checks.values())
    for name, row in sorted(out["spans"].items(),
                            key=lambda kv: -kv[1]["self_ns"]):
        run.log(f"span {name}: {row['spans']} spans, self "
                f"{row['self_ns'] * 1e-6:.3f} ms, {row['launches']} launches")
    for key, value in out.items():
        if key != "spans":
            run.log(f"{key} {json.dumps(value)}")
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(out, indent=1))
    return 0 if out["correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
