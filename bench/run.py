#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` on the chip and print its result.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell names a configuration and a traffic mix.  Everything about them
lives in data files found by name, so that a new cell needs no edit here:

  bench/configs/<config>.json       the deployment: sizes and guarantees
  bench/traffic/<traffic>.json      the mix, and the ``driver`` that runs it
  bench/drivers/<driver>.py         a loop: set-up, measured window, check
  bench/layer_metrics/<metric>.py   one reader per per-layer metric

The run sets up (counted in ``setup_s``), measures for ``--seconds``, reads
the device's peak memory, frees the system's state, and then compares what
the measured window produced with the plain reference (``reference.py``).
With ``--trace 1`` the window runs under the JAX profiler and the result
carries the per-layer metrics and a breakdown of the trace instead of the
end-to-end metrics.  The last line of stdout is the result as one JSON
object; the numbers compared, each beside its limit, are the last lines of
stderr and the last key of that object.  Without the cell's chips the run
exits non-zero and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from collections import defaultdict  # noqa: E402
from contextlib import contextmanager  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
# libtpu logs under /tmp unless told otherwise; a run writes nothing there
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def log(msg: str) -> None:
    """An earlier line of the result: anything but the last line."""
    print(msg, flush=True)


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list


def _for_cell(metrics: list, workload: str) -> list:
    return [m for m in metrics
            if "workloads" not in m or workload in m["workloads"]]


def load_cell(root: Path, workload: str) -> Cell:
    """The cell ``workload`` of ``<root>/BENCHMARK.json`` with its files."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; have {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
    return Cell(name=workload, chips=int(w["chips"]), config=config,
                traffic=traffic,
                end_to_end=_for_cell(bench["end_to_end"], workload),
                per_layer=_for_cell(bench["per_layer"], workload))


class Spans:
    """Host spans around each call into the system: recorded on the host
    clock, and written into the profiler's trace when one is running."""

    def __init__(self):
        self.records = defaultdict(list)     # name -> [(start_s, end_s)]

    @contextmanager
    def __call__(self, name: str):
        import jax
        start = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(name):
                yield
        finally:
            self.records[name].append((start, time.perf_counter()))


@dataclasses.dataclass
class Run:
    """What a driver is given: the cell's files, the seed, and the span
    recorder to wrap each call into the system in."""
    cell: Cell
    seed: int
    devices: list
    span: Spans
    log: object = log

    @property
    def config(self) -> dict:
        return self.cell.config

    @property
    def traffic(self) -> dict:
        return self.cell.traffic


@dataclasses.dataclass
class Reading:
    """What a per-layer reader is given: the reduced trace (``None`` when
    nothing was traced), the run's host spans, the cell and the peaks."""
    trace: object
    spans: dict
    cell: Cell
    peaks: dict
    log: object = log


def parse_args(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def main(argv=None, *, root: Path = ROOT, platform: str = "tpu",
         started: float = T_START) -> int:
    args = parse_args(argv)
    root = Path(root)
    for path in (str(root), str(root / "src")):
        if path not in sys.path:
            sys.path.insert(0, path)
    cell = load_cell(root, args.workload)
    import jax
    from bench import device, trace as tracemod

    try:
        devices = device.require(cell.chips, platform)
    except device.NoDevice as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    peaks = device.peaks(devices[0].device_kind, root / "bench" / "peaks.json")
    log(f"cache {device.enable_compile_cache(root)}")
    counter = device.CompileCounter()
    driver = load_module(root / "bench" / "drivers"
                         / f"{cell.traffic['driver']}.py")
    run = Run(cell=cell, seed=args.seed, devices=devices, span=Spans())

    loop = driver.Loop(run)
    setup_s = time.perf_counter() - started
    log(f"setup {setup_s:.6f} s: {len(counter.loaded)} programs loaded, "
        f"{counter.cache_misses} compiled")

    if args.trace:
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        jax.profiler.start_trace(trace_dir)
    counter.reset()
    try:
        with jax.profiler.TraceAnnotation(tracemod.WINDOW):
            loop.window(args.seconds)
    finally:
        if args.trace:
            jax.profiler.stop_trace()
    log(f"window compiles: {len(counter.traced)} traced, "
        f"{len(counter.loaded)} loaded, {counter.cache_misses} compiled"
        + (f" ({sorted(set(counter.traced))})" if counter.traced else ""))
    counter.close()

    dev = device.record(devices)
    log(f"device memory_stats {devices[0].memory_stats()}")
    e2e, attempted, failed = loop.results()
    e2e["setup_s"] = setup_s
    loop.release()

    if args.trace:
        try:
            xplane = tracemod.find_xplane(trace_dir)
            log(f"trace {os.path.getsize(xplane)} bytes")
            trace = tracemod.read(trace_dir, run.span.records)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        log(f"trace clock offset {trace.clock_offset_ns} ns")
        dev["busy_s"] = tracemod.busy_ns(trace) * 1e-9
        dev["window_s"] = trace.window_s
        reading = Reading(trace=trace, spans=dict(run.span.records),
                          cell=cell, peaks=peaks)
        metrics = {}
        for m in cell.per_layer:
            reader = load_module(root / "bench" / "layer_metrics"
                                 / f"{m['name']}.py")
            value = reader.read(reading)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        missing = [m["name"] for m in cell.end_to_end if m["name"] not in e2e]
        if missing:
            raise KeyError(f"the {cell.traffic['driver']} driver reports no "
                           f"{missing}")
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}

    checks = loop.check()
    correct = all(value <= limit for value, limit in checks.values())
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": dev}
    if args.trace:
        result["breakdown"] = tracemod.breakdown(trace)
    result["checks"] = {name: {"value": value, "limit": limit}
                        for name, (value, limit) in checks.items()}
    for name, (value, limit) in checks.items():
        print(f"check {name} {value} limit {limit}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
