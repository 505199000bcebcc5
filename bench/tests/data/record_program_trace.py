#!/usr/bin/env python3
"""Record ``program.xplane.pb``, the chip trace ``test_program_trace.py``
reads, on one TPU chip.

    python3 bench/tests/data/record_program_trace.py <out.xplane.pb>

Inside one ``window`` span: a ``job`` span around a tiny ``gk_select``
(4 x 2048 float32, p99), two ``tick`` spans around
``QuantileService.ingest_batch`` of 8 series x 256 values, then two
``query`` spans around ``windowed`` p90 queries of the last two ticks.
The same work runs three times before the trace starts, through a
sub-window's recycling, so that the trace holds no compile.

The file keeps what the tests read and drops the rest of the trace (the
runtime's and the compiler's own host events, other planes and stats):
on the chip's plane the ``XLA Modules`` and ``XLA Ops`` lines; on the
host's plane the benchmark-style spans, the program's ``repro/`` spans,
the Python thread's program launches and the runtime's
``DoEnqueueProgram`` events; of stats, ``run_id`` and the spans' own.
"""
import contextlib
import glob
import os
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[3]
sys.path.insert(0, str(ROOT / "src"))
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.profiler import ProfileData  # noqa: E402

from repro.core import gk_select  # noqa: E402
from repro.launch import QuantileService, Window  # noqa: E402

NAMES = [f"s{i}" for i in range(8)]
SPANS = ("window", "job", "tick", "query")
LAUNCH = "PJRT_LoadedExecutable_Execute linkage"
ENQUEUE = "DoEnqueueProgram"
DEVICE_LINES = ("XLA Modules", "XLA Ops")
STATS = ("run_id", "request", "where")


def workload(parts, svc, ticks, span):
    with span("job"):
        gk_select(parts, 0.99).block_until_ready()
    for tick in ticks:
        with span("tick"):
            svc.ingest_batch(NAMES, list(tick))
    for name in NAMES[:2]:
        with span("query"):
            np.asarray(svc.windowed(name, 0.9, window=Window(ticks=2)))


def _quote(text: str) -> str:
    return '"' + text.replace("\\", "\\\\").replace('"', '\\"') + '"'


def _kept(plane_name: str, line_name: str, event_name: str) -> bool:
    if plane_name.startswith("/device:TPU:"):
        return line_name in DEVICE_LINES
    return plane_name == "/host:CPU" and (
        event_name in SPANS or event_name in (LAUNCH, ENQUEUE)
        or event_name.startswith("repro/"))


def _stats(event, stat_ids: dict) -> str:
    out = ""
    for key, value in event.stats:
        if key not in STATS:
            continue
        stat_id = stat_ids.setdefault(key, len(stat_ids) + 1)
        if isinstance(value, str):
            out += f"stats {{ metadata_id: {stat_id} str_value: {_quote(value)} }} "
        else:
            out += f"stats {{ metadata_id: {stat_id} int64_value: {int(value)} }} "
    return out


def shrink(profile) -> bytes:
    """The kept part of ``profile``, as a serialized XSpace."""
    planes = []
    for plane_id, plane in enumerate(profile.planes, 1):
        names, stat_ids, lines = {}, {}, []
        for line_id, line in enumerate(plane.lines, 1):
            events = [
                f"events {{ metadata_id: {names.setdefault(e.name, len(names) + 1)}"
                f" offset_ps: {round(e.start_ns * 1000)}"
                f" duration_ps: {round(e.duration_ns * 1000)}"
                f" {_stats(e, stat_ids)}}}"
                for e in line.events if _kept(plane.name, line.name, e.name)]
            if events:
                lines.append(f"lines {{ id: {line_id} name: {_quote(line.name)}"
                             f" timestamp_ns: 0\n" + "\n".join(events) + "\n}")
        if not lines:
            continue
        meta = [f"event_metadata {{ key: {i} value {{ id: {i} name: "
                f"{_quote(n)} }} }}" for n, i in names.items()]
        meta += [f"stat_metadata {{ key: {i} value {{ id: {i} name: "
                 f"{_quote(n)} }} }}" for n, i in stat_ids.items()]
        planes.append(f"planes {{ id: {plane_id} name: {_quote(plane.name)}\n"
                      + "\n".join(lines + meta) + "\n}")
    return ProfileData.text_proto_to_serialized_xspace("\n".join(planes))


def main(out: str) -> None:
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("record_program_trace: no TPU")
    rng = np.random.default_rng(13)
    parts = jnp.asarray(rng.uniform(size=(4, 2048)).astype(np.float32))
    svc = QuantileService(window_ticks=4, window_subs=2)
    ticks = rng.lognormal(size=(10, len(NAMES), 256)).astype(np.float32)
    for warm in (ticks[0:2], ticks[2:5], ticks[5:8]):
        workload(parts, svc, warm, lambda _: contextlib.nullcontext())
    trace_dir = tempfile.mkdtemp()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation("window"):
            workload(parts, svc, ticks[8:], jax.profiler.TraceAnnotation)
    finally:
        jax.profiler.stop_trace()
    [path] = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    Path(out).write_bytes(shrink(ProfileData.from_file(path)))
    shutil.rmtree(trace_dir)
    print(f"{out}: {os.path.getsize(out)} bytes")


if __name__ == "__main__":
    main(sys.argv[1])
