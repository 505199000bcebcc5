"""Reduction of a JAX profiler trace to the numbers the per-layer metrics
read.

A traced run writes one ``.xplane.pb``; ``jax.profiler.ProfileData``
reads it.  Each TPU chip is a plane named ``/device:TPU:<n>``; on it the
line ``XLA Ops`` holds one event per operation the chip ran, named by its
HLO text (``%sort.27 = (f32[...]) sort(...)``; an op that holds others,
such as a ``conditional``, spans them), and the line ``XLA Modules`` one
event per program launch.  The host plane
``/host:CPU`` holds, among the runtime's own events, the benchmark's
spans: ``jax.profiler.TraceAnnotation`` around the measured window
(``window``) and around each call into the system.  A driver names its
own spans (``job``, ``tick``, ``query``, ...); the run passes the names it
recorded, so that a new driver's spans need no edit here.

All times are nanoseconds on that clock.  The functions below the reader
work on plain ``(name, start, end)`` tuples, so that a test can check them
against hand-computed values.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]          # (name, start_ns, end_ns)

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
ENQUEUE = "DoEnqueueProgram"
WINDOW = "window"


@dataclasses.dataclass
class Trace:
    """A traced window: per chip its operations and program launches, and
    the benchmark's host spans inside the window."""
    window: Tuple[float, float]
    ops: Dict[str, List[Event]]
    modules: Dict[str, List[Event]]
    spans: List[Event]
    clock_offset_ns: float = 0.0

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def spans_named(self, name: str) -> List[Event]:
        return [s for s in self.spans if s[0] == name]


def find_xplane(profile_dir: str) -> str:
    """The one ``.xplane.pb`` a trace wrote under ``profile_dir``."""
    found = glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under "
                                f"{profile_dir}, found {len(found)}")
    return found[0]


def _events(line) -> List[Event]:
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for e in line.events]


def _run_ids(line, name: Optional[str] = None) -> Dict[int, float]:
    """Start time of each event (of ``name``, if given) by its ``run_id``."""
    out = {}
    for e in line.events:
        if name is None or e.name == name:
            run_id = dict(e.stats).get("run_id")
            if run_id is not None:
                out.setdefault(int(run_id), e.start_ns)
    return out


def _shift(events: List[Event], by: float) -> List[Event]:
    return [(n, s + by, e + by) for n, s, e in events]


def reduce(profile, span_names: Iterable[str]) -> Trace:
    """Reduce a ``jax.profiler.ProfileData`` to a ``Trace`` that keeps the
    host spans named ``span_names``.  ``ValueError`` when the trace holds
    no ``window`` span.

    The chip's clock runs apart from the host's by a millisecond or two.
    Every launch carries a ``run_id`` on both sides: the host's
    ``DoEnqueueProgram`` event and the chip's ``XLA Modules`` event.  A
    program cannot start before it was enqueued, so each pair bounds the
    offset from below; the device events are moved by the largest bound."""
    ops, modules, host, enqueued, started = {}, {}, [], {}, {}
    wanted = set(span_names) | {WINDOW}
    for plane in profile.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[plane.name] = _events(line)
                elif line.name == MODULES_LINE:
                    modules[plane.name] = _events(line)
                    started[plane.name] = _run_ids(line)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host.extend(e for e in _events(line) if e[0] in wanted)
                enqueued.update(_run_ids(line, ENQUEUE))
    bounds = [t - s for runs in started.values()
              for run_id, s in runs.items()
              if (t := enqueued.get(run_id)) is not None]
    offset = max(bounds) if bounds else 0.0
    windows = [e for e in host if e[0] == WINDOW]
    if len(windows) != 1:
        raise ValueError(f"the trace holds {len(windows)} '{WINDOW}' spans")
    lo, hi = windows[0][1], windows[0][2]
    return Trace(
        window=(lo, hi),
        ops={p: clip(_shift(evs, offset), lo, hi) for p, evs in ops.items()},
        modules={p: [e for e in _shift(evs, offset) if lo <= e[1] < hi]
                 for p, evs in modules.items()},
        spans=sorted((e for e in host if e[0] != WINDOW and lo <= e[1] < hi),
                     key=lambda ev: ev[1]),
        clock_offset_ns=offset,
    )


def read(profile_dir: str, span_names: Iterable[str]) -> Trace:
    from jax.profiler import ProfileData
    return reduce(ProfileData.from_file(find_xplane(profile_dir)), span_names)


# -- interval arithmetic ------------------------------------------------------

def clip(events: Iterable[Event], lo: float, hi: float) -> List[Event]:
    """Events cut to ``[lo, hi)``; those wholly outside are dropped."""
    out = []
    for name, s, e in events:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            out.append((name, s, e))
    return out


def merge(events: Iterable[Event]) -> List[Tuple[float, float]]:
    """The union of the events' intervals, as sorted disjoint intervals."""
    out: List[List[float]] = []
    for _, s, e in sorted(events, key=lambda ev: ev[1]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def covered(intervals: Sequence[Tuple[float, float]]) -> float:
    return sum(e - s for s, e in intervals)


def _overlapping(events: Sequence[Event], lo: float, hi: float) -> List[Event]:
    """The events, sorted by start, that overlap ``[lo, hi)``, cut to it.
    Finds the first candidate by bisection, so that a reduction over many
    spans stays near linear in the events."""
    starts = [e[1] for e in events]
    longest = max((e[2] - e[1] for e in events), default=0.0)
    out = []
    for name, s, e in events[bisect.bisect_left(starts, lo - longest):]:
        if s >= hi:
            break
        if e > lo:
            out.append((name, max(s, lo), min(e, hi)))
    return out


def _inside(events: Sequence[Event], within: Sequence[Event]) -> List[Event]:
    """The parts of the events that lie inside the union of ``within``."""
    events = sorted(events, key=lambda ev: ev[1])
    return [cut for lo, hi in merge(within)
            for cut in _overlapping(events, lo, hi)]


def busy_ns(trace: Trace, within: Optional[Sequence[Event]] = None) -> float:
    """Nanoseconds in which an operation ran, averaged over the chips;
    ``within`` restricts it to the union of those spans."""
    if not trace.ops:
        return 0.0
    total = 0.0
    for evs in trace.ops.values():
        total += covered(merge(evs if within is None else _inside(evs, within)))
    return total / len(trace.ops)


def idle_share(trace: Trace) -> Optional[float]:
    """Percent of the window in which the chips ran no operation, or
    ``None`` when the trace holds no chip."""
    if not trace.ops or trace.window[1] <= trace.window[0]:
        return None
    window = trace.window[1] - trace.window[0]
    return 100.0 * (1.0 - busy_ns(trace) / window)


def op_time_ns(trace: Trace, match, within: Sequence[Event]) -> Tuple[float, set]:
    """Device time of the operations whose ``op_name`` ``match`` accepts,
    inside the ``within`` spans, averaged over the chips; and the names
    matched."""
    if not trace.ops:
        return 0.0, set()
    total, names = 0.0, set()
    for evs in trace.ops.values():
        for hlo, s, e in _inside(evs, within):
            name = op_name(hlo)
            if match(name):
                total += e - s
                names.add(name)
    return total / len(trace.ops), names


def launches_in(trace: Trace, within: Sequence[Event]) -> int:
    """Program launches that started inside the ``within`` spans, summed
    over the chips."""
    spans = merge(within)
    starts = [lo for lo, _ in spans]
    count = 0
    for evs in trace.modules.values():
        for _, s, _ in evs:
            i = bisect.bisect_right(starts, s) - 1
            count += i >= 0 and s < spans[i][1]
    return count


def idle_gaps(trace: Trace, n: int = 10) -> List[Tuple[str, float]]:
    """The ``n`` longest idle intervals of any chip inside the window, as
    ``(what the host was doing, seconds)``: the name of the benchmark span
    that overlaps the gap most, or ``"none"``."""
    lo, hi = trace.window
    gaps = []
    for evs in trace.ops.values():
        edges = [lo] + [t for iv in merge(evs) for t in iv] + [hi]
        gaps.extend((s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s)
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for s, e in gaps[:n]:
        best, name = 0.0, "none"
        for span, a, b in _overlapping(trace.spans, s, e):
            if b - a > best:
                best, name = b - a, span
        out.append((name, (e - s) * 1e-9))
    return out


def op_name(hlo: str) -> str:
    """An operation's own name (``sort.27``) out of its HLO text."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def top_ops(trace: Trace, n: int = 10) -> List[Tuple[str, float]]:
    """The ``n`` operations that took most device time, in seconds summed
    over the window and averaged over the chips, by ``op_name``."""
    by_name: Dict[str, float] = defaultdict(float)
    for evs in trace.ops.values():
        for name, s, e in evs:
            by_name[op_name(name)] += (e - s) * 1e-9
    chips = max(1, len(trace.ops))
    ranked = sorted(by_name.items(), key=lambda kv: -kv[1])[:n]
    return [(name, secs / chips) for name, secs in ranked]


def breakdown(trace: Trace, n: int = 10) -> dict:
    return {"device_ops": [list(x) for x in top_ops(trace, n)],
            "idle_gaps": [list(x) for x in idle_gaps(trace, n)]}
