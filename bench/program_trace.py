"""The program's own spans and named scopes, read from a profiler trace.

``bench/trace.py`` reduces a trace to the device's operations and the
benchmark's own spans (``job``, ``tick``, ``query``).  The program adds
two more things to the same trace (``repro.obs``):

* host spans named ``repro/<name>`` (``service.windowed``, ``read``,
  ...), on the host plane and the host's clock, with their metadata as
  stats; ``read_spans`` keeps those of the measured window, with the
  thread line each ran on;
* ``jax.named_scope`` names on the engine's rounds (``phase_sketch``,
  ...), which the trace does not carry: each operation's event is named by
  its HLO instruction (``%sort.20 = ...``), and the compiled module's text
  maps that instruction to its ``metadata={op_name="..."}`` path
  (``scopes``).

Device time is given to the innermost operation running (``self_times``):
an operation that holds others, such as a ``conditional`` or a ``while``,
keeps only the time its children leave uncovered.  All times are
nanoseconds on the host's clock, as in ``bench/trace.py``.
"""
from __future__ import annotations

import bisect
import functools
import re
from collections import defaultdict
from typing import Dict, Iterable, List, NamedTuple, Optional, Sequence, Tuple

from bench import trace as tracemod

PREFIX = "repro/"
READ = "read"                 # a blocking device-to-host read
LAUNCH = "PJRT_LoadedExecutable_Execute linkage"
PHASE = re.compile(r"phase_\w+")
INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%(\S+) = ")
OP_NAME = re.compile(r'op_name="([^"]*)"')


class Span(NamedTuple):
    name: str                 # without the ``repro/`` prefix
    start: float
    end: float
    stats: dict
    thread: str               # the host line it was recorded on


# -- host spans -----------------------------------------------------------------

def read_spans(profile, trace: tracemod.Trace) -> List[Span]:
    """The program's spans that start inside the trace's window, ordered
    by start, an enclosing span before the spans it holds."""
    lo, hi = trace.window
    out = []
    for plane in profile.planes:
        if plane.name != tracemod.HOST_PLANE:
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIX) and lo <= e.start_ns < hi:
                    out.append(Span(e.name[len(PREFIX):], e.start_ns,
                                    e.start_ns + e.duration_ns,
                                    dict(e.stats), line.name))
    return sorted(out, key=lambda s: (s.start, -s.end))


def launch_times(profile, trace: tracemod.Trace) -> List[float]:
    """Host times, inside the window, at which a thread launched a
    program (the runtime's ``PJRT_LoadedExecutable_Execute linkage``
    events, on the launching thread), sorted."""
    lo, hi = trace.window
    return sorted(e.start_ns for plane in profile.planes
                  if plane.name == tracemod.HOST_PLANE
                  for line in plane.lines for e in line.events
                  if e.name == LAUNCH and lo <= e.start_ns < hi)


def parents(spans: Sequence[Span]) -> List[Optional[int]]:
    """Index of each span's innermost enclosing span on its own thread, or
    ``None`` for a root.  ``spans`` are ordered as ``read_spans`` orders
    them."""
    out: List[Optional[int]] = []
    open_by_thread: Dict[str, List[int]] = defaultdict(list)
    for i, s in enumerate(spans):
        stack = open_by_thread[s.thread]
        while stack and spans[stack[-1]].end < s.end:
            stack.pop()
        out.append(stack[-1] if stack else None)
        stack.append(i)
    return out


def self_segments(spans: Sequence[Span]) -> List[Tuple[float, float, int]]:
    """``(start, end, i)``: the intervals in which ``spans[i]`` was the
    innermost span open on its thread, sorted by start."""
    held = defaultdict(list)
    for i, p in enumerate(parents(spans)):
        if p is not None:
            held[p].append(i)
    out = []
    for i, s in enumerate(spans):
        t = s.start
        for c in held[i]:
            if spans[c].start > t:
                out.append((t, spans[c].start, i))
            t = max(t, spans[c].end)
        if s.end > t:
            out.append((t, s.end, i))
    return sorted(out)


def self_ns(spans: Sequence[Span]) -> List[float]:
    """Each span's duration less the part of it the spans it holds
    cover."""
    out = [0.0] * len(spans)
    for s, e, i in self_segments(spans):
        out[i] += e - s
    return out


class Cover:
    """``self_segments`` indexed for the question: which spans' own time
    covers an interval or an instant."""

    def __init__(self, spans: Sequence[Span]):
        self.spans = spans
        self.segments = self_segments(spans)
        self.starts = [s for s, _, _ in self.segments]
        self.longest = max((e - s for s, e, _ in self.segments), default=0.0)

    def own(self, lo: float, hi: float) -> Dict[int, float]:
        """Per span index, the part of ``[lo, hi]`` its own time covers;
        for an instant (``lo == hi``), 1 for each span open then."""
        cover: Dict[int, float] = defaultdict(float)
        first = bisect.bisect_left(self.starts, lo - self.longest)
        for s, e, i in self.segments[first:]:
            if s > hi or (s == hi and lo < hi):
                break
            if lo == hi:
                if s <= lo < e:
                    cover[i] += 1.0
            elif min(e, hi) > max(s, lo):
                cover[i] += min(e, hi) - max(s, lo)
        return cover

    def innermost(self, lo: float, hi: float) -> Optional[Span]:
        """The span whose own time covers most of ``[lo, hi]``; for an
        instant, the innermost span open then.  ``None`` when no span
        overlaps."""
        cover = self.own(lo, hi)
        if not cover:
            return None
        return self.spans[max(cover, key=lambda i: (cover[i], i))]


def descendants(spans: Sequence[Span], i: int, name: Optional[str] = None,
                up: Optional[List[Optional[int]]] = None) -> List[Span]:
    """The spans held, at any depth, by ``spans[i]`` (those named
    ``name``, if given).  ``up`` is ``parents(spans)``, if at hand."""
    up = parents(spans) if up is None else up
    out = []
    for j in range(i + 1, len(spans)):
        if spans[j].start >= spans[i].end:
            break
        p = up[j]
        while p is not None and p != i:
            p = up[p]
        if p == i and (name is None or spans[j].name == name):
            out.append(spans[j])
    return out


def per_root(spans: Sequence[Span], root: str,
             name: str) -> List[Tuple[float, float]]:
    """For each span named ``root``: its duration and the summed duration
    of the ``name`` spans it holds, in nanoseconds."""
    up = parents(spans)
    return [(s.end - s.start,
             sum(d.end - d.start for d in descendants(spans, i, name, up)))
            for i, s in enumerate(spans) if s.name == root]


def span_table(spans: Sequence[Span],
               launched: Sequence[float]) -> Dict[str, dict]:
    """Per span name: how many spans, their summed self time (ns), and the
    programs launched while such a span was the innermost one open."""
    cover = Cover(spans)
    table: Dict[str, dict] = defaultdict(
        lambda: {"spans": 0, "self_ns": 0.0, "launches": 0})
    for s, own in zip(spans, self_ns(spans)):
        table[s.name]["spans"] += 1
        table[s.name]["self_ns"] += own
    for t in launched:
        inner = cover.innermost(t, t)
        if inner is not None:
            table[inner.name]["launches"] += 1
    return dict(table)


def gap_intervals(trace: tracemod.Trace, n: int = 10) -> List[Tuple[float, float]]:
    """The ``n`` longest idle intervals of any chip inside the window,
    longest first, found and ordered as ``bench/trace.py``'s ``idle_gaps``
    finds and orders them."""
    lo, hi = trace.window
    gaps = []
    for evs in trace.ops.values():
        edges = [lo] + [t for iv in tracemod.merge(evs) for t in iv] + [hi]
        gaps.extend((s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s)
    gaps.sort(key=lambda g: g[0] - g[1])
    return gaps[:n]


def label_gaps(trace: tracemod.Trace, spans: Sequence[Span],
               n: int = 10) -> List[Tuple[str, float, float]]:
    """``bench/trace.py``'s ``n`` longest idle gaps as ``(label, seconds,
    seconds the labelling program span covers)``.  The label is
    ``"<benchmark span>:<program span>"``, by the program span whose own
    time covers most of the gap, or the benchmark span alone where no
    program span overlaps it.  Like the benchmark span's, the program
    span's name says what the gap touches: the covered seconds say how
    much of it the program's own work accounts for."""
    cover = Cover(spans)
    out = []
    for (s, e), (name, seconds) in zip(gap_intervals(trace, n),
                                       tracemod.idle_gaps(trace, n)):
        own = cover.own(s, e)
        if not own:
            out.append((name, seconds, 0.0))
            continue
        best = max(own, key=lambda i: (own[i], i))
        out.append((f"{name}:{spans[best].name}", seconds, own[best] * 1e-9))
    return out


def host_activity(profile, intervals: Sequence[Tuple[float, float]],
                  skip: Iterable[str], n: int = 3) -> List[List[Tuple[str, float]]]:
    """For each ``(lo, hi)`` interval: the ``n`` host events whose own
    time (less the events they hold on their thread) covers most of it, as
    ``(name, milliseconds)``.  The program's spans and the names in
    ``skip`` (the benchmark's spans) are left out, so that what remains is
    the runtime's, the compiler's and the Python tracer's events."""
    skip = set(skip) | {tracemod.WINDOW}
    found: List[List[Span]] = [[] for _ in intervals]
    for plane in profile.planes:
        if plane.name != tracemod.HOST_PLANE:
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in skip or e.name.startswith(PREFIX):
                    continue
                s, end = e.start_ns, e.start_ns + e.duration_ns
                for k, (lo, hi) in enumerate(intervals):
                    if s < hi and end > lo:
                        found[k].append(Span(e.name, s, end, {}, line.name))
    out = []
    for (lo, hi), events in zip(intervals, found):
        events.sort(key=lambda ev: (ev.start, -ev.end))
        cover: Dict[str, float] = defaultdict(float)
        for a, b, i in self_segments(events):
            if min(b, hi) > max(a, lo):
                cover[events[i].name] += min(b, hi) - max(a, lo)
        top = sorted(cover.items(), key=lambda kv: -kv[1])[:n]
        out.append([(name, ns * 1e-6) for name, ns in top])
    return out


# -- device scopes --------------------------------------------------------------

def scopes(hlo_text: str) -> Dict[str, str]:
    """HLO instruction name -> its ``op_name`` path, for every instruction
    of a compiled module's text that carries one."""
    out = {}
    for line in hlo_text.splitlines():
        m = INSTRUCTION.match(line)
        if m:
            path = OP_NAME.search(line)
            if path:
                out[m.group(1)] = path.group(1)
    return out


def module_name(hlo_text: str) -> str:
    """The module's name (``jit__gk_select_jit``), which names its launches
    in the trace's ``XLA Modules`` line (``jit__gk_select_jit(<id>)``)."""
    first = hlo_text.lstrip().split("\n", 1)[0]
    if not first.startswith("HloModule "):
        raise ValueError("not the text of an HLO module")
    return first.split()[1].rstrip(",")


def in_scope(path: str, scope: str) -> bool:
    """True when ``scope`` is the innermost ``phase_*`` name of ``path``."""
    found = PHASE.findall(path)
    return bool(found) and found[-1] == scope


def self_times(events: Iterable[tracemod.Event]) -> Dict[str, float]:
    """Nanoseconds in which each operation (by its own name) was the
    innermost one running.  An event that holds others keeps only the time
    they leave uncovered, so the values sum to the union of the events."""
    out: Dict[str, float] = defaultdict(float)
    stack: List[Tuple[str, float]] = []        # (name, end), innermost last
    now = float("-inf")

    def run_to(t):
        """Give ``[now, t)`` to the innermost open events, closing those
        that end by ``t``."""
        nonlocal now
        while stack and now < t:
            name, end = stack[-1]
            if min(end, t) > now:
                out[name] += min(end, t) - now
                now = min(end, t)
            if end > t:
                break
            stack.pop()
        now = max(now, t)

    for hlo, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        run_to(s)
        stack.append((tracemod.op_name(hlo), e))
    run_to(float("inf"))
    return dict(out)


def program_launches(trace: tracemod.Trace, module: str,
                     within: Sequence[tracemod.Event]) -> List[tracemod.Event]:
    """The launches of ``module`` on every chip, cut to the ``within``
    spans."""
    spans = tracemod.merge(within)
    starts = [s for s, _ in spans]
    out = []
    for evs in trace.modules.values():
        for name, s, e in evs:
            if name.split("(", 1)[0] != module:
                continue
            i = max(0, bisect.bisect_right(starts, s) - 1)
            for a, b in spans[i:]:
                if a >= e:
                    break
                if min(b, e) > max(a, s):
                    out.append((name, max(a, s), min(b, e)))
    return out


def scope_time_ns(trace: tracemod.Trace, op_scopes: Dict[str, str], scope: str,
                  within: Sequence[tracemod.Event]) -> float:
    """Device time, averaged over the chips, of the operations whose
    innermost phase scope is ``scope``, counting each nanosecond inside the
    ``within`` spans once, for the innermost operation running then."""
    if not trace.ops:
        return 0.0
    total = 0.0
    for evs in trace.ops.values():
        cut = [piece for lo, hi in tracemod.merge(within)
               for piece in tracemod.clip(evs, lo, hi)]
        for name, ns in self_times(cut).items():
            if in_scope(op_scopes.get(name, ""), scope):
                total += ns
    return total / len(trace.ops)


# -- the job cell's program -------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _job_hlo(shape: Tuple[int, int], dtype: str, q: float) -> str:
    import jax
    import repro.core
    from jax.experimental.compilation_cache import compilation_cache
    from repro.core import lowering
    x = jax.ShapeDtypeStruct(shape, dtype,
                             sharding=jax.sharding.SingleDeviceSharding(
                                 jax.devices()[0]))
    enabled = jax.config.jax_enable_compilation_cache
    jax.clear_caches()
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return lowering.lower(repro.core.gk_select, x, q).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
        compilation_cache.reset_cache()


def job_hlo(cell) -> str:
    """The compiled text of the program a job cell's ``gk_select`` call
    runs: the same entry, arguments and device as ``job_loop``.

    It is compiled afresh, past the in-memory and persistent compilation
    caches: the persistent cache's key leaves out the ``op_name``
    metadata, so the program the run loaded may carry the scopes of
    another checkout of the same code (a parent commit's, or a child's),
    while its instructions, and so their names, are the same."""
    cfg = cell.config
    return _job_hlo((int(cfg["partitions"]), int(cfg["values_per_partition"])),
                    cfg["dtype"], float(cell.traffic["q"]))


def phase_shares(r) -> Optional[Dict[str, float]]:
    """Percent of the job's device time (the union of operations inside
    ``job`` spans) whose innermost operation ran under each phase scope of
    the job's program, logged with what no phase holds; ``None`` when
    nothing was traced or the program names no phase.  Computed once per
    reading, which keeps it for the other readers."""
    if r.trace is None:
        return None
    if not hasattr(r, "_phase_shares"):
        r._phase_shares = _phase_shares(r)
    return r._phase_shares


def _phase_shares(r) -> Optional[Dict[str, float]]:
    jobs = r.trace.spans_named("job")
    busy = tracemod.busy_ns(r.trace, jobs)
    if busy <= 0:
        return None
    hlo = job_hlo(r.cell)
    op_scopes = scopes(hlo)
    phases = sorted({PHASE.findall(p)[-1] for p in op_scopes.values()
                     if PHASE.search(p)})
    if not phases:
        return None
    within = program_launches(r.trace, module_name(hlo), jobs)
    shares = {phase: 100.0 * scope_time_ns(r.trace, op_scopes, phase, within)
              / busy for phase in phases}
    program = 100.0 * tracemod.busy_ns(r.trace, within) / busy
    r.log("phase shares of the job's device time (%): "
          + " ".join(f"{p} {v:.4f}" for p, v in shares.items())
          + f"; no phase {program - sum(shares.values()):.4f}"
          + f"; other programs {100.0 - program:.4f}")
    return shares


def phase_share(r, scope: str) -> Optional[float]:
    """``phase_shares(r)[scope]``; ``None`` where that is missing."""
    shares = phase_shares(r)
    return None if shares is None else shares.get(scope)
