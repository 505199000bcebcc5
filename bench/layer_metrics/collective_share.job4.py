"""Percent of the sharded job's device time spent in collective
operations, averaged over the chips: the exposed cross-chip time.

The job's device time is the union of operations inside the ``job``
spans.  An operation is a collective by its HLO opcode, read from its own
HLO text in the trace (``all-gather``, ``all-reduce``,
``collective-permute``, ``all-to-all``, ``reduce-scatter``, and their
``-start``/``-done`` halves), since XLA may name an instruction after the
JAX call that made it (``%psum.7 = s32[3] all-reduce(...)``).  The names
matched are printed on an earlier line; nothing where none ran.
"""
import re

from bench import trace

COLLECTIVE = re.compile(r"\s(all-gather|all-reduce|collective-permute|"
                        r"all-to-all|reduce-scatter)(-start|-done)?\(")


def read(r):
    if r.trace is None:
        return None
    jobs = r.trace.spans_named("job")
    busy = trace.busy_ns(r.trace, jobs)
    if busy <= 0:
        return None
    total, names = 0.0, set()
    for evs in r.trace.ops.values():
        for hlo, s, e in (piece for lo, hi in trace.merge(jobs)
                          for piece in trace.clip(evs, lo, hi)):
            if COLLECTIVE.search(hlo):
                total += e - s
                names.add(trace.op_name(hlo))
    r.log(f"collective_share.job4 matched {sorted(names)}")
    if not names:
        return None
    return 100.0 * total / len(r.trace.ops) / busy
