"""Percent of the job's device time spent in sort and top-k operations.

The job's device time is the union of operations inside the ``job`` spans
(so the input draw is left out).  Operations are matched by their own
HLO names (``sort.27``; XLA lowers a ``top_k`` that takes a whole
partition to a sort); the names matched are printed on an earlier line.
"""
import re

from bench import trace

SORT = re.compile(r"^(sort|top.?k)", re.IGNORECASE)


def read(r):
    if r.trace is None:
        return None
    jobs = r.trace.spans_named("job")
    busy = trace.busy_ns(r.trace, jobs)
    if busy <= 0:
        return None
    sorting, names = trace.op_time_ns(r.trace, SORT.match, jobs)
    r.log(f"sort_share.job matched {sorted(names)}")
    if not names:
        return None
    return 100.0 * sorting / busy
