"""The job's share of its HBM roofline, in percent.

The least any exact job must do is read its input once: P * n_i values of
the configured dtype.  At the chip's peak HBM bandwidth that takes
``bytes / hbm_bytes_per_s`` seconds; the share is that time over the
device time of one job (the union of operations inside ``job`` spans,
divided by the number of jobs traced).  It counts the same work however
the program does it.
"""
import numpy as np

from bench import trace


def read(r):
    if r.trace is None:
        return None
    jobs = r.trace.spans_named("job")
    busy = trace.busy_ns(r.trace, jobs)
    if not jobs or busy <= 0:
        return None
    cfg = r.cell.config
    nbytes = (int(cfg["partitions"]) * int(cfg["values_per_partition"])
              * np.dtype(cfg["dtype"]).itemsize)
    least_s = nbytes / float(r.peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (busy * 1e-9 / len(jobs))
