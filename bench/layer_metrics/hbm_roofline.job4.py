"""The sharded job's share of its HBM roofline on one chip, in percent.

The least any exact job must do is read its input once, and each chip
holds ``1 / chips`` of it: P * n_i / chips values of the configured dtype.
At the chip's peak HBM bandwidth that takes ``bytes / hbm_bytes_per_s``
seconds; the share is that time over one job's device time on a chip (the
union of operations inside ``job`` spans, averaged over the chips, divided
by the number of jobs traced).  ``hbm_roofline.job`` counts the whole
column against one chip, which fits only a one-chip cell.
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
              * np.dtype(cfg["dtype"]).itemsize / r.cell.chips)
    least_s = nbytes / float(r.peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (busy * 1e-9 / len(jobs))
