"""Percent of the traced window in which the chip ran no operation, in the
service cell."""
from bench import trace


def read(r):
    return None if r.trace is None else trace.idle_share(r.trace)
