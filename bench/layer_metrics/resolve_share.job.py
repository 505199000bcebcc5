"""Percent of the job's device time spent in the engine's ``phase_resolve``
round: the operations of the job's program whose innermost
``jax.named_scope`` is ``phase_resolve``, each nanosecond given to the
innermost operation running, over the union of operations inside ``job``
spans (``bench/program_trace.py``).  Nothing where the program names no
phase."""
from bench import program_trace


def read(r):
    return program_trace.phase_share(r, "phase_resolve")
