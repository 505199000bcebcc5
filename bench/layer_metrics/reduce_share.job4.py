"""Percent of the sharded job's device time spent in the engine's
``phase_reduce`` round, the candidate butterfly: the operations of the
sharded program whose innermost ``jax.named_scope`` is ``phase_reduce``,
each nanosecond given to the innermost operation running, over the union
of operations inside ``job`` spans, averaged over the chips
(``bench/sharded_program.py``).  Nothing where the program names no
phase."""
from bench import sharded_program


def read(r):
    return sharded_program.phase_share(r, "phase_reduce")
