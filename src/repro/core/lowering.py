"""Lower an eager entry point without running it.

The eager entries (``gk_select``, ``gk_select_multi``,
``distributed_quantile``, ``distributed_quantile_multi``) check their input
and then run one jitted program through ``call``.  ``lower(entry, *args,
**kw)`` makes the same entry call with the same arguments but returns that
program's ``jax.stages.Lowered`` instead of running it, and skips the input
checks (they would read the data).  So ``lower(entry, ...).compile()``
compiles exactly the program that ``entry(...)`` then runs: no caller
restates an entry's arguments or defaults.
"""
from __future__ import annotations

import contextvars

import jax

_LOWERING = contextvars.ContextVar("repro_lowering", default=False)


def active() -> bool:
    """True inside ``lower``: entries skip their data checks."""
    return _LOWERING.get()


def call(program, *args, **kwargs):
    """``program(*args, **kwargs)``, or ``program.lower(...)`` inside
    ``lower``."""
    if _LOWERING.get():
        return program.lower(*args, **kwargs)
    return program(*args, **kwargs)


def lower(entry, *args, **kwargs) -> jax.stages.Lowered:
    """The program ``entry(*args, **kwargs)`` would run, lowered."""
    token = _LOWERING.set(True)
    try:
        out = entry(*args, **kwargs)
    finally:
        _LOWERING.reset(token)
    if not isinstance(out, jax.stages.Lowered):
        raise TypeError(f"{entry.__name__} runs no program through "
                        f"repro.core.lowering.call")
    return out
