"""The plain reference that decides ``correct``, and its lower-precision
control.

Both configurations promise the same thing: an answer equal to the value
of rank ``k = ceil(q * n)`` (1-based, ``q * n`` taken in double precision
and clamped to ``[1, n]``) in a sort of the ``n`` float32 values asked
about.  The reference computes that with numpy on the host, from the
benchmark's own copy of the data; it imports nothing of the program.

``control_kth`` is the same reference in the next precision down,
bfloat16: it rounds every value to bfloat16 (to nearest, ties to even)
before it selects.  It is the change that would tempt a faster build, and
it has to read as not correct.
"""
from __future__ import annotations

import math

import numpy as np


def target_rank(n: int, q: float) -> int:
    """1-based rank of the q-quantile of n values."""
    if n < 1:
        raise ValueError("no values to rank")
    return int(min(n, max(1, math.ceil(q * n))))


def kth(values: np.ndarray, k: int) -> np.float32:
    """The k-th smallest (1-based) of ``values``."""
    flat = np.array(values, dtype=np.float32, copy=True).reshape(-1)
    flat.partition(k - 1)
    return flat[k - 1]


def quantile(values: np.ndarray, q: float) -> np.float32:
    return kth(values, target_rank(np.size(values), q))


def round_bf16(values: np.ndarray) -> np.ndarray:
    """float32 values rounded to bfloat16 precision, kept as float32."""
    bits = np.asarray(values, np.float32).view(np.uint32)
    bits = (bits + np.uint32(0x7FFF) + ((bits >> 16) & np.uint32(1))) \
        & np.uint32(0xFFFF0000)
    return bits.view(np.float32)


def control_quantile(values: np.ndarray, q: float) -> np.float32:
    """``quantile`` computed on bfloat16-rounded values."""
    return quantile(round_bf16(values), q)


def same(answer, expected) -> bool:
    """An answer is right when it equals the reference's value."""
    return bool(np.float32(answer) == np.float32(expected))
