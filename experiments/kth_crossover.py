"""Chip sweep behind ``local_ops.BISECT_MIN_LANES``: the device time of
picking the k-th smallest of a flat float32 buffer by a sort of its order
keys (``local_ops._kth_sort``) and by order-key bisection
(``local_ops._kth_bisect``, one key bit a pass), at 2^14 to 2^30 lanes.
From 2^20 lanes up it also times a 4-bit digit a pass (8 passes of 15
counts), the wider-digit alternative.

Each selector is a jitted program of its own, warmed up, then launched
``reps`` times under the profiler; a time is the median of its launches'
device durations (the trace's ``XLA Modules`` line), so host dispatch is
left out.  Every selection is checked against the sort's answer.

    python experiments/kth_crossover.py [--min-log2 14] [--max-log2 30] \
        [--out sweep.json]

Needs a TPU.  Prints one JSON line per size; ``--out`` also writes them
all, with the device kind, to one file.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import statistics
import sys
import tempfile
import time
from pathlib import Path

import jax
import jax.numpy as jnp

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))

from repro.core import local_ops  # noqa: E402


def by_sort(x, k):
    return local_ops._kth_sort(x, k)


def by_bisect(x, k):
    return local_ops._kth_bisect(x, k)


def by_digit4(x, k):
    """4 bits a pass: 15 thresholds counted in one pass over the buffer."""
    u = jnp.uint32

    def step(i, prefix):
        shift = (28 - 4 * i).astype(u)
        trials = [prefix | (u(d) << shift) for d in range(1, 16)]
        xb, _ = jax.lax.optimization_barrier((x, prefix))
        below = [local_ops._count_below_key(xb, t) < k for t in trials]
        digit = sum(b.astype(u) for b in below)
        return prefix | (digit << shift)

    key = jax.lax.fori_loop(0, 8, step, jnp.zeros((), u))
    return local_ops._from_key(key, x.dtype)


SELECTORS = {"sort": by_sort, "bisect": by_bisect, "digit4": by_digit4}


def program(name, fn, log2):
    def f(x, k):
        return fn(x, k)
    f.__name__ = f"kth_{name}_{log2}"
    return jax.jit(f)


def device_ms(profile_dir: str) -> dict:
    """Module name -> device milliseconds of each of its launches."""
    path = glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    out: dict = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        for line in plane.lines:
            if line.name != "XLA Modules":
                continue
            for e in line.events:
                name = e.name.split("(", 1)[0]
                out.setdefault(name, []).append(e.duration_ns * 1e-6)
    return out


def sweep(log2s, reps):
    rows = []
    for log2 in log2s:
        n = 1 << log2
        x = jax.random.uniform(jax.random.key(log2), (n,), jnp.float32,
                               -1e9, 1e9)
        ks = [jnp.int32(1 + (n * j) // reps) for j in range(reps)]
        names = ["sort", "bisect"] + (["digit4"] if log2 >= 20 else [])
        progs = {s: program(s, SELECTORS[s], log2) for s in names}
        for s, f in progs.items():                        # warm up
            jax.block_until_ready(f(x, ks[0]))
        r = reps if log2 < 27 else max(3, reps // 4)
        wall = {}
        with tempfile.TemporaryDirectory() as d:
            with jax.profiler.trace(d):
                answers = {}
                for s, f in progs.items():
                    t = time.perf_counter()
                    answers[s] = [jax.block_until_ready(f(x, ks[j]))
                                  for j in range(r)]
                    wall[s] = (time.perf_counter() - t) * 1e3 / r
            times = device_ms(d)
        for s in names:
            if any(a != b for a, b in zip(answers[s], answers["sort"])):
                raise AssertionError(f"{s} disagrees with the sort at 2^{log2}")
        row = {"log2_lanes": log2}
        for s in names:
            ms = times.get(f"jit_kth_{s}_{log2}", [])
            row[f"{s}_ms"] = statistics.median(ms) if ms else None
            row[f"{s}_wall_ms"] = wall[s]
        rows.append(row)
        print(json.dumps(row), flush=True)
        del x
    return rows


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--min-log2", type=int, default=14)
    p.add_argument("--max-log2", type=int, default=30)
    p.add_argument("--reps", type=int, default=12)
    p.add_argument("--out", type=Path)
    args = p.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"kth_crossover times the chip; found {dev.platform}")
    rows = sweep(range(args.min_log2, args.max_log2 + 1), args.reps)
    if args.out:
        args.out.write_text(json.dumps({"device": dev.device_kind,
                                        "rows": rows}, indent=1))


if __name__ == "__main__":
    main()
