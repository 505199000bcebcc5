#!/usr/bin/env python3
"""Spread of a cell's end-to-end metrics over repeated runs, the number a
bound is set from.

    python3 bench/spread.py RESULTS...

Each argument is a file holding a run's output (its last line is the
result) or a ``.jsonl`` of result lines.  For each metric it prints the
median and the spread: the distance between the first and the third
quartile, as ``statistics.quantiles(values, n=4)`` gives them, over the
median.  Pass the files of one set of runs at a time.
"""
from __future__ import annotations

import json
import statistics
import sys
from collections import defaultdict


def results(paths):
    for path in paths:
        with open(path) as f:
            lines = [ln for ln in f.read().splitlines() if ln.startswith("{")]
        if path.endswith(".jsonl"):
            yield from (json.loads(ln) for ln in lines)
        elif lines:
            yield json.loads(lines[-1])


def spread(values) -> float:
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main(paths) -> int:
    by_metric = defaultdict(list)
    correct = []
    for r in results(paths):
        correct.append(r["correct"])
        for name, m in r["metrics"].items():
            by_metric[name].append(m["value"])
    print(f"runs {len(correct)} correct {sum(correct)}")
    for name, values in sorted(by_metric.items()):
        line = f"{name} n {len(values)} median {statistics.median(values)}"
        if len(values) >= 2:
            line += f" spread {spread(values)}"
        print(line + f" values {values}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
