#!/usr/bin/env python3
"""Sweep the query rate of an open-loop service cell on the chip, to find
the highest rate at which the backlog stays flat (the knee).

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> \\
        --rates 2,4,8 [--set series=2048 ...]

Sets the cell up once, then serves one window of ``--seconds`` at each
rate in turn, going on from the tick the last window reached.  For each
rate it prints the query latencies and how late the serving loop started
the events of the window's last quarter against its first: a backlog that
grows shows as a lateness that grows.  The cell's traffic file keeps the
rate chosen from this, about four fifths of the knee.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--rates", required=True)
    p.add_argument("--set", action="append", default=[],
                   metavar="KEY=JSON", help="override a configuration key")
    args = p.parse_args(argv)
    for path in (str(ROOT), str(ROOT / "src")):
        if path not in sys.path:
            sys.path.insert(0, path)
    import numpy as np
    from bench import device, run

    cell = run.load_cell(ROOT, args.workload)
    for item in args.set:
        key, value = item.split("=", 1)
        cell.config[key] = json.loads(value)
    devices = device.require(cell.chips)
    device.enable_compile_cache(ROOT)
    driver = run.load_module(ROOT / "bench" / "drivers"
                             / f"{cell.traffic['driver']}.py")
    loop = driver.Loop(run.Run(cell=cell, seed=args.seed, devices=devices,
                               span=run.Spans()))
    counter = device.CompileCounter()
    for rate in (float(r) for r in args.rates.split(",")):
        cell.traffic["query_rate_per_s"] = rate
        counter.reset()
        q0, l0 = len(loop.queries), len(loop.lateness)
        t0 = time.perf_counter()
        loop.window(args.seconds)
        took = time.perf_counter() - t0
        lat = np.asarray([(end - due) * 1e3
                          for due, _, _, _, end in loop.queries[q0:]])
        late = np.asarray(loop.lateness[l0:]) * 1e3
        quarter = max(1, len(late) // 4)
        print(f"rate {rate} queries {len(lat)} served_in_s {took:.3f} "
              f"p50_ms {np.percentile(lat, 50):.3f} "
              f"p95_ms {np.percentile(lat, 95):.3f} "
              f"late_first_quarter_ms {late[:quarter].mean():.3f} "
              f"late_last_quarter_ms {late[-quarter:].mean():.3f} "
              f"loaded {len(counter.loaded)}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
