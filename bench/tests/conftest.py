"""Test set-up: the CPU, the repository on the import path, and a tiny
copy of the benchmark that runs in seconds."""
import json
import os
import shutil
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = Path(__file__).resolve().parents[2]
for path in (str(ROOT), str(ROOT / "src")):
    if path not in sys.path:
        sys.path.insert(0, path)

import pytest  # noqa: E402

# The same deployments and mixes at sizes a CPU test can hold.
TINY_CONFIGS = {
    "gkselect-1e9-p120": {"partitions": 4, "values_per_partition": 2048},
    "summary-s2048-w10m": {"series": 16, "observations_per_tick": 8,
                        "window_ticks": 10, "window_subs": 5},
}
TINY_TRAFFIC = {
    "uniform-p99": {"check_jobs": 2},
    "dashboard": {"tick_period_s": 0.25, "query_rate_per_s": 12.0,
                  "query_window_ticks": 10, "drain_s": 20},
}
JOB = "gkselect-1e9-p120.uniform-p99"
SERVICE = "summary-s2048-w10m.dashboard"


def copy_benchmark(dest: Path) -> Path:
    """``BENCHMARK.json`` and ``bench/`` copied under ``dest``."""
    shutil.copy(ROOT / "BENCHMARK.json", dest / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", dest / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    return dest


def _update(path: Path, changes: dict) -> None:
    data = json.loads(path.read_text())
    data.update(changes)
    path.write_text(json.dumps(data, indent=1))


@pytest.fixture
def tiny_root(tmp_path):
    """A copy of the benchmark at tiny sizes, whose peak table knows the
    CPU, for runs of ``run.main(..., platform="cpu")``."""
    root = copy_benchmark(tmp_path)
    for name, changes in TINY_CONFIGS.items():
        _update(root / "bench" / "configs" / f"{name}.json", changes)
    for name, changes in TINY_TRAFFIC.items():
        _update(root / "bench" / "traffic" / f"{name}.json", changes)
    peaks = root / "bench" / "peaks.json"
    table = json.loads(peaks.read_text())
    table["cpu"] = dict(table["TPU v5 lite"], source="test stand-in")
    peaks.write_text(json.dumps(table))
    return root


def run_cell(root: Path, workload: str, capsys, *, seed=2**31 + 12345,
             seconds=1.0, trace=0):
    """Run one cell in this process; return (exit code, result or None,
    stdout lines, stderr lines)."""
    from bench import run
    rc = run.main(["--workload", workload, "--seed", str(seed),
                   "--seconds", str(seconds), "--trace", str(trace)],
                  root=root, platform="cpu")
    out, err = capsys.readouterr()
    lines = out.strip().splitlines()
    result = None
    if rc == 0 and lines:
        result = json.loads(lines[-1])
    return rc, result, lines, err.strip().splitlines()
