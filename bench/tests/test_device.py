"""The platform guard and the peak table."""
import json
import os
import subprocess
import sys

import pytest

from bench import device
from conftest import JOB, ROOT, copy_benchmark


def test_known_peaks():
    v5e = device.peaks("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["ici_bits_per_s"] == 1600e9


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="TPU v99"):
        device.peaks("TPU v99")


def test_too_few_chips_is_refused():
    with pytest.raises(device.NoDevice, match="needs 4"):
        device.require(4, platform="cpu")
    with pytest.raises(device.NoDevice, match="no tpu device"):
        device.require(1, platform="tpu")


def _run(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", JOB, "--seed",
         "4294967301", "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(proc):
    assert proc.returncode != 0
    for line in proc.stdout.splitlines():
        with pytest.raises(ValueError):
            json.loads(line)


def test_a_run_without_the_chip_exits_with_no_result():
    _no_result(_run(ROOT))


def test_the_benchmark_alone_does_not_run(tmp_path):
    """A directory with only ``BENCHMARK.json`` and ``bench/`` has no
    system to measure."""
    _no_result(_run(copy_benchmark(tmp_path)))
