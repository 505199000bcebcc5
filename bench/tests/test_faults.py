"""A whole run at tiny size on the CPU, past the chip guard: sound, it
comes out correct; with the timed path broken underneath, it does not.

Each fault is one the cell can have.  The job cell: an answer altered
where it is produced, half of the partitions left out, and a stale answer
returned in place of a new one.  The service cell: an answer altered,
half of every tick's values left out, and ticks that stop landing once
the window opens (the service's state left unchanged).  Neither cell
crosses chips, so there is no exchange to leave out.
"""
import re

import numpy as np
import pytest

import repro.core
import repro.launch
from conftest import JOB, SERVICE, run_cell
from repro.core import lowering

_gk_select = repro.core.gk_select


def _altered(parts, q, **kw):
    answer = _gk_select(parts, q, **kw)
    if lowering.active():        # set-up loads the program it would run
        return answer
    return np.nextafter(np.float32(answer), np.float32(np.inf))


def _half(parts, q, **kw):
    return _gk_select(parts[: parts.shape[0] // 2], q, **kw)


class _Stale:
    def __init__(self):
        self.first = None

    def __call__(self, parts, q, **kw):
        if lowering.active():
            return _gk_select(parts, q, **kw)
        if self.first is None:
            self.first = _gk_select(parts, q, **kw)
        return self.first


JOB_FAULTS = {"altered": _altered, "half": _half, "stale": _Stale()}


def _service(kind):
    base = repro.launch.QuantileService

    class Faulty(base):
        ingested = 0

        def ingest_batch(self, names, batches, **kw):
            self.ingested += 1
            if kind == "half":
                batches = [b[: len(b) // 2] for b in batches]
            if kind == "unchanged" and self.ingested > 13:   # set-up's fill
                return
            super().ingest_batch(names, batches, **kw)

        def windowed(self, name, q, *, window):
            answer = super().windowed(name, q, window=window)
            if kind == "altered":
                return np.nextafter(np.float32(answer), np.float32(np.inf))
            return answer
    return Faulty


@pytest.mark.parametrize("workload", [JOB, SERVICE])
def test_sound_run_is_correct(tiny_root, capsys, workload):
    rc, result, out, err = run_cell(tiny_root, workload, capsys)
    assert rc == 0 and result["correct"], out[-6:] + err[-4:]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert list(result)[-1] == "checks"
    assert err[-1].startswith("check ")
    # the job cell's set-up loads its program without running it, so the
    # window's first job traces it once more (about 50 ms at full size)
    traced = "[01]" if workload == JOB else "0"
    assert any(re.match(rf"window compiles: {traced} traced, 0 loaded, "
                        r"0 compiled", line) for line in out)


@pytest.mark.parametrize("fault", sorted(JOB_FAULTS))
def test_job_faults_are_not_correct(tiny_root, capsys, monkeypatch, fault):
    monkeypatch.setattr(repro.core, "gk_select", JOB_FAULTS[fault])
    rc, result, out, _ = run_cell(tiny_root, JOB, capsys)
    assert rc == 0 and result["correct"] is False, out[-6:]
    assert result["checks"]["wrong_answers"]["value"] > 0


@pytest.mark.parametrize("fault", ["altered", "half", "unchanged"])
def test_service_faults_are_not_correct(tiny_root, capsys, monkeypatch,
                                        fault):
    monkeypatch.setattr(repro.launch, "QuantileService", _service(fault))
    rc, result, out, _ = run_cell(tiny_root, SERVICE, capsys)
    assert rc == 0 and result["correct"] is False, out[-6:]
    assert result["checks"]["wrong_answers"]["value"] > 0
