"""The four-chip job cell, ``gkselect-1e9-4chip.sharded-uniform-p99``, at
tiny size: whole runs through ``run.main(..., platform="cpu")`` on four
forced host devices, in a child process (the device count is fixed when
JAX starts); and its three readers on a hand-built four-chip trace.

Sound, the cell comes out correct, traced or not; with the answer altered
where the entry produces it, it does not.  The CPU trace holds no chip
plane, so the readers are checked against hand-worked values instead.
"""
import json
import os
import re
import subprocess
import sys
import textwrap

import pytest

from bench import run, sharded_program, trace
from conftest import ROOT, copy_benchmark

CELL = "gkselect-1e9-4chip.sharded-uniform-p99"
TINY = {"partitions": 8, "values_per_partition": 2048}


@pytest.fixture(scope="module")
def tiny4(tmp_path_factory):
    """A copy of the benchmark with the four-chip configuration at a tiny
    size, whose peak table knows the CPU."""
    root = copy_benchmark(tmp_path_factory.mktemp("bench4"))
    config = root / "bench" / "configs" / "gkselect-1e9-4chip.json"
    config.write_text(json.dumps(dict(json.loads(config.read_text()), **TINY)))
    peaks = root / "bench" / "peaks.json"
    table = json.loads(peaks.read_text())
    table["cpu"] = dict(table["TPU v5 lite"], source="test stand-in")
    peaks.write_text(json.dumps(table))
    return root


@pytest.fixture(scope="module")
def runs(tiny4):
    """Each run's (exit code, result line, stdout) by tag, and the sharded
    program's compiled text, from one child with four CPU devices."""
    hlo_file = tiny4 / "sharded.hlo"
    code = textwrap.dedent(f"""
        import os, sys
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        os.environ["JAX_PLATFORMS"] = "cpu"
        sys.path[:0] = [{str(ROOT)!r}, {str(ROOT / "src")!r}]
        from pathlib import Path
        import numpy as np
        import repro.core
        from repro.core import lowering
        from bench import run, sharded_program

        def cell(tag, trace):
            rc = run.main(["--workload", {CELL!r}, "--seed", "2147501777",
                           "--seconds", "1", "--trace", str(trace)],
                          root={str(tiny4)!r}, platform="cpu")
            print("RUN", tag, rc, flush=True)

        cell("sound", 0)
        cell("traced", 1)
        Path({str(hlo_file)!r}).write_text(sharded_program.sharded_hlo(
            run.load_cell(Path({str(tiny4)!r}), {CELL!r})))

        sound = repro.core.distributed_quantile

        def altered(x, q, mesh, **kw):
            answer = sound(x, q, mesh, **kw)
            if lowering.active():     # set-up loads the program it would run
                return answer
            return np.nextafter(np.float32(answer), np.float32(np.inf))

        repro.core.distributed_quantile = altered
        cell("altered", 0)
    """)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=900, cwd=str(ROOT),
                         env={k: v for k, v in os.environ.items()
                              if k != "XLA_FLAGS"})
    assert out.returncode == 0, out.stderr[-3000:]
    lines = out.stdout.splitlines()
    got = {}
    for i, line in enumerate(lines):
        if line.startswith("RUN "):
            _, tag, rc = line.split()
            got[tag] = (int(rc), json.loads(lines[i - 1]), lines[:i])
    return got, hlo_file.read_text()


@pytest.mark.parametrize("tag", ["sound", "traced"])
def test_sound_run_is_correct(runs, tag):
    rc, result, out = runs[0][tag]
    assert rc == 0 and result["correct"], out[-6:]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert result["device"]["count"] == 4
    assert result["checks"]["nothing_checked"]["value"] == 0
    if tag == "sound":
        assert set(result["metrics"]) == {"setup_s", "job_s"}
    else:
        assert "window_s" in result["device"]
    assert any(re.match(r"window compiles: [01] traced, 0 loaded, "
                        r"0 compiled", line) for line in out)


def test_an_altered_answer_is_not_correct(runs):
    rc, result, out = runs[0]["altered"]
    assert rc == 0 and result["correct"] is False, out[-6:]
    assert result["checks"]["wrong_answers"]["value"] > 0


def test_the_sharded_program_names_its_reduce(runs):
    """The program the readers read: its collectives found by opcode, each
    ppermute of the butterfly, and its merges, under ``phase_reduce``."""
    from bench import program_trace as pt
    hlo = runs[1]
    assert pt.module_name(hlo) == "jit_gk_select_sharded"
    op_scopes = pt.scopes(hlo)
    collective = _reader("collective_share.job4").COLLECTIVE
    found = {}
    for line in hlo.splitlines():
        m = pt.INSTRUCTION.match(line)
        c = m and collective.search(line.split(", metadata")[0])
        if c:
            found[m.group(1)] = c.group(1)
    assert sorted(found.values()) == ["all-gather", "all-gather",
                                      "all-reduce", "collective-permute",
                                      "collective-permute"]
    for name, opcode in found.items():
        if opcode == "collective-permute":
            assert pt.in_scope(op_scopes[name], "phase_reduce"), name
    assert any(pt.in_scope(p, "phase_reduce") and p.endswith("/top_k")
               for p in op_scopes.values())


# -- readers, on a hand-built trace of four chips -------------------------------

HLO4 = """HloModule jit_gk_select_sharded, entry_computation_layout={(f32[64]{0})->f32[]}

ENTRY %main.1 (p: f32[16]) -> f32[] {
  %sort.1 = f32[16]{0} sort(f32[16]{0} %p), metadata={op_name="jit(gk_select_sharded)/shard_map/phase_sketch/jit(sort)/sort"}
  %all-reduce.2 = f32[8]{0} all-reduce(f32[8]{0} %a), metadata={op_name="jit(gk_select_sharded)/shard_map/phase_sketch/all_gather"}
  %psum.3 = s32[3]{0} all-reduce(s32[3]{0} %c), metadata={op_name="jit(gk_select_sharded)/shard_map/phase_count/psum"}
  %top_k.4 = f32[16]{0} sort(f32[16]{0} %y), metadata={op_name="jit(gk_select_sharded)/shard_map/phase_extract/top_k"}
  %collective-permute-start = (f32[4]{0}, f32[4]{0}) collective-permute-start(f32[4]{0} %b), metadata={op_name="jit(gk_select_sharded)/shard_map/phase_reduce/ppermute"}
  %collective-permute-done = f32[4]{0} collective-permute-done((f32[4]{0}, f32[4]{0}) %collective-permute-start), metadata={op_name="jit(gk_select_sharded)/shard_map/phase_reduce/ppermute"}
  %top_k.7 = f32[8]{0} sort(f32[8]{0} %m), metadata={op_name="jit(gk_select_sharded)/shard_map/phase_reduce/top_k"}
  ROOT %while.8 = f32[] while(f32[4]{0} %d), metadata={op_name="jit(gk_select_sharded)/shard_map/phase_resolve/kth_bisect/while"}
}
"""
OPS4 = {name: line.strip().split(", metadata")[0]
        for line in HLO4.splitlines()
        if (m := re.match(r"\s*(?:ROOT )?%(\S+) = ", line))
        for name in [m.group(1)]}


def _chip(c):
    """One job [0, 10000) on chip c: a NaN-check program [500, 900), then
    the sharded program [1000, 8000 + 100c), whose ppermute waits 100c
    longer on chip c."""
    late = 100 * c
    ops = [("%fusion.9 = pred[] fusion(f32[16]{0} %x)", 500, 900),
           (OPS4["sort.1"], 1000, 3000), (OPS4["all-reduce.2"], 3000, 3200),
           (OPS4["psum.3"], 3200, 3300), (OPS4["top_k.4"], 3300, 6000),
           (OPS4["collective-permute-start"], 6000, 6100),
           (OPS4["collective-permute-done"], 6100, 6600 + late),
           (OPS4["top_k.7"], 6600 + late, 7600 + late),
           (OPS4["while.8"], 7600 + late, 8000 + late)]
    modules = [("jit_isnan(2)", 500, 900),
               ("jit_gk_select_sharded(1)", 1000, 8000 + late)]
    return ops, modules


@pytest.fixture
def reading(monkeypatch):
    monkeypatch.setattr(sharded_program, "sharded_hlo", lambda cell: HLO4)
    chips = {f"/device:TPU:{c}": _chip(c) for c in range(4)}
    tr = trace.Trace(window=(0, 20000),
                     ops={p: ops for p, (ops, _) in chips.items()},
                     modules={p: mods for p, (_, mods) in chips.items()},
                     spans=[("job", 0, 10000)])
    logged = []
    r = run.Reading(trace=tr, spans={}, cell=run.load_cell(ROOT, CELL),
                    peaks={"hbm_bytes_per_s": 819e9}, log=logged.append)
    return r, logged


def _reader(name):
    return run.load_module(ROOT / "bench" / "layer_metrics" / f"{name}.py")


NEW = ("reduce_share.job4", "collective_share.job4", "hbm_roofline.job4")


def test_readers_give_their_hand_worked_values(reading):
    # job device time on chip c: 400 + 7000 + 100c, 7550 on the average;
    # phase_reduce 100 + (500 + 100c) + 1000, 1750 on the average;
    # collectives 200 + 100 + 100 + (500 + 100c), 1050 on the average
    r, logged = reading
    got = {m: _reader(m).read(r) for m in NEW}
    least_s = 120 * 2 ** 23 * 4 / 4 / 819e9
    assert got == pytest.approx({"reduce_share.job4": 100 * 1750 / 7550,
                                 "collective_share.job4": 100 * 1050 / 7550,
                                 "hbm_roofline.job4": 100 * least_s / 7550e-9})
    [phases, matched] = logged
    assert "phase_reduce 23.1788" in phases and "other programs 5.2980" in phases
    assert matched == ("collective_share.job4 matched ['all-reduce.2', "
                       "'collective-permute-done', "
                       "'collective-permute-start', 'psum.3']")


def test_readers_give_nothing_without_a_trace(reading):
    r, _ = reading
    r.trace = None
    assert all(_reader(m).read(r) is None for m in NEW)
