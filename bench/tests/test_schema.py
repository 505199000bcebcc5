"""``BENCHMARK.json`` and the files it names, against the benchmark's
contract; and a new cell, traffic mix and metric found by name alone."""
import hashlib
import json
import math
import re
from pathlib import Path

import pytest

from bench import run
from conftest import ROOT, SERVICE, run_cell

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
# the keys each entry may have ("workloads" only on metrics)
KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


def _line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert len(json.dumps(BENCH)) < 64 * 1024
    assert 1 <= len(BENCH["paths"]) <= 16
    for path in BENCH["paths"]:
        assert PATH.match(path) and ".." not in path.split("/")
        assert not path.startswith("/")
    command = BENCH["command"]
    assert 1 <= len(command) <= 32 and all(_line(w) for w in command)
    for word in command[1:]:
        assert not word.startswith("/") and ".." not in word.split("/")
        if "/" in word:
            assert any(word.startswith(p + "/") for p in BENCH["paths"])


def test_run_seconds_fit_a_check_with_24_cells():
    seconds = BENCH["run_seconds"]
    assert isinstance(seconds, int) and 1 <= seconds <= 51
    runs = 2 + 14 * 24
    assert runs * (seconds + 60) + 24 * 2 * 90 + 1200 <= 43200


@pytest.mark.parametrize("section", sorted(KEYS))
def test_entries(section):
    entries = BENCH[section]
    assert entries
    names = [e["name"] for e in entries]
    assert len(set(names)) == len(names)
    for e in entries:
        extra = {"workloads"} if section in ("end_to_end", "per_layer") else set()
        assert KEYS[section] <= set(e) <= KEYS[section] | extra, e["name"]
        assert NAME.match(e["name"]), e["name"]
        if "unit" in e:
            assert UNIT.match(e["unit"]) and e["better"] in ("lower", "higher")
            assert e["source"] in SOURCES
        for key in ("why", "layer", "source"):
            if key in e:
                assert _line(e[key]), (e["name"], key)


def test_configs():
    used = {w["config"] for w in BENCH["workloads"]}
    files = [c["file"] for c in BENCH["configs"]]
    assert len(set(files)) == len(files)
    for c in BENCH["configs"]:
        assert c["name"] in used
        assert any(c["file"].startswith(p + "/") for p in BENCH["paths"])
        data = json.loads((ROOT / c["file"]).read_text())
        assert data["source"] == c["source"]
        assert data["reduced"] == c["reduced"] and len(c["reduced"]) <= 16
        for key in c["reduced"]:
            assert NAME.match(key)
            assert not re.search(r"(_dim|_rank|size|width)$", key)
        for key in ("assumed", "guarantees", "chips"):
            assert key in data, (c["name"], key)


def test_workloads():
    configs = {c["name"] for c in BENCH["configs"]}
    cells = BENCH["workloads"]
    assert 1 <= len(cells) <= 24
    pairs = {(w["config"], w["traffic"]) for w in cells}
    assert len(pairs) == len(cells)
    four = sum(w["chips"] == 4 for w in cells)
    assert four <= max(1, len(cells) // 2)
    for w in cells:
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert NAME.match(w["traffic"])
        traffic = ROOT / "bench" / "traffic" / f"{w['traffic']}.json"
        driver = json.loads(traffic.read_text())["driver"]
        assert (ROOT / "bench" / "drivers" / f"{driver}.py").is_file()


def test_end_to_end_bounds():
    names = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in names and len(names) <= 16
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25


def test_every_cell_reports_what_it_must():
    for w in BENCH["workloads"]:
        cell = run.load_cell(ROOT, w["name"])
        e2e = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in e2e and len(e2e) >= 2, w["name"]
        assert cell.per_layer, w["name"]


def test_per_layer_metrics_move_what_their_cells_report():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    cells = [w["name"] for w in BENCH["workloads"]]
    layers = {}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        for cell in m.get("workloads", cells):
            assert cell in cells
            assert cell in e2e[m["moves"]].get("workloads", cells), \
                (m["name"], cell)
        reader = ROOT / "bench" / "layer_metrics" / f"{m['name']}.py"
        assert reader.is_file(), reader
        layers.setdefault(m["layer"].lower(), set()).add(m["layer"])
        if m["unit"] == "%" and "roofline" in m["name"]:
            assert m["better"] == "higher"
    # one spelling per layer
    assert all(len(spellings) == 1 for spellings in layers.values())


def _digest(root: Path) -> dict:
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file()}


def test_a_new_cell_is_found_by_name_alone(tiny_root, capsys):
    """A new configuration, traffic mix and per-layer metric, added as new
    files plus new entries in ``BENCHMARK.json``, run without an edit to
    any file the benchmark already has."""
    bench_dir = tiny_root / "bench"
    before = _digest(bench_dir)
    config = json.loads((bench_dir / "configs" / "summary-s2048-w10m.json")
                        .read_text())
    config["series"] = 24
    (bench_dir / "configs" / "summary-dummy.json").write_text(
        json.dumps(config))
    traffic = json.loads((bench_dir / "traffic" / "dashboard.json")
                         .read_text())
    traffic["quantiles"] = [0.75]
    (bench_dir / "traffic" / "dummy-mix.json").write_text(json.dumps(traffic))
    (bench_dir / "layer_metrics" / "queries_traced.dummy.py").write_text(
        "def read(r):\n    return float(len(r.spans.get('query', [])))\n")
    bench = json.loads((tiny_root / "BENCHMARK.json").read_text())
    bench["configs"].append(dict(bench["configs"][1], name="summary-dummy",
                                 file="bench/configs/summary-dummy.json"))
    cell = "summary-dummy.dummy-mix"
    bench["workloads"].append({"name": cell, "config": "summary-dummy",
                               "traffic": "dummy-mix", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if SERVICE in m.get("workloads", []):
            m["workloads"].append(cell)
    bench["per_layer"].append({"name": "queries_traced.dummy", "unit": "1",
                               "better": "higher", "source": "host_clock",
                               "layer": "service host path",
                               "moves": "query_p50_ms", "workloads": [cell]})
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(bench))

    rc, result, out, _ = run_cell(tiny_root, cell, capsys, trace=1)
    assert rc == 0 and result["correct"], out[-6:]
    assert result["metrics"]["queries_traced.dummy"]["value"] > 0
    assert "tick_host_ms.service" in result["metrics"]
    after = _digest(bench_dir)
    assert {k: after[k] for k in before} == before
    assert math.isfinite(result["device"]["window_s"])
