"""The benchmark finds each part of a cell by name, a cell is added by
adding files alone, and BENCHMARK.json keeps to the benchmark contract."""
from __future__ import annotations

import json
import re
import shutil

import pytest

from bench_helpers import CELLS, ROOT
from bench import registry

SPEC = registry.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
METRICS = SPEC["end_to_end"] + SPEC["per_layer"]
SOURCES_E2E = {"host_clock", "device_trace"}
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _line(text: str) -> bool:
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


@pytest.mark.parametrize("name", CELLS)
def test_every_cell_resolves_by_name(name):
    cell = registry.cell(name)
    assert cell.flow["steps"]
    assert registry.generator(cell.config["generator"])
    for metric in cell.end_to_end + cell.per_layer:
        assert callable(registry.reader(metric["name"]))
    names = [m["name"] for m in cell.end_to_end]
    assert "setup_s" in names and len(names) >= 2
    assert cell.per_layer


def test_a_cell_is_added_by_files_alone(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench")
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    before = {p.relative_to(tmp_path): p.read_bytes()
              for p in (tmp_path / "bench").rglob("*") if p.is_file()}
    # a new flow, traffic mix and per-layer metric, each in a file of its own
    flow = json.loads((ROOT / "bench/flows/ssb_q1.1.json").read_text())
    flow["name"] = "ssb-q1.1-1994"
    flow["steps"][1]["filter"] = flow["steps"][1]["filter"].replace(
        "1993", "1994")
    (tmp_path / "bench/flows/ssb_q1.1_1994.json").write_text(
        json.dumps(flow))
    (tmp_path / "bench/traffic/q1.1_1994.json").write_text(json.dumps(
        {"flow": "ssb_q1.1_1994", "loop": "closed",
         "warm_up": "all"}))
    (tmp_path / "bench/metrics/runs_in_window.py").write_text(
        "def read(ctx):\n    return float(len(ctx.records))\n")
    spec["workloads"].append({"name": "ssb_sf1.q1.1_1994",
                              "config": "ssb_sf1", "traffic": "q1.1_1994",
                              "chips": 1, "why": "a test cell"})
    spec["per_layer"].append({"name": "runs_in_window.batch", "unit": "count",
                              "better": "higher", "source": "host_clock",
                              "layer": "Device", "moves": "batch_rows_per_s",
                              "workloads": ["ssb_sf1.q1.1_1994"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = registry.cell("ssb_sf1.q1.1_1994", root=tmp_path)
    assert cell.flow["name"] == "ssb-q1.1-1994"
    assert [m["name"] for m in cell.per_layer] == ["runs_in_window.batch"]
    read = registry.reader("runs_in_window.batch", root=tmp_path)
    assert read(type("Ctx", (), {"records": [1, 2]})()) == 2.0
    after = {p.relative_to(tmp_path): p.read_bytes()
             for p in (tmp_path / "bench").rglob("*") if p.is_file()}
    assert all(after[p] == b for p, b in before.items())


def test_unknown_workload_is_an_error():
    with pytest.raises(KeyError):
        registry.cell("no_such.cell")


@pytest.mark.parametrize("metric", METRICS, ids=[m["name"] for m in METRICS])
def test_metric_names_units_and_keys(metric):
    assert NAME.match(metric["name"])
    assert UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES
    if metric in SPEC["end_to_end"]:
        assert metric["source"] in SOURCES_E2E
        assert 0 < metric["bound"] <= 0.25
        assert set(metric) <= {"name", "unit", "better", "bound", "source",
                               "workloads"}
    else:
        assert _line(metric["layer"])
        assert metric["moves"] in [m["name"] for m in SPEC["end_to_end"]]
        assert set(metric) <= {"name", "unit", "better", "source", "layer",
                               "moves", "workloads"}
        for cell in metric.get("workloads", []):
            moves = next(m for m in SPEC["end_to_end"]
                         if m["name"] == metric["moves"])
            assert cell in moves.get("workloads", CELLS)


@pytest.mark.parametrize("entry", SPEC["workloads"] + SPEC["configs"],
                         ids=[e["name"] for e in SPEC["workloads"]
                              + SPEC["configs"]])
def test_cell_and_config_entries(entry):
    assert NAME.match(entry["name"])
    assert _line(entry["why"])
    if "traffic" in entry:
        assert set(entry) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(entry["traffic"]) and NAME.match(entry["config"])
        assert entry["chips"] in (1, 4)
    else:
        assert set(entry) == {"name", "source", "file", "reduced", "why"}
        assert _line(entry["source"])
        assert entry["file"].startswith("bench/configs/")
        assert len(entry["reduced"]) <= 16
        assert all(NAME.match(k) for k in entry["reduced"])


def test_benchmark_top_level():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(SPEC["paths"]) <= 16
    assert all(PATH.match(p) and not p.startswith("/") and ".." not in p
               for p in SPEC["paths"])
    assert all(_line(w) for w in SPEC["command"]) and len(SPEC["command"]) <= 32
    assert any(w.startswith(SPEC["paths"][0]) for w in SPEC["command"])
    assert isinstance(SPEC["run_seconds"], int)
    assert 1 <= SPEC["run_seconds"] <= 51
    # a check of 24 cells, 14 runs each, fits in 12 hours
    assert (2 + 14 * 24) * (SPEC["run_seconds"] + 60) + 24 * 180 + 1200 \
        <= 43200
    pairs = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert len(pairs) == len(set(pairs))
    names = [e["name"] for e in METRICS + SPEC["workloads"] + SPEC["configs"]]
    assert len(names) == len(set(names))
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024
    four = sum(w["chips"] == 4 for w in SPEC["workloads"])
    assert four <= max(1, len(SPEC["workloads"]) // 2)
    used = {w["config"] for w in SPEC["workloads"]}
    assert used == {c["name"] for c in SPEC["configs"]}
    layers = {}
    for m in SPEC["per_layer"]:
        layers.setdefault(m["name"].split(".")[0], set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values())
