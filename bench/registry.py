"""Find each part of a cell by its name: the cell and its metrics in
``BENCHMARK.json``, its configuration in the file that names, its traffic
mix in ``bench/traffic/<name>.json``, the flow that mix drives in
``bench/flows/<name>.json``, the data generator in ``bench/gen/<name>.py``
and each metric's reader in ``bench/metrics/<name>.py`` (or, for a split
metric such as ``device_idle.batch``, ``bench/metrics/device_idle.py``).

A cell, a mix, a flow or a metric is added by adding files and entries;
no file here names one.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from typing import Callable, Dict, List, Tuple

ROOT = Path(__file__).resolve().parent.parent


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    flow: dict
    #: BENCHMARK.json entries of the metrics this cell reports
    end_to_end: List[dict]
    per_layer: List[dict]


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _json(path: Path) -> dict:
    return json.loads(path.read_text())


def reports(metric: dict, cell: str, end_to_end_names: List[str]) -> bool:
    """Whether ``cell`` reports ``metric``: it is listed, or the metric
    lists no cells and the cell reports what the metric moves."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    moves = metric.get("moves")
    return moves is None or moves in end_to_end_names


def cell(name: str, root: Path = ROOT) -> Cell:
    spec = load_benchmark(root)
    entry = next((w for w in spec["workloads"] if w["name"] == name), None)
    if entry is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{[w['name'] for w in spec['workloads']]}")
    conf = next(c for c in spec["configs"] if c["name"] == entry["config"])
    config, traffic, flow = parts(conf["file"], entry["traffic"], root)
    e2e = [m for m in spec["end_to_end"] if reports(m, name, [])]
    e2e_names = [m["name"] for m in e2e]
    per_layer = [m for m in spec["per_layer"]
                 if reports(m, name, e2e_names)]
    return Cell(name=name, chips=int(entry["chips"]), config=config,
                traffic=traffic, flow=flow, end_to_end=e2e,
                per_layer=per_layer)


def parts(config_file: str, traffic: str, root: Path = ROOT
          ) -> Tuple[dict, dict, dict]:
    """A configuration file, the traffic mix ``traffic`` and the flow that
    mix drives."""
    config = _json(root / config_file)
    mix = _json(root / "bench" / "traffic" / f"{traffic}.json")
    flow = _json(root / "bench" / "flows" / f"{mix['flow']}.json")
    return config, mix, flow


def generator(name: str) -> Callable:
    """``generate(config, seed)`` of ``bench/gen/<name>.py``."""
    return importlib.import_module(f"bench.gen.{name}").generate


def reader(metric: str, root: Path = ROOT) -> Callable:
    """``read(ctx)`` of the metric's reader file."""
    base = root / "bench" / "metrics"
    for stem in (metric, metric.split(".")[0]):
        path = base / f"{stem}.py"
        if path.is_file():
            spec = importlib.util.spec_from_file_location(
                f"bench_metric_{stem.replace('.', '_')}", path)
            module = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(module)
            return module.read
    raise FileNotFoundError(f"no reader for metric {metric!r} under {base}")


def readers(metrics: List[dict], root: Path = ROOT) -> Dict[str, Callable]:
    return {m["name"]: reader(m["name"], root) for m in metrics}
