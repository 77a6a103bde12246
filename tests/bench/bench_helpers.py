"""Shared set-up of the benchmark's tests: the repository root on the
import path, and cells cut to a size the CPU runs in a second."""
from __future__ import annotations

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import registry  # noqa: E402

CELLS = [w["name"] for w in registry.load_benchmark()["workloads"]]
#: cells whose files the benchmark keeps but whose BENCHMARK.json entries
#: wait for chip time (PERF.md, section 7): configuration file and traffic
PARKED = {"ssb_sf1_serve.t256k": ("bench/configs/ssb_sf1_serve.json",
                                  "t256k"),
          "ssb_sf1.q1.1": ("bench/configs/ssb_sf1.json", "q1.1")}
ALL_CELLS = CELLS + sorted(PARKED)

#: table sizes small enough for the CPU, with every flow still finding rows
TINY = {"lineorder_rows": 8192, "customer_rows": 600, "supplier_rows": 60,
        "part_rows": 800}
TINY_TICK_ROWS = 2048


def tiny_cell(name: str):
    """The cell ``name`` (of ``ALL_CELLS``) with its tables and ticks cut
    to ``TINY``; a parked cell reports ``setup_s`` alone."""
    if name in PARKED:
        config, traffic, flow = registry.parts(*PARKED[name])
        setup = [m for m in registry.load_benchmark()["end_to_end"]
                 if m["name"] == "setup_s"]
        cell = registry.Cell(name, 1, config, traffic, flow, setup, [])
    else:
        cell = registry.cell(name)
    cell.config.update(TINY)
    if "tick_rows" in cell.traffic:
        cell.traffic["tick_rows"] = TINY_TICK_ROWS
    return cell
