"""The readers that join the profiler's trace with the program's own
events, on a small recording made on the chip: SSB Q4.1 at 65,536
lineorder rows, two runs inside the ``bench.window`` annotation with a
``repro.obs`` tracer in scope, on one TPU v5 lite
(``bench/record_program_trace.py``)."""
from __future__ import annotations

import json
from pathlib import Path

import pytest

from bench_helpers import ROOT
from bench import registry
from bench.run import Context
from bench.trace import reduce_xplane

BASE = ROOT / "bench/testdata/q4.1_65k_program"
#: each Lookup's probe loop (the benchmark's tables are unnamed, so a
#: Lookup takes its fact key column's name)
LOOKUPS = {"lo_custkey": "%while.12", "lo_suppkey": "%while.13",
           "lo_partkey": "%while.14", "lo_orderdate": "%while.15"}


@pytest.fixture(scope="module")
def ctx():
    program = json.loads(Path(f"{BASE}.events.json").read_text())
    summary = reduce_xplane(f"{BASE}.xplane.pb.gz", devices=1)
    return Context(cell=registry.cell("ssb_sf1.q4.1"), setup_s=0.0,
                   window=tuple(program["window"]), records=[],
                   window_compiles=0, trace=summary,
                   spans=program["events"], peaks={}, shapes={},
                   dim_rows={})


@pytest.mark.parametrize("metric,value", [
    ("probe_share.batch", 94.39125118751811),
    ("probe_efficiency.batch", 6.417928423200335),
    ("h2d_wait_share.batch", 12.568729941228192),
])
def test_readers_find_their_events(ctx, metric, value):
    assert registry.reader(metric)(ctx) == pytest.approx(value, rel=1e-12)


def test_each_lookup_maps_to_one_probe_loop_with_device_time(ctx):
    scopes = [e["args"] for e in ctx.spans if e["name"] == "scopes"]
    assert len(scopes) == 16                         # 2 runs of 8 chunks
    assert all(a["program"] == "jit__kernel" for a in scopes)
    ops = scopes[0]["ops"]
    assert all(a["ops"] == ops for a in scopes)      # one layout
    loops = {}
    for op, scope in ops.items():
        if op.startswith("%while"):
            loops.setdefault(scope, []).append(op)
    assert loops == {f"lookup.{k}/probe": [op] for k, op in LOOKUPS.items()}
    for op in LOOKUPS.values():
        assert ctx.trace.ops_ns[f"jit__kernel/{op}"] > 0


def test_probe_counters_of_each_lookup(ctx):
    passes = {e["name"]: e["args"]["passes"] for e in ctx.spans
              if e["cat"] == "probe"}
    assert passes == {"lo_custkey": 26, "lo_suppkey": 22, "lo_partkey": 24,
                      "lo_orderdate": 12}


def test_idle_gaps_are_named_by_the_programs_spans(ctx):
    """Every one of the longest gaps is named by one of the program's own
    profiler-clock spans, and none by the runtime's ``np.asarray`` or
    ``XlaLinearize`` events under them (those lie inside the program's
    ``repro.transfer.d2h`` and ``h2d`` spans)."""
    names = [n for n, _ in ctx.trace.gaps]
    assert len(names) == 10
    assert all(n.startswith("bench.run > repro.") for n in names), names
    assert not [n for n in names
                if "np.asarray" in n or "XlaLinearize" in n]


def test_probe_counters_count_the_passes_rows_need(ctx):
    """Each Lookup's ``need`` lies between one pass a row and the loop's
    passes a row, and near the table's mean probe length (uniform keys)."""
    for e in ctx.spans:
        if e["cat"] != "probe":
            continue
        a = e["args"]
        assert a["rows"] <= a["need"] <= a["rows"] * a["passes"]
        assert a["need"] / a["rows"] == pytest.approx(a["mean_probes"],
                                                      rel=0.05)
