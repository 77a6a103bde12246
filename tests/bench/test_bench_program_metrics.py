"""The readers that join the profiler's trace with the program's own trace
events (``probe_share``, ``probe_efficiency``, ``h2d_wait_share``), on
synthetic contexts."""
from __future__ import annotations

import pytest

from bench_helpers import ROOT  # noqa: F401  (puts the repo on the path)
from bench import registry
from bench.run import Context
from bench.trace import TraceSummary

#: host clock of the synthetic window, seconds
WINDOW = (10.0, 12.0)


def _ctx(spans, ops_ns=None, segment_ns=1000.0):
    trace = TraceSummary(window=(0.0, 2e9), busy_ns=[1.9e9],
                         ops_ns=dict(ops_ns or {}),
                         modules_ns={"jit__kernel": segment_ns,
                                     "jit_radix_groupby": 5.0})
    return Context(cell=None, setup_s=0.0, window=WINDOW, records=[],
                   window_compiles=0, trace=trace, spans=spans, peaks={},
                   shapes={}, dim_rows={})


def _scopes(ops, layout="8192:a"):
    return {"ph": "i", "cat": "program", "name": "scopes", "ts": 10.5e6,
            "args": {"program": "jit__kernel", "layout": layout,
                     "ops": ops}}


def _probe(dim, rows, padded, passes, need, ts=11e6):
    args = {"rows": rows, "padded_rows": padded, "passes": passes,
            "mean_probes": 1.25, "slots": 1024}
    if need is not None:
        args["need"] = need
    return {"ph": "C", "cat": "probe", "name": dim, "ts": ts, "args": args}


def _span(cat, name, t0, t1):
    return {"ph": "X", "cat": cat, "name": name, "ts": t0 * 1e6,
            "dur": (t1 - t0) * 1e6}


def read(metric, ctx):
    return registry.reader(metric)(ctx)


OPS = {"%while.12": "lookup.customer/probe", "%while.14": "lookup.part/probe",
       "%fusion.47": "lookup.part/gather", "%fusion.3": "filter.0"}
OPS_NS = {"jit__kernel/%while.12": 300.0, "jit__kernel/%while.14": 450.0,
          "jit__kernel/%fusion.52": 200.0,     # inside %while.14: no scope
          "jit__kernel/%fusion.47": 100.0, "jit__kernel/%fusion.3": 50.0,
          "jit_radix_groupby/%while.12": 5.0}


def test_probe_share_sums_the_probe_loops_over_the_segment():
    ctx = _ctx([_scopes(OPS), _scopes(OPS, layout="1024:a")], OPS_NS)
    assert read("probe_share.batch", ctx) == pytest.approx(75.0)


@pytest.mark.parametrize("spans", [
    [],                                                      # no events
    [_probe("part", 1, 1, 1, 1)],                            # no scopes
    [_scopes(OPS), _scopes(dict(OPS, **{"%while.14": "lookup.date/probe",
                                        "%while.12": "filter.0"}),
                           layout="1024:a")],                # layouts differ
])
def test_probe_share_is_none_without_one_answer(spans):
    assert read("probe_share.batch", _ctx(spans, OPS_NS)) is None


def test_probe_share_is_none_untraced():
    ctx = _ctx([_scopes(OPS)], OPS_NS)
    ctx.trace = None
    assert read("probe_share.batch", ctx) is None


def test_probe_efficiency_is_needed_over_run_iterations():
    spans = [_probe("part", 1000, 1024, 24, 1300),
             _probe("date", 1000, 1024, 12, 1200),
             _probe("customer", 1000, 1024, 26, None),      # key not counted
             _probe("part", 9, 16, 24, 12, ts=20e6),        # after the window
             _scopes(OPS)]
    assert read("probe_efficiency.batch", _ctx(spans)) == pytest.approx(
        100.0 * (1300 + 1200) / (1024 * 24 + 1024 * 12))


@pytest.mark.parametrize("spans", [
    None, [], [_scopes(OPS)],
    [_probe("part", 1000, 1024, 24, None)],                 # no counted need
])
def test_probe_efficiency_is_none_without_probe_counters(spans):
    assert read("probe_efficiency.batch", _ctx(spans)) is None


def test_h2d_wait_share_is_the_union_of_the_waits_in_the_window():
    spans = [_span("wait", "h2d.ready", 9.5, 10.5),          # clipped
             _span("wait", "h2d.ready", 11.0, 11.4),
             _span("wait", "h2d.ready", 11.2, 11.5),         # overlaps
             _span("transfer", "h2d", 10.0, 12.0),           # not a wait
             _span("wait", "d2h", 10.0, 12.0)]
    assert read("h2d_wait_share.batch", _ctx(spans)) == pytest.approx(
        100.0 * (0.5 + 0.5) / 2.0)


@pytest.mark.parametrize("spans", [None, [], [_span("transfer", "h2d",
                                                     10.0, 11.0)]])
def test_h2d_wait_share_is_none_without_waits(spans):
    assert read("h2d_wait_share.batch", _ctx(spans)) is None
