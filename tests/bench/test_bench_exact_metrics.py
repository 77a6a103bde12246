"""The readers of the wide integer expressions and exact group sums
(``wide_share``, ``sum_limbs``), on synthetic contexts, and their silence
on a program that emits neither."""
from __future__ import annotations

import pytest

from bench_helpers import ROOT  # noqa: F401  (puts the repo on the path)
from bench import registry
from bench.run import Context
from bench.trace import TraceSummary

WINDOW = (10.0, 12.0)


def _ctx(spans, ops_ns=None, segment_ns=1000.0):
    trace = TraceSummary(window=(0.0, 2e9), busy_ns=[1.9e9],
                         ops_ns=dict(ops_ns or {}),
                         modules_ns={"jit__kernel": segment_ns,
                                     "jit_radix_groupby": 5.0})
    return Context(cell=None, setup_s=0.0, window=WINDOW, records=[],
                   window_compiles=0, trace=trace, spans=spans, peaks={},
                   shapes={}, dim_rows={})


def _scopes(ops):
    return {"ph": "i", "cat": "program", "name": "scopes", "ts": 10.5e6,
            "args": {"program": "jit__kernel", "layout": "8192:a",
                     "ops": ops}}


def _exact(rows, columns, limbs, ts=11e6):
    return {"ph": "C", "cat": "exact", "name": "groupby", "ts": ts,
            "args": {"rows": rows, "columns": columns, "limbs": limbs,
                     "max_bits": 37}}


def read(metric, ctx):
    return registry.reader(metric)(ctx)


OPS = {"%add_convert_fusion": "expr.charge/wide.charge",
       "%shift-left_or_fusion": "expr.charge/wide.charge",
       "%fusion.2": "wide.filter.0", "%compare_fusion": "filter.0",
       "%reshape": "unpack"}
OPS_NS = {"jit__kernel/%add_convert_fusion": 120.0,
          "jit__kernel/%shift-left_or_fusion": 80.0,
          "jit__kernel/%fusion.2": 50.0,
          "jit__kernel/%compare_fusion": 30.0,
          "jit__kernel/%reshape": 400.0,
          "jit_radix_groupby/%add_convert_fusion": 9.0}


def test_wide_share_sums_the_wide_ops_over_the_segment():
    assert read("wide_share.batch", _ctx([_scopes(OPS)], OPS_NS)) == \
        pytest.approx(25.0)


def test_wide_share_is_silent_without_wide_ops():
    narrow = {op: s for op, s in OPS.items() if "wide." not in s}
    assert read("wide_share.batch", _ctx([_scopes(narrow)], OPS_NS)) is None
    assert read("wide_share.batch", _ctx([], OPS_NS)) is None
    assert read("wide_share.batch", _ctx(None, OPS_NS)) is None


def test_sum_limbs_is_limbs_per_distinct_input_in_the_window():
    spans = [_exact(5_900_000, 5, 14), _exact(5_890_000, 5, 14),
             _exact(10, 1, 7, ts=9e6)]            # before the window
    assert read("sum_limbs.batch", _ctx(spans)) == pytest.approx(2.8)


def test_sum_limbs_is_silent_without_exact_sums():
    assert read("sum_limbs.batch", _ctx([])) is None
    assert read("sum_limbs.batch", _ctx(None)) is None
