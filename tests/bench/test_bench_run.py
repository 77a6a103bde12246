"""The harness's entry point refuses to measure off a TPU, and the pieces
it is built from: generator, expressions, peaks and byte counts."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from bench_helpers import ROOT, TINY
from bench import costs, exprs, peaks, reference, registry

ARGS = ["--workload", "ssb_sf1.q4.1", "--seed", "4294967311", "--seconds",
        "1", "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=120)


def test_run_refuses_a_machine_without_a_tpu():
    out = _run(ROOT)
    assert out.returncode != 0
    assert "{" not in out.stdout
    assert "TPU" in out.stderr


def test_run_fails_with_only_the_benchmark_files(tmp_path):
    spec = registry.load_benchmark()
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for p in spec["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p)
    out = _run(tmp_path)
    assert out.returncode != 0
    assert "{" not in out.stdout


def test_peaks_are_keyed_by_device_kind():
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    assert peaks.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        peaks.peaks("TPU v4")


def _tables(seed):
    cfg = dict(registry.cell("ssb_sf1.q4.1").config, **TINY)
    return registry.generator("ssb")(cfg, seed)


def test_generator_is_seeded_and_keeps_the_schema():
    a, b, c = _tables(2**31 + 5), _tables(2**31 + 5), _tables(6)
    assert len(a.facts) == 2 and len(a.facts[0]) == 17
    for t in ("customer", "supplier", "part", "date"):
        assert a.dims[t].keys() == b.dims[t].keys()
    assert [len(v) for v in a.dims["customer"].values()] == [600] * 8
    assert len(a.dims["part"]) == 9 and len(a.dims["date"]) == 17
    assert len(a.dims["date"]["d_datekey"]) == 2557      # 1992..1998
    for x, y in zip(a.facts, b.facts):
        assert all(np.array_equal(x[k], y[k]) for k in x)
    assert not np.array_equal(a.facts[0]["lo_custkey"],
                              c.facts[0]["lo_custkey"])
    lo = a.facts[0]
    assert len(lo["lo_orderkey"]) == TINY["lineorder_rows"]
    assert lo["lo_custkey"].min() >= 1
    assert lo["lo_custkey"].max() <= TINY["customer_rows"]
    assert np.isin(lo["lo_orderdate"], a.dims["date"]["d_datekey"]).all()
    assert (lo["lo_linenumber"] >= 1).all() and (lo["lo_linenumber"] <= 7).all()


def test_expressions_parse_and_list_their_columns():
    tree = exprs.parse("d_ok & (d_year == 1993) & between(lo_discount, 1, 3)")
    assert exprs.columns(tree) == {"d_ok", "d_year", "lo_discount"}
    cols = {"d_ok": np.array([True, True, False]),
            "d_year": np.array([1993, 1994, 1993]),
            "lo_discount": np.array([2, 2, 2])}
    assert reference.evaluate_expr(
        "d_ok & (d_year == 1993) & between(lo_discount, 1, 3)",
        cols).tolist() == [True, False, False]
    with pytest.raises(ValueError):
        exprs.parse("d_year ** 2")


def test_bfloat16_rounds_to_nearest_even():
    got = reference.to_bfloat16(np.array([1.0, 257.0, 259.0, 3_000_001.0]))
    assert got.tolist() == [1.0, 256.0, 260.0, 2_998_272.0]


def test_byte_counts_of_q4_1():
    flow = json.loads((ROOT / "bench/flows/ssb_q4.1.json").read_text())
    dims = {"customer": 30_000, "supplier": 2_000, "part": 200_000,
            "date": 2_557}
    # 6 fact columns read, 4 Lookups of one key and one payload, 3 columns
    # and the keep-mask handed to the aggregate; each dimension read once
    per_row = 6 * 4 + 4 * (4 + 4) + 3 * 4 + 1
    once = 4 * 2 * sum(dims.values())
    assert costs.segment_bytes(flow, 1000, dims) == 1000 * per_row + once
    assert costs.groupby_bytes(flow, 500, 35) == 500 * 8 + 35 * 4 * 2
