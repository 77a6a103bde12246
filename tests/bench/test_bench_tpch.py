"""The TPC-H cell: the generator keeps clause 4.2.3's rules, and the check
that decides ``correct`` fails the control and a float32 sum of the same
columns while it passes the exact integer sums."""
from __future__ import annotations

import numpy as np
import pytest

from bench_helpers import tiny_cell
from bench import check, control, loops, reference, registry
from bench.gen import tpch

CELL = "tpch_sf1.q1"


def _data(seed):
    cell = tiny_cell(CELL)
    return cell, registry.generator(cell.config["generator"])(cell.config,
                                                              seed)


def _days(yyyymmdd):
    s = np.asarray(yyyymmdd)
    return np.array([f"{d // 10000:04d}-{d // 100 % 100:02d}-{d % 100:02d}"
                     for d in s], dtype="datetime64[D]")


def test_generator_keeps_clause_4_2_3():
    cell, data = _data(2**31 + 3)
    cfg = cell.config
    orders = data.dims["orders"]
    n_orders = cfg["orders_per_customer"] * cfg["customer_rows"]
    assert len(orders["o_orderkey"]) == n_orders
    assert len(data.facts) == 2 and all(len(f) == 16 for f in data.facts)
    assert {len(t) for t in (data.dims["part"], orders)} == {9}
    assert [len(data.dims[t]) for t in ("customer", "supplier", "partsupp",
                                        "nation", "region")] == [8, 7, 5, 4, 3]
    # sparse keys: the first 8 of every 32
    assert np.isin((orders["o_orderkey"] - 1) % 32, np.arange(8)).all()
    assert (orders["o_custkey"] % 3 != 0).all()
    o_day = dict(zip(orders["o_orderkey"], _days(orders["o_orderdate"])))
    current = np.datetime64(cfg["current_date"])
    for li in data.facts:
        lines = np.unique(li["l_orderkey"], return_counts=True)[1]
        assert lines.min() >= 1 and lines.max() <= 7
        assert np.array_equal(li["l_orderkey"], data.facts[0]["l_orderkey"])
        pk = li["l_partkey"]
        assert np.array_equal(li["l_extendedprice"], li["l_quantity"] * (
            90_000 + (pk // 10) % 20_001 + 100 * (pk % 1_000)))
        assert li["l_quantity"].min() >= 1 and li["l_quantity"].max() <= 50
        assert li["l_discount"].max() <= 10 and li["l_tax"].max() <= 8
        order = np.array([o_day[k] for k in li["l_orderkey"]])
        ship, receipt = _days(li["l_shipdate"]), _days(li["l_receiptdate"])
        commit = _days(li["l_commitdate"])
        for got, lo, hi in [((ship - order).astype(int), 1, 121),
                            ((receipt - ship).astype(int), 1, 30),
                            ((commit - order).astype(int), 30, 90)]:
            assert got.min() >= lo and got.max() <= hi
        returned = receipt <= current
        assert np.isin(li["l_returnflag"][returned],
                       [tpch.FLAG_A, tpch.FLAG_R]).all()
        assert (li["l_returnflag"][~returned] == tpch.FLAG_N).all()
        assert np.array_equal(li["l_linestatus"] == tpch.STATUS_O,
                              ship > current)
    # o_totalprice sums the first table's lines, rounded to cents
    first = data.facts[0]
    total = {}
    for k, e, t, d in zip(first["l_orderkey"], first["l_extendedprice"],
                          first["l_tax"], first["l_discount"]):
        total[k] = total.get(k, 0) + (e * (100 + t) * (100 - d) + 5000) \
            // 10000
    assert [total[k] for k in orders["o_orderkey"]] == \
        orders["o_totalprice"].tolist()
    ps = data.dims["partsupp"]
    assert np.array_equal(ps["ps_suppkey"], tpch.supplier_of(
        ps["ps_partkey"], np.tile(np.arange(4), cfg["part_rows"]),
        cfg["supplier_rows"]))


def test_generator_is_seeded():
    _, a = _data(7)
    _, b = _data(7)
    _, c = _data(8)
    for x, y in zip(a.facts, b.facts):
        assert all(np.array_equal(x[k], y[k]) for k in x)
    assert not np.array_equal(a.facts[0]["l_partkey"],
                              c.facts[0]["l_partkey"])
    assert not np.array_equal(a.facts[0]["l_partkey"],
                              a.facts[1]["l_partkey"])


def _exact_and_float32(cell, data, fact):
    """The flow's sink table with every sum exact in int64, and with every
    sum taken as today's float32 route does: values rounded to float32,
    summed per 512-row tile and the tiles added one by one in float32."""
    flow = cell.flow
    agg = next(s for s in flow["steps"] if "aggregate" in s)
    cols = dict(data.facts[fact])
    keep = reference.evaluate_expr(flow["steps"][0]["filter"], cols)
    for step in flow["steps"][1:3]:
        cols[step["derive"]] = reference.evaluate_expr(step["expr"], cols)
    cols = {k: v[keep] for k, v in cols.items()}
    gid = cols["l_returnflag"] * 2 + cols["l_linestatus"]
    groups = np.unique(gid)
    exact = {"l_returnflag": groups // 2, "l_linestatus": groups % 2}
    f32 = dict(exact)
    counts = np.array([(gid == g).sum() for g in groups])
    for out, (src, op) in agg["aggs"].items():
        if op == "count":
            exact[out] = f32[out] = counts
            continue
        s = np.array([cols[src][gid == g].sum() for g in groups])
        t = []
        for g in groups:
            v = cols[src][gid == g].astype(np.float32)
            tiles = np.add.reduceat(v, np.arange(0, len(v), 512))
            t.append(np.cumsum(tiles, dtype=np.float32)[-1])
        t = np.array(t, dtype=np.float64)
        exact[out] = s / counts if op == "avg" else s
        f32[out] = t / counts if op == "avg" else t
    return exact, f32


@pytest.mark.parametrize("seed", [3, 2**31 + 9])
def test_limit_passes_exact_sums_and_fails_float32_and_the_control(seed):
    cell, data = _data(seed)
    limits = cell.config["limits"]
    feed = loops.feed(cell.config, cell.traffic, cell.flow, data)
    expected = feed.reference(2)
    exact, f32 = _exact_and_float32(cell, data, 0)
    sound = check.worst(feed.compare([(0, exact)], expected), 0)
    assert check.passed(check.verdict(sound, limits)), sound
    rounded = check.worst(feed.compare([(0, f32)], expected), 0)
    assert not check.passed(check.verdict(rounded, limits)), rounded
    _, ctrl = control.readings(cell, seed)
    assert not check.passed(check.verdict(ctrl, limits)), ctrl
