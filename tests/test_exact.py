"""Exact integer arithmetic on the jax backend: wide expressions past int32
in the fused segment and unfused, exact integer group sums on the dense,
keyless and sort routes, and the numpy backend's int64 sums, each compared
with numpy int64 by ``==``."""
from __future__ import annotations

import numpy as np
import pytest

import repro
from repro import col
from repro.core import wideint
from repro.core.backend import get_backend

jax = pytest.importorskip("jax")
jnp = jax.numpy

#: TPC-H Q1's aggregates over (returnflag, linestatus)
Q1_AGGS = {"sum_qty": ("l_quantity", "sum"),
           "sum_base_price": ("l_extendedprice", "sum"),
           "sum_disc_price": ("disc_price", "sum"),
           "sum_charge": ("charge", "sum"),
           "avg_qty": ("l_quantity", "avg"),
           "avg_price": ("l_extendedprice", "avg"),
           "avg_disc": ("l_discount", "avg"),
           "count_order": ("l_quantity", "count")}
DISC_PRICE = col("l_extendedprice") * (100 - col("l_discount"))
CHARGE = DISC_PRICE * (100 + col("l_tax"))


@pytest.fixture(scope="module")
def data():
    from repro.etl.ssb import generate
    return generate(lineorder_rows=5000, customers=300, suppliers=50,
                    parts=400, seed=3)


def _lineitem(n: int, seed: int, price_lo: int = 90_000,
              price_hi: int = 209_900) -> dict:
    rng = np.random.default_rng(seed)
    qty = rng.integers(1, 51, n)
    return {"l_quantity": qty,
            "l_extendedprice": qty * rng.integers(price_lo, price_hi + 1, n),
            "l_discount": rng.integers(0, 11, n),
            "l_tax": rng.integers(0, 9, n),
            "l_returnflag": rng.integers(0, 3, n),
            "l_linestatus": rng.integers(0, 2, n),
            "l_shipdate": rng.integers(19920102, 19981201, n)}


def _q1_flow(table: dict):
    return (repro.flow("q1").source(table)
            .filter(col("l_shipdate") <= 19980902)
            .derive("disc_price", DISC_PRICE)
            .derive("charge", CHARGE)
            .aggregate(["l_returnflag", "l_linestatus"], Q1_AGGS)
            .sort(["l_returnflag", "l_linestatus"]).sink())


def _q1_numpy(t: dict) -> dict:
    """Q1 in plain numpy int64: per group, the counts and the exact sum of
    each sum's and each average's input."""
    keep = t["l_shipdate"] <= 19980902
    c = {k: v[keep] for k, v in t.items()}
    c["disc_price"] = c["l_extendedprice"] * (100 - c["l_discount"])
    c["charge"] = c["disc_price"] * (100 + c["l_tax"])
    gid = c["l_returnflag"] * 2 + c["l_linestatus"]
    groups = np.unique(gid)
    out = {"l_returnflag": groups // 2, "l_linestatus": groups % 2}
    for name, (src, op) in Q1_AGGS.items():
        if op == "count":
            out[name] = np.array([(gid == g).sum() for g in groups])
        else:
            out[name] = np.array([c[src][gid == g].sum() for g in groups],
                                 dtype=np.int64)
    return out


def _exact_equal(got: dict, want: dict) -> None:
    """Every sum and count equal as integers, every average the exact sum
    over the count in float64."""
    for name, w in want.items():
        g = np.asarray(got[name])
        if Q1_AGGS.get(name, ("", ""))[1] == "avg":
            assert np.array_equal(g, w / want["count_order"]), name
        else:
            assert np.array_equal(g.astype(np.int64), w), (name, g, w)


# ---------------------------------------------------------------------------
#  The wide form
# ---------------------------------------------------------------------------
def _host(w: wideint.Wide) -> np.ndarray:
    return (np.asarray(w.hi).astype(np.int64) << 32) | \
        np.asarray(w.lo).astype(np.int64)


def test_wide_arithmetic_is_int64_arithmetic():
    rng = np.random.default_rng(0)
    a = rng.integers(-2**31, 2**31, 4096)
    b = rng.integers(-2**31, 2**31, 4096)
    wa, wb = (wideint.widen(jnp.asarray(x, jnp.int32)) for x in (a, b))
    prod = wideint.mul(wa, wb)
    assert np.array_equal(_host(prod), a * b)
    assert np.array_equal(_host(wideint.add(wa, wb)), a + b)
    assert np.array_equal(_host(wideint.sub(prod, wideint.mul(wb, wb))),
                          a * b - b * b)
    assert np.array_equal(_host(wideint.neg(prod)), -(a * b))
    assert np.array_equal(_host(wideint.mul(wa, wideint.const(-12345678901))),
                          a * -12345678901)
    for op, fn in [("eq", np.equal), ("ne", np.not_equal), ("lt", np.less),
                   ("le", np.less_equal), ("gt", np.greater),
                   ("ge", np.greater_equal)]:
        got = np.asarray(wideint.compare(op, prod, wideint.mul(wb, wb)))
        assert np.array_equal(got, fn(a * b, b * b)), op
    limbs = wideint.limbs(prod, wideint.const(int((a * b).min())), 8)
    back = sum(np.asarray(l).astype(np.int64) << (8 * k)
               for k, l in enumerate(limbs))
    assert np.array_equal(back + (a * b).min(), a * b)


def test_plan_marks_only_the_nodes_past_int32():
    ranges = {"l_extendedprice": (90_000, 10_495_000), "l_discount": (0, 10),
              "l_tax": (0, 8)}
    assert wideint.plan(DISC_PRICE, ranges).flags is None
    p = wideint.plan(CHARGE, ranges)
    assert p.bound == (90_000 * 90 * 100, 10_495_000 * 100 * 108)
    assert p.flags[0] and not any(p.flags[1:])    # only the root product
    assert p.bits == 38
    # a float column or a division makes no integer bound
    assert wideint.plan(col("x") / 2, {"x": (0, 1)}).bound is None
    assert wideint.plan(col("x") * col("y"), {}).flags is None
    with pytest.raises(wideint.IntRangeError):
        wideint.plan(col("x") * col("x") * col("x"), {"x": (0, 1 << 30)})


def test_a_wide_division_raises_rather_than_wrap():
    p = wideint.plan(CHARGE // 7, {"l_extendedprice": (0, 10**7),
                                   "l_discount": (0, 10), "l_tax": (0, 8)})
    cols = {"l_extendedprice": jnp.asarray([10**7], jnp.int32),
            "l_discount": jnp.asarray([0], jnp.int32),
            "l_tax": jnp.asarray([8], jnp.int32)}
    from repro.core.expr import ColumnsView
    with pytest.raises(NotImplementedError):
        wideint.evaluate(CHARGE // 7, ColumnsView(cols), slice(None),
                         p.flags)


# ---------------------------------------------------------------------------
#  TPC-H Q1 through Session.run
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("backend,fuse", [("jax", True), ("jax", False),
                                          ("numpy", False)])
def test_q1_sums_are_exact(backend, fuse):
    table = _lineitem(30_000, seed=1)
    res = repro.Session(backend=backend, metadata=None).run(
        _q1_flow(table), engine="streaming", fuse=fuse)
    _exact_equal(res.table, _q1_numpy(table))


@pytest.mark.parametrize("backend,fuse", [("jax", True), ("jax", False),
                                          ("numpy", False)])
def test_q1_past_int32_a_row_and_2_53_a_group(backend, fuse):
    """Near-maximal prices in one group: every charge is past 2**31 and the
    group's sum of charges past 2**53."""
    n = 300_000
    table = _lineitem(n, seed=2, price_lo=209_000)
    table["l_returnflag"][:] = 1
    table["l_linestatus"][:] = 0
    table["l_shipdate"][:] = 19950101
    want = _q1_numpy(table)
    assert (table["l_extendedprice"] * 100 * 100).max() > 2**31
    assert want["sum_charge"][0] > 2**53
    res = repro.Session(backend=backend, metadata=None).run(
        _q1_flow(table), engine="streaming", fuse=fuse)
    _exact_equal(res.table, want)
    assert np.asarray(res.table["sum_charge"]).dtype == np.int64


def test_q1_fused_segment_keeps_charge_wide():
    """The segment hands the aggregate ``charge`` as a wide column; the
    columns that fit stay int32."""
    from repro.core.shared_cache import SharedCache
    from repro.etl.components import Expression, Filter, FusedSegment
    table = _lineitem(5000, seed=3)
    seg = FusedSegment.from_components([
        Filter("f", col("l_shipdate") <= 19980902),
        Expression("d", "disc_price", DISC_PRICE),
        Expression("c", "charge", CHARGE)])
    runner = get_backend("jax").compile_segment(seg)
    cache = SharedCache({k: v.copy() for k, v in table.items()})
    runner(cache)
    charge = cache.col("charge")
    assert isinstance(charge, wideint.WideColumn)
    assert cache.col("disc_price").dtype == np.int32
    keep = table["l_shipdate"] <= 19980902
    want = (table["l_extendedprice"] * (100 - table["l_discount"])
            * (100 + table["l_tax"]))[keep]
    assert np.array_equal(np.asarray(charge), want)
    (layout,) = runner._layouts
    assert len(layout) == 3 and layout[2][0][0][0] == 2   # op 2 is wide


def test_the_fused_segment_of_q41_compiles_as_before(data):
    """Q4.1's expressions fit int32: its layouts carry no wide part."""
    from repro.etl.components import FusedSegment
    from repro.etl.queries import build_q4
    from repro.core.shared_cache import SharedCache
    qf = build_q4(data)
    seg = FusedSegment.from_components([qf.flow.component(m) for m in (
        "lookup_customer", "lookup_supplier", "lookup_part", "lookup_date",
        "filter_unmatched", "project", "profit_expr")])
    runner = get_backend("jax").compile_segment(seg)
    runner(SharedCache({k: v.copy() for k, v in data.lineorder.items()}))
    assert all(len(layout) == 2 for layout in runner._layouts)


# ---------------------------------------------------------------------------
#  Exact group sums on each route, signed values
# ---------------------------------------------------------------------------
def _signed_values(n: int, seed: int):
    rng = np.random.default_rng(seed)
    keys = rng.integers(0, 5, n)
    small = rng.integers(-40_000, 40_000, n)           # profit-like, signed
    big = rng.integers(-2**31, 2**31 - 1, n)            # the whole int32
    return keys, small, big


@pytest.mark.parametrize("route", ["dense", "sort", "keyless"])
def test_groupby_integer_sums_are_exact(route, monkeypatch):
    if route == "sort":
        monkeypatch.setenv("REPRO_GROUPBY_IMPL", "sort")
    keys, small, big = _signed_values(20_000, seed=4)
    bk = get_backend("jax")
    values = {"s": (small, "sum"), "a": (small, "avg"), "b": (big, "sum"),
              "n": (small, "count")}
    group_cols, aggs = bk.groupby_reduce(
        [] if route == "keyless" else [keys], values, len(keys))
    gid = np.zeros_like(keys) if route == "keyless" else keys
    groups = np.unique(gid)
    want_s = np.array([small[gid == g].sum() for g in groups])
    want_b = np.array([big[gid == g].sum() for g in groups])
    counts = np.array([(gid == g).sum() for g in groups])
    assert np.array_equal(np.asarray(bk.to_host(aggs["s"])).astype(np.int64),
                          want_s)
    assert np.array_equal(np.asarray(bk.to_host(aggs["b"])).astype(np.int64),
                          want_b)
    assert np.array_equal(np.asarray(aggs["a"]), want_s / counts)
    if route != "keyless":
        assert np.array_equal(np.asarray(bk.to_host(group_cols[0])), groups)


def test_numpy_backend_sums_integers_in_int64():
    keys, small, big = _signed_values(5000, seed=5)
    big = big.astype(np.int64) << 24               # sums past 2**53
    _, aggs = get_backend("numpy").groupby_reduce(
        [keys], {"b": (big, "sum"), "s": (small, "sum")}, len(keys))
    assert aggs["b"].dtype == np.int64
    assert np.array_equal(aggs["b"], [big[keys == g].sum() for g in range(5)])
    assert np.array_equal(aggs["s"], [small[keys == g].sum()
                                      for g in range(5)])


@pytest.mark.parametrize("query", ["Q1.1", "Q4.1"])
def test_ssb_sums_are_exact(data, query):
    """Q1.1's revenue (keyless) and Q4.1's profit (dense) on the jax
    backend equal the numpy backend's int64 sums."""
    from repro.etl import BUILDERS
    got = repro.Session(backend="jax", metadata=None).run(
        BUILDERS[query](data).flow, engine="streaming", fuse=True).table
    want = repro.Session(backend="numpy", metadata=None).run(
        BUILDERS[query](data).flow, engine="streaming").table
    for name, w in want.items():
        assert np.array_equal(np.asarray(got[name]), w), name


# ---------------------------------------------------------------------------
#  Host int64 columns past int32
# ---------------------------------------------------------------------------
def test_an_int64_column_past_int32_raises_at_upload():
    bk = get_backend("jax")
    with pytest.raises(wideint.IntRangeError, match="'big'"):
        bk.asarray(np.array([1, 2**40], dtype=np.int64), name="big")
    assert bk.asarray(np.array([1, 2**31 - 1], dtype=np.int64)).dtype \
        == jnp.int32


@pytest.mark.parametrize("fuse", [True, False])
def test_an_int64_column_past_int32_raises_at_pack(fuse):
    table = _lineitem(1000, seed=6)
    table["l_quantity"] = table["l_quantity"] + 2**33
    flow = (repro.flow("q").source(table)
            .derive("q2", col("l_quantity") * 2)
            .aggregate(["l_returnflag"], {"s": ("q2", "sum")}).sink())
    with pytest.raises(Exception, match="l_quantity"):
        repro.Session(backend="jax", metadata=None).run(
            flow, engine="streaming", fuse=fuse)
