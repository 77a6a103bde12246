"""TPC-H tables (TPC Benchmark H Standard Specification, clause 4.2.3),
generated from a seed in vectorised numpy: all eight tables, every column.

Cardinalities follow clause 4.2.5 from the configured customers, parts and
suppliers: ``orders_per_customer`` orders a customer slot, 1 to 7 lines an
order, ``partsupp_per_part`` suppliers a part, 25 nations, 5 regions.
Text columns are dictionary codes (ints) whose order is the
specification's sort order where a query sorts on them:

- ``l_returnflag``: A=0, N=1, R=2; ``l_linestatus``: F=0, O=1;
  ``o_orderstatus``: F=0, O=1, P=2;
- names, addresses, comments and phones are integer codes, types,
  containers, segments, priorities, instructions and modes an index into
  the specification's list; ``p_brand`` is mfgr * 10 + n (Brand#MN).

Money is integer cents and discount and tax integer percent.  Dates are
yyyymmdd integers.  ``o_orderkey`` is sparse (the first 8 of every 32
keys), and no order goes to a customer key divisible by 3.
"""
from __future__ import annotations

from typing import Dict

import numpy as np

from bench.gen.ssb import Dataset

Table = Dict[str, np.ndarray]

#: n_regionkey of each nation, clause 4.2.3
NATION_REGION = np.array([0, 1, 1, 1, 4, 0, 3, 3, 2, 2, 4, 4, 2, 4, 0, 0, 0,
                          1, 2, 3, 4, 2, 3, 3, 1], dtype=np.int64)
#: A, N, R; F, O
FLAG_A, FLAG_N, FLAG_R = 0, 1, 2
STATUS_F, STATUS_O, STATUS_P = 0, 1, 2


def _days(first: str, last: str) -> np.ndarray:
    return np.arange(np.datetime64(first, "D"),
                     np.datetime64(last, "D") + np.timedelta64(1, "D"))


def _yyyymmdd(days: np.ndarray) -> np.ndarray:
    year = days.astype("datetime64[Y]").astype(np.int64) + 1970
    month = days.astype("datetime64[M]").astype(np.int64) % 12 + 1
    dom = (days - days.astype("datetime64[M]")).astype(np.int64) + 1
    return year * 10_000 + month * 100 + dom


def retail_price(partkey: np.ndarray) -> np.ndarray:
    """P_RETAILPRICE in cents: 90000 + (pk / 10 mod 20001) + 100 (pk mod
    1000)."""
    return 90_000 + (partkey // 10) % 20_001 + 100 * (partkey % 1_000)


def supplier_of(partkey: np.ndarray, i: np.ndarray, suppliers: int
                ) -> np.ndarray:
    """The i-th (0..3) supplier of a part: (pk + i (S / 4 + (pk - 1) / S))
    mod S + 1."""
    return (partkey + i * (suppliers // 4 + (partkey - 1) // suppliers)) \
        % suppliers + 1


def _dimensions(cfg: dict, rng: np.random.Generator) -> Dict[str, Table]:
    nc, npart, ns = (cfg["customer_rows"], cfg["part_rows"],
                     cfg["supplier_rows"])
    c_nation = rng.integers(0, 25, nc)
    customer = {
        "c_custkey": np.arange(1, nc + 1, dtype=np.int64),
        "c_name": np.arange(1, nc + 1, dtype=np.int64),
        "c_address": rng.integers(0, 1 << 30, nc),
        "c_nationkey": c_nation,
        "c_phone": (c_nation + 10) * 10_000_000
        + rng.integers(0, 10_000_000, nc),
        "c_acctbal": rng.integers(-99_999, 1_000_000, nc),
        "c_mktsegment": rng.integers(0, 5, nc),
        "c_comment": rng.integers(0, 1 << 30, nc),
    }
    s_nation = rng.integers(0, 25, ns)
    supplier = {
        "s_suppkey": np.arange(1, ns + 1, dtype=np.int64),
        "s_name": np.arange(1, ns + 1, dtype=np.int64),
        "s_address": rng.integers(0, 1 << 30, ns),
        "s_nationkey": s_nation,
        "s_phone": (s_nation + 10) * 10_000_000
        + rng.integers(0, 10_000_000, ns),
        "s_acctbal": rng.integers(-99_999, 1_000_000, ns),
        "s_comment": rng.integers(0, 1 << 30, ns),
    }
    pk = np.arange(1, npart + 1, dtype=np.int64)
    mfgr = rng.integers(1, 6, npart)
    part = {
        "p_partkey": pk,
        "p_name": rng.integers(0, 1 << 30, npart),
        "p_mfgr": mfgr,
        "p_brand": mfgr * 10 + rng.integers(1, 6, npart),
        "p_type": rng.integers(0, 150, npart),
        "p_size": rng.integers(1, 51, npart),
        "p_container": rng.integers(0, 40, npart),
        "p_retailprice": retail_price(pk),
        "p_comment": rng.integers(0, 1 << 30, npart),
    }
    per = cfg["partsupp_per_part"]
    ps_pk = np.repeat(pk, per)
    ps_i = np.tile(np.arange(per, dtype=np.int64), npart)
    partsupp = {
        "ps_partkey": ps_pk,
        "ps_suppkey": supplier_of(ps_pk, ps_i, ns),
        "ps_availqty": rng.integers(1, 10_000, npart * per),
        "ps_supplycost": rng.integers(100, 100_001, npart * per),
        "ps_comment": rng.integers(0, 1 << 30, npart * per),
    }
    nation = {
        "n_nationkey": np.arange(25, dtype=np.int64),
        "n_name": np.arange(25, dtype=np.int64),
        "n_regionkey": NATION_REGION.copy(),
        "n_comment": rng.integers(0, 1 << 30, 25),
    }
    region = {
        "r_regionkey": np.arange(5, dtype=np.int64),
        "r_name": np.arange(5, dtype=np.int64),
        "r_comment": rng.integers(0, 1 << 30, 5),
    }
    return {"customer": customer, "supplier": supplier, "part": part,
            "partsupp": partsupp, "nation": nation, "region": region}


def _orders(cfg: dict, rng: np.random.Generator):
    """The orders' keys, customers, dates and line counts (the columns the
    line items derive from), and the calendar the dates index."""
    n = cfg["orders_per_customer"] * cfg["customer_rows"]
    i = np.arange(n, dtype=np.int64)
    # customers whose key is not divisible by 3: 1, 2, 4, 5, 7, ...
    j = rng.integers(0, (cfg["customer_rows"] + 1) * 2 // 3, n)
    days = _days(cfg["date_first"], cfg["date_last"])
    last_order = len(days) - 1 - 151          # ENDDATE - 151 days
    lo, hi = cfg["lines_per_order"]
    return {
        "o_orderkey": (i // 8) * 32 + i % 8 + 1,
        "o_custkey": 3 * (j // 2) + 1 + j % 2,
        "day": rng.integers(0, last_order + 1, n),
        "lines": rng.integers(lo, hi + 1, n),
        "o_orderpriority": rng.integers(0, 5, n),
        "o_clerk": rng.integers(1, max(cfg["customer_rows"] // 150, 1) + 1,
                                n),
        "o_comment": rng.integers(0, 1 << 30, n),
    }, days


def _lineitem(cfg: dict, orders: dict, keys: np.ndarray, current: int,
              rng: np.random.Generator) -> Table:
    lines = orders["lines"]
    n = int(lines.sum())
    order = np.repeat(np.arange(len(lines)), lines)
    starts = np.cumsum(lines) - lines
    partkey = rng.integers(1, cfg["part_rows"] + 1, n)
    quantity = rng.integers(1, 51, n)
    day = orders["day"][order]
    ship = day + rng.integers(1, 122, n)
    receipt = ship + rng.integers(1, 31, n)
    flag = np.where(receipt <= current,
                    np.where(rng.integers(0, 2, n) == 1, FLAG_R, FLAG_A),
                    FLAG_N)
    return {
        "l_orderkey": orders["o_orderkey"][order],
        "l_partkey": partkey,
        "l_suppkey": supplier_of(partkey, rng.integers(0, 4, n),
                                 cfg["supplier_rows"]),
        "l_linenumber": np.arange(n, dtype=np.int64) - starts[order] + 1,
        "l_quantity": quantity,
        "l_extendedprice": quantity * retail_price(partkey),
        "l_discount": rng.integers(0, 11, n),
        "l_tax": rng.integers(0, 9, n),
        "l_returnflag": flag,
        "l_linestatus": np.where(ship > current, STATUS_O, STATUS_F),
        "l_shipdate": keys[ship],
        "l_commitdate": keys[day + rng.integers(30, 91, n)],
        "l_receiptdate": keys[receipt],
        "l_shipinstruct": rng.integers(0, 4, n),
        "l_shipmode": rng.integers(0, 7, n),
        "l_comment": rng.integers(0, 1 << 30, n),
    }


def _finish_orders(orders: dict, keys: np.ndarray, li: Table) -> Table:
    """The orders table, its total price and status from the line items
    ``li`` (the first lineitem table)."""
    lines = orders["lines"]
    starts = np.cumsum(lines) - lines
    # extended price x (1 + tax) x (1 - discount), rounded to cents
    line_total = (li["l_extendedprice"] * (100 + li["l_tax"])
                  * (100 - li["l_discount"]) + 5_000) // 10_000
    n_open = np.add.reduceat(li["l_linestatus"], starts)
    status = np.where(n_open == 0, STATUS_F,
                      np.where(n_open == lines, STATUS_O, STATUS_P))
    return {
        "o_orderkey": orders["o_orderkey"],
        "o_custkey": orders["o_custkey"],
        "o_orderstatus": status,
        "o_totalprice": np.add.reduceat(line_total, starts),
        "o_orderdate": keys[orders["day"]],
        "o_orderpriority": orders["o_orderpriority"],
        "o_clerk": orders["o_clerk"],
        "o_shippriority": np.zeros(len(lines), dtype=np.int64),
        "o_comment": orders["o_comment"],
    }


def generate(cfg: dict, seed: int) -> Dataset:
    """The dimensions, orders and ``cfg["fact_tables"]`` lineitem tables
    over the same orders and parts, all from ``seed``."""
    streams = np.random.SeedSequence(int(seed)).spawn(
        2 + int(cfg["fact_tables"]))
    dims = _dimensions(cfg, np.random.default_rng(streams[0]))
    orders, days = _orders(cfg, np.random.default_rng(streams[1]))
    # ship and receipt dates run up to 151 days past the last order date
    keys = _yyyymmdd(days)
    current = int(np.searchsorted(
        days, np.datetime64(cfg["current_date"], "D")))
    facts = [_lineitem(cfg, orders, keys, current,
                       np.random.default_rng(s)) for s in streams[2:]]
    dims["orders"] = _finish_orders(orders, keys, facts[0])
    return Dataset(fact_name="lineitem", facts=facts, dims=dims)
