"""Star Schema Benchmark tables (O'Neil, O'Neil, Chen, "Star Schema
Benchmark", rev. 3, 2009), generated from a seed in vectorised numpy.

Every table keeps all of the specification's columns.  Text columns are
dictionary codes (ints), so the program's integer kernels can read them:

- region: AFRICA=0, AMERICA=1, ASIA=2, EUROPE=3, MIDDLE EAST=4; nation n
  (0..24) lies in region n % 5, and city = nation * 10 + (0..9);
- p_mfgr: MFGR#m is m (1..5); p_category: MFGR#mc is m * 10 + c (c 1..5);
  p_brand1: MFGR#mcb is category * 100 + b (b 1..40);
- dates: yyyymmdd integers, one row per calendar day of the configured
  span; text date fields are codes derived from the day.

Fact rows come in orders of 1 to 7 lines, as in the specification, and
every foreign key is drawn uniformly.  Money is in integer cents.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List

import numpy as np

Table = Dict[str, np.ndarray]


@dataclass
class Dataset:
    """Dimension tables shared by every fact table, and the fact tables
    (``facts[i]``) that runs take in turn."""
    fact_name: str
    facts: List[Table]
    dims: Dict[str, Table]


def _calendar(first: str, last: str) -> Table:
    days = np.arange(np.datetime64(first, "D"), np.datetime64(last, "D")
                     + np.timedelta64(1, "D"))
    year = days.astype("datetime64[Y]").astype(np.int64) + 1970
    month0 = days.astype("datetime64[M]").astype(np.int64) % 12
    month = month0 + 1
    dom = (days - days.astype("datetime64[M]")).astype(np.int64) + 1
    doy = (days - days.astype("datetime64[Y]")).astype(np.int64) + 1
    dow = (days.astype(np.int64) + 3) % 7          # 1970-01-01 was a Thursday
    next_day = days + np.timedelta64(1, "D")
    last_in_month = next_day.astype("datetime64[M]") != days.astype(
        "datetime64[M]")
    n = len(days)
    return {
        "d_datekey": year * 10_000 + month * 100 + dom,
        "d_date": np.arange(n, dtype=np.int64),
        "d_dayofweek": dow,
        "d_month": month,
        "d_year": year,
        "d_yearmonthnum": year * 100 + month,
        "d_yearmonth": year * 100 + month,
        "d_daynuminweek": dow + 1,
        "d_daynuminmonth": dom,
        "d_daynuminyear": doy,
        "d_monthnuminyear": month,
        "d_weeknuminyear": (doy - 1) // 7 + 1,
        "d_sellingseason": np.array([0, 0, 1, 1, 1, 2, 2, 2, 3, 3, 4, 4],
                                    dtype=np.int64)[month0],
        "d_lastdayinweekfl": (dow == 5).astype(np.int64),
        "d_lastdayinmonthfl": last_in_month.astype(np.int64),
        "d_holidayfl": ((month == 12) & (dom == 25)
                        | (month == 1) & (dom == 1)).astype(np.int64),
        "d_weekdayfl": (dow < 5).astype(np.int64),
    }


def _dimensions(cfg: dict, rng: np.random.Generator) -> Dict[str, Table]:
    nc, ns, npart = (cfg["customer_rows"], cfg["supplier_rows"],
                     cfg["part_rows"])
    c_nation = rng.integers(0, 25, nc)
    customer = {
        "c_custkey": np.arange(1, nc + 1, dtype=np.int64),
        "c_name": np.arange(1, nc + 1, dtype=np.int64),
        "c_address": rng.integers(0, 1 << 30, nc),
        "c_city": c_nation * 10 + rng.integers(0, 10, nc),
        "c_nation": c_nation,
        "c_region": c_nation % 5,
        "c_phone": rng.integers(10_000_000, 100_000_000, nc),
        "c_mktsegment": rng.integers(0, 5, nc),
    }
    s_nation = rng.integers(0, 25, ns)
    supplier = {
        "s_suppkey": np.arange(1, ns + 1, dtype=np.int64),
        "s_name": np.arange(1, ns + 1, dtype=np.int64),
        "s_address": rng.integers(0, 1 << 30, ns),
        "s_city": s_nation * 10 + rng.integers(0, 10, ns),
        "s_nation": s_nation,
        "s_region": s_nation % 5,
        "s_phone": rng.integers(10_000_000, 100_000_000, ns),
    }
    mfgr = rng.integers(1, 6, npart)
    category = mfgr * 10 + rng.integers(1, 6, npart)
    part = {
        "p_partkey": np.arange(1, npart + 1, dtype=np.int64),
        "p_name": rng.integers(0, 1 << 30, npart),
        "p_mfgr": mfgr,
        "p_category": category,
        "p_brand1": category * 100 + rng.integers(1, 41, npart),
        "p_color": rng.integers(0, 92, npart),
        "p_type": rng.integers(0, 150, npart),
        "p_size": rng.integers(1, 51, npart),
        "p_container": rng.integers(0, 40, npart),
    }
    return {"customer": customer, "supplier": supplier, "part": part,
            "date": _calendar(cfg["date_first"], cfg["date_last"])}


def _lineorder(cfg: dict, dims: Dict[str, Table],
               rng: np.random.Generator) -> Table:
    n = cfg["lineorder_rows"]
    lines = rng.integers(1, 8, n // 4 + 64)        # 1..7 lines an order
    while lines.sum() < n:
        lines = np.concatenate([lines, rng.integers(1, 8, n // 64 + 64)])
    ends = np.cumsum(lines)
    n_orders = int(np.searchsorted(ends, n)) + 1
    lines = lines[:n_orders].copy()
    lines[-1] -= int(ends[n_orders - 1]) - n
    order = np.repeat(np.arange(n_orders), lines)
    starts = np.concatenate([[0], np.cumsum(lines)[:-1]])

    datekeys = dims["date"]["d_datekey"]
    n_days = len(datekeys)
    # commit dates run up to 90 days past the last order date
    ahead = np.arange(np.datetime64(cfg["date_first"], "D"),
                      np.datetime64(cfg["date_first"], "D")
                      + np.timedelta64(n_days + 91, "D"))
    ahead_keys = (ahead.astype("datetime64[Y]").astype(np.int64) + 1970) \
        * 10_000 + (ahead.astype("datetime64[M]").astype(np.int64) % 12
                    + 1) * 100 \
        + (ahead - ahead.astype("datetime64[M]")).astype(np.int64) + 1

    o_cust = rng.integers(1, cfg["customer_rows"] + 1, n_orders)
    o_day = rng.integers(0, n_days, n_orders)
    o_prio = rng.integers(0, 5, n_orders)

    quantity = rng.integers(1, 51, n)
    extendedprice = rng.integers(90_000, 1_100_000, n)
    discount = rng.integers(0, 11, n)
    tax = rng.integers(0, 9, n)
    revenue = extendedprice * (100 - discount) // 100
    line_total = revenue * (100 + tax) // 100
    day = o_day[order]
    return {
        "lo_orderkey": order + 1,
        "lo_linenumber": np.arange(n) - starts[order] + 1,
        "lo_custkey": o_cust[order],
        "lo_partkey": rng.integers(1, cfg["part_rows"] + 1, n),
        "lo_suppkey": rng.integers(1, cfg["supplier_rows"] + 1, n),
        "lo_orderdate": datekeys[day],
        "lo_orderpriority": o_prio[order],
        "lo_shippriority": np.zeros(n, dtype=np.int64),
        "lo_quantity": quantity,
        "lo_extendedprice": extendedprice,
        "lo_ordtotalprice": np.add.reduceat(line_total, starts)[order],
        "lo_discount": discount,
        "lo_revenue": revenue,
        "lo_supplycost": rng.integers(40_000, 60_000, n),
        "lo_tax": tax,
        "lo_commitdate": ahead_keys[day + rng.integers(30, 91, n)],
        "lo_shipmode": rng.integers(0, 7, n),
    }


def generate(cfg: dict, seed: int) -> Dataset:
    """The dimensions and ``cfg["fact_tables"]`` lineorder tables, all
    from ``seed``: the same seed gives the same tables."""
    streams = np.random.SeedSequence(int(seed)).spawn(
        1 + int(cfg["fact_tables"]))
    dims = _dimensions(cfg, np.random.default_rng(streams[0]))
    facts = [_lineorder(cfg, dims, np.random.default_rng(s))
             for s in streams[1:]]
    return Dataset(fact_name="lineorder", facts=facts, dims=dims)
