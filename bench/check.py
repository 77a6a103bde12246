"""The comparison that decides ``correct``: each sink table the timed path
produced against the reference's, number by number, each number against
the limit the configuration states for it.

- ``rows_mismatch``: rows that one table has and the other lacks;
- ``key_mismatch``: rows whose group keys differ (order counts);
- ``exact_mismatch``: counts that differ;
- ``float_rel_err``: the largest relative error of a sum or average;
- ``failed``: runs or ticks that raised, retried, degraded or were
  dead-lettered (their answer never came).
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np

Table = Dict[str, np.ndarray]
#: the reading of a number that could not be compared (a missing column,
#: a table of the wrong length, a NaN): finite, so the result stays JSON
UNCOMPARABLE = 1e30
NAMES = ("rows_mismatch", "key_mismatch", "exact_mismatch", "float_rel_err",
         "failed")


def rel_err(got: np.ndarray, expect: np.ndarray) -> float:
    g = np.asarray(got, dtype=np.float64)
    e = np.asarray(expect, dtype=np.float64)
    if e.size == 0:
        return 0.0
    den = np.maximum(np.abs(e), np.finfo(np.float64).tiny)
    err = np.abs(g - e) / den
    return float(np.max(np.where(np.isfinite(g), err, UNCOMPARABLE)))


def sort_by_keys(table: Table, keys: Sequence[str]) -> Table:
    if not keys or not table:
        return table
    order = np.lexsort(tuple(np.asarray(table[k]) for k in reversed(keys)))
    return {c: np.asarray(v)[order] for c, v in table.items()}


def compare(got: Table, expect: Table, cols: Dict[str, Sequence[str]]
            ) -> Dict[str, float]:
    """The numbers for one table; ``cols`` sorts the reference's columns
    into ``keys``, ``exact`` and ``float`` (``reference.aggregate_columns``)."""
    n_exp = len(next(iter(expect.values()))) if expect else 0
    missing = [c for c in expect if c not in got]
    if missing:
        return {"rows_mismatch": float(max(n_exp, 1)),
                "key_mismatch": float(n_exp), "exact_mismatch": float(n_exp),
                "float_rel_err": UNCOMPARABLE}
    n_got = len(np.asarray(got[next(iter(expect))]))
    if n_got != n_exp:
        return {"rows_mismatch": float(abs(n_got - n_exp)),
                "key_mismatch": float(max(n_got, n_exp)),
                "exact_mismatch": float(max(n_got, n_exp)),
                "float_rel_err": UNCOMPARABLE}
    key_bad = np.zeros(n_exp, dtype=bool)
    for k in cols["keys"]:
        key_bad |= np.asarray(got[k]).astype(np.int64) != expect[k]
    exact_bad = 0
    for c in cols["exact"]:
        exact_bad += int(np.count_nonzero(
            np.asarray(got[c]).astype(np.int64) != expect[c]))
    err = max((rel_err(got[c], expect[c]) for c in cols["float"]),
              default=0.0)
    return {"rows_mismatch": 0.0, "key_mismatch": float(key_bad.sum()),
            "exact_mismatch": float(exact_bad), "float_rel_err": err}


def worst(readings: List[Dict[str, float]], failed: int) -> Dict[str, float]:
    """Each number's worst value over ``readings``; with nothing compared,
    every number reads as failed."""
    out = {"rows_mismatch": 0.0, "key_mismatch": 0.0, "exact_mismatch": 0.0,
           "float_rel_err": 0.0, "failed": float(failed)}
    if not readings:
        out["rows_mismatch"] = UNCOMPARABLE
    for r in readings:
        for k, v in r.items():
            out[k] = max(out[k], v)
    return out


def verdict(numbers: Dict[str, float], limits: Dict[str, float]
            ) -> Dict[str, Dict[str, float]]:
    """Each number beside its limit; a number passes at or under it."""
    return {k: {"value": numbers[k], "limit": float(limits[k])}
            for k in NAMES}


def passed(checks: Dict[str, Dict[str, float]]) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())


def lines(checks: Dict[str, Dict[str, float]]) -> List[str]:
    return [f"check {k} {c['value']!r} limit {c['limit']!r} "
            f"{'ok' if c['value'] <= c['limit'] else 'FAIL'}"
            for k, c in checks.items()]
