"""Plain numpy reference of a flow file: the same steps on the same tables,
written without the program (no hashing, no chunks, no device).

Lookups are a binary search over the dimension's sorted keys, filters are
masks over the whole table, and group-by sums accumulate in float64 over
integer inputs, which is exact below 2**53.  A group-by is kept as
*partials* (per-group sums and counts), so serving cells can
merge them tick by tick as the program's resident state does.

The ``precision`` argument gives the control: ``"bfloat16"`` rounds each
summed value to bfloat16 before it is added (what a one-hot matmul at the
TPU's default precision does to float32 operands), and ``merge`` can keep
the running state in bfloat16.  The reference proper uses ``"float64"``.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence

import numpy as np

from bench import exprs

Table = Dict[str, np.ndarray]


def to_bfloat16(x: np.ndarray) -> np.ndarray:
    """``x`` rounded to the nearest bfloat16 (ties to even), as float64."""
    f = np.asarray(x, dtype=np.float32)
    u = f.view(np.uint32).astype(np.uint64)
    u = (u + 0x7FFF + ((u >> 16) & 1)) & 0xFFFF0000
    return u.astype(np.uint32).view(np.float32).astype(np.float64)


def _round(x: np.ndarray, precision: str) -> np.ndarray:
    if precision == "float64":
        return np.asarray(x, dtype=np.float64)
    if precision == "bfloat16":
        return to_bfloat16(x)
    raise ValueError(f"unknown precision {precision!r}")


def evaluate_expr(text: str, cols: Table) -> np.ndarray:
    return exprs.walk(exprs.parse(text), cols.__getitem__, lambda v: v)


#: aggregate ops the reference computes; a flow with another is refused
AGG_OPS = ("sum", "avg", "count")


@dataclass
class Partials:
    """Per-group partial aggregates: ``keys`` are the group columns (sorted
    ascending, lexicographic), ``sums`` maps a summed input column to its
    per-group sums and ``counts`` is rows per group."""
    group_by: List[str]
    aggs: Dict[str, List[str]]
    keys: Table
    counts: np.ndarray
    sums: Dict[str, np.ndarray]


def _lookup(step: dict, cols: Table, dims: Dict[str, Table]) -> None:
    dim = dims[step["lookup"]]
    keep = (evaluate_expr(step["where"], dim) if step.get("where")
            else np.ones(len(dim[step["dim_key"]]), dtype=bool))
    keys = dim[step["dim_key"]][keep]
    # first occurrence of a duplicate key wins, as in a unique-key lookup
    order = np.argsort(keys, kind="stable")
    sorted_keys = keys[order]
    probe = cols[step["key"]]
    pos = np.searchsorted(sorted_keys, probe, side="left")
    pos_c = np.minimum(pos, max(len(sorted_keys) - 1, 0))
    found = (pos < len(sorted_keys)) & (
        sorted_keys[pos_c] == probe if len(sorted_keys) else False)
    default = step.get("default", -1)
    for out, src in step["returns"].items():
        vals = dim[src][keep][order]
        cols[out] = (np.where(found, vals[pos_c], default) if len(vals)
                     else np.full(len(probe), default, dtype=np.int64))
    if step.get("matched_flag"):
        cols[step["matched_flag"]] = found


def partials(flow: dict, fact: Table, dims: Dict[str, Table],
             precision: str = "float64") -> Partials:
    """Run the flow's steps up to and including its aggregate over the
    whole fact table; return the aggregate's partials."""
    cols: Table = dict(fact)
    mask: Optional[np.ndarray] = None
    for step in flow["steps"]:
        if "lookup" in step:
            _lookup(step, cols, dims)
        elif "filter" in step:
            m = np.asarray(evaluate_expr(step["filter"], cols), dtype=bool)
            mask = m if mask is None else (mask & m)
        elif "derive" in step:
            cols[step["derive"]] = evaluate_expr(step["expr"], cols)
        elif "project" in step:
            cols = {k: cols[k] for k in step["project"]}
        elif "aggregate" in step:
            return _group(step, cols, mask, precision)
        else:
            raise ValueError(f"step {step} before the aggregate")
    raise ValueError(f"flow {flow['name']!r} has no aggregate")


def _group(step: dict, cols: Table, mask: Optional[np.ndarray],
           precision: str) -> Partials:
    group_by = list(step["aggregate"])
    aggs = {out: list(spec) for out, spec in step["aggs"].items()}
    sel = (slice(None) if mask is None else mask)
    n = len(next(iter(cols.values())))
    if group_by:
        stacked = np.stack([np.asarray(cols[g])[sel] for g in group_by])
        uniq, inv = np.unique(stacked, axis=1, return_inverse=True)
        inv = inv.reshape(-1)
        keys = {g: uniq[i].astype(np.int64) for i, g in enumerate(group_by)}
        n_groups = uniq.shape[1]
    else:
        rows = int(np.count_nonzero(mask)) if mask is not None else n
        inv = np.zeros(rows, dtype=np.int64)
        keys, n_groups = {}, 1
    counts = np.bincount(inv, minlength=n_groups).astype(np.int64)
    sums = {}
    for src, op in aggs.values():
        if op not in AGG_OPS:
            raise ValueError(f"the reference has no aggregate {op!r}")
        if op in ("sum", "avg") and src not in sums:
            vals = np.asarray(cols[src])[sel]
            sums[src] = np.bincount(inv, weights=_round(vals, precision),
                                    minlength=n_groups)
    return Partials(group_by, aggs, keys, counts, sums)


def merge(acc: Optional[Partials], part: Partials,
          state: str = "float64") -> Partials:
    """``acc`` with ``part`` added group by group (a serving tick's merge).
    ``state="bfloat16"`` rounds the running sums and counts to bfloat16
    after every merge."""
    if acc is None:
        return part
    names = acc.group_by
    if names:
        k_acc = np.stack([acc.keys[g] for g in names])
        k_new = np.stack([part.keys[g] for g in names])
        uniq, inv = np.unique(np.concatenate([k_acc, k_new], axis=1),
                              axis=1, return_inverse=True)
        inv = inv.reshape(-1)
        keys = {g: uniq[i] for i, g in enumerate(names)}
        n_groups = uniq.shape[1]
    else:
        inv, keys, n_groups = np.zeros(2, dtype=np.int64), {}, 1
    a_idx, p_idx = inv[:len(acc.counts)], inv[len(acc.counts):]

    def add(x, y, dtype):
        out = np.zeros(n_groups, dtype=dtype)
        np.add.at(out, a_idx, x)
        np.add.at(out, p_idx, y)
        return _round(out, state) if state != "float64" else out

    counts = add(acc.counts, part.counts, np.float64 if state != "float64"
                 else np.int64)
    sums = {c: add(acc.sums[c], part.sums[c], np.float64) for c in acc.sums}
    return Partials(acc.group_by, acc.aggs, keys, counts, sums)


def finalize(p: Partials, flow: dict, state: str = "float64") -> Table:
    """The sink table the flow's aggregate and sort steps give from
    ``p``: groups with at least one row, in the sort's key order (or
    ascending group order when the flow does not sort)."""
    live = p.counts > 0 if p.group_by else np.ones(1, dtype=bool)
    out: Table = {g: p.keys[g][live] for g in p.group_by}
    for name, (src, op) in p.aggs.items():
        if op == "sum":
            out[name] = p.sums[src][live]
        elif op == "count":
            out[name] = np.rint(p.counts[live]).astype(np.int64)
        else:
            avg = p.sums[src][live] / p.counts[live]
            out[name] = _round(avg, state) if state != "float64" else avg
    sort = next((s["sort"] for s in flow["steps"] if "sort" in s), None)
    if sort:
        order = np.lexsort(tuple(out[c] for c in reversed(sort)))
        out = {k: v[order] for k, v in out.items()}
    return out


def run(flow: dict, fact: Table, dims: Dict[str, Table],
        precision: str = "float64") -> Table:
    """The flow's sink table over one whole fact table."""
    return finalize(partials(flow, fact, dims, precision), flow)


def aggregate_columns(flow: dict) -> Dict[str, Sequence[str]]:
    """The sink's columns by how they are compared: ``keys`` (group
    columns, exact), ``exact`` (counts) and ``float`` (sums and averages,
    to a relative limit)."""
    agg = next(s for s in flow["steps"] if "aggregate" in s)
    exact = [n for n, (_, op) in agg["aggs"].items() if op == "count"]
    floats = [n for n, (_, op) in agg["aggs"].items() if op in ("sum", "avg")]
    return {"keys": list(agg["aggregate"]), "exact": exact, "float": floats}
