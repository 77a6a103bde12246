"""Build the program's flow from a flow file, through its public
``repro.flow()`` API.

A flow file (``bench/flows/<name>.json``) names its fact table and lists
its steps; ``bench.reference`` reads the same file for the numpy reference:

- ``{"lookup": dim, "key": fact column, "dim_key": dim column,
  "returns": {out: dim column}, "where": dim expression,
  "matched_flag": out, "default": -1}`` (``where``, ``matched_flag`` and
  ``default`` are optional);
- ``{"filter": expression}``, ``{"derive": out, "expr": expression}``,
  ``{"project": [columns]}``;
- ``{"aggregate": [group columns], "aggs": {out: [column, op]}}`` and
  ``{"sort": [columns]}``.
"""
from __future__ import annotations

from typing import Dict

import numpy as np

from bench import exprs
from bench.reference import evaluate_expr

Table = Dict[str, np.ndarray]


def expr(text: str):
    """The program's expression object for ``text``."""
    import repro
    return exprs.walk(exprs.parse(text), repro.col, lambda v: v)


def build(flow: dict, source: Table, dims: Dict[str, Table]):
    """A sealed ``repro`` flow over ``source``, with the steps of
    ``flow``.  Dimension row filters (``where``) are evaluated here, on the
    host, and handed to the program as the lookup's row filter."""
    import repro
    b = repro.flow(flow["name"]).source(source)
    for step in flow["steps"]:
        if "lookup" in step:
            dim = dims[step["lookup"]]
            payload = {c: dim[c] for c in set(step["returns"].values())}
            table = (dim[step["dim_key"]], payload)
            if step.get("where"):
                table += (np.asarray(evaluate_expr(step["where"], dim),
                                     dtype=bool),)
            b = b.lookup(table, step["key"], dict(step["returns"]),
                         default=step.get("default", -1),
                         matched_flag=step.get("matched_flag"))
        elif "filter" in step:
            b = b.filter(expr(step["filter"]))
        elif "derive" in step:
            b = b.derive(step["derive"], expr(step["expr"]))
        elif "project" in step:
            b = b.project(*step["project"])
        elif "aggregate" in step:
            b = b.aggregate(list(step["aggregate"]),
                            {k: tuple(v) for k, v in step["aggs"].items()})
        elif "sort" in step:
            b = b.sort(list(step["sort"]))
        else:
            raise ValueError(f"unknown step {step}")
    return b.sink()


def source_of(built) -> object:
    """The flow's source component, whose ``set_data`` swaps the fact
    table between runs."""
    return next(c for c in built.flow.vertices.values()
                if hasattr(c, "set_data"))
