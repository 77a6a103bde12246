"""Bytes that a flow's device work needs at the least, from its shapes.

A roofline share is the least time the chip could take for these bytes at
its peak HBM bandwidth, over the device time the trace measured.  The
work is integer compares, gathers and one-hot sums, with no matmul of a
size where peak FLOP/s could bind, so the bytes bound it.  Every column
is 4 bytes on the device (the program runs with 32-bit types).
"""
from __future__ import annotations

from typing import Dict, List

from bench import exprs

WORD = 4          # bytes of one device column value
MASK = 1          # bytes of one keep-mask value


def _segment_steps(flow: dict) -> List[dict]:
    """The row-synchronized steps the program fuses into one segment:
    everything before the aggregate."""
    out = []
    for step in flow["steps"]:
        if "aggregate" in step or "sort" in step:
            break
        out.append(step)
    return out


def _aggregate(flow: dict) -> dict:
    return next(s for s in flow["steps"] if "aggregate" in s)


def segment_bytes(flow: dict, rows: int, dim_rows: Dict[str, int]) -> int:
    """Bytes the fused segment needs for ``rows`` fact rows: each fact
    column it reads, one key and the returned payloads per Lookup per
    row, the columns it hands to the aggregate and the keep-mask, and
    each dimension table (key and payloads) once."""
    produced, read = set(), set()
    per_row = MASK
    once = 0
    for step in _segment_steps(flow):
        if "lookup" in step:
            if step["key"] not in produced:
                read.add(step["key"])
            per_row += WORD * (1 + len(step["returns"]))
            once += WORD * dim_rows[step["lookup"]] * (
                1 + len(set(step["returns"].values())))
            produced |= set(step["returns"])
            if step.get("matched_flag"):
                produced.add(step["matched_flag"])
        elif "filter" in step:
            read |= exprs.columns(exprs.parse(step["filter"])) - produced
        elif "derive" in step:
            read |= exprs.columns(exprs.parse(step["expr"])) - produced
            produced.add(step["derive"])
    agg = _aggregate(flow)
    handed = set(agg["aggregate"]) | {src for src, _ in agg["aggs"].values()}
    per_row += WORD * (len(read) + len(handed & produced))
    return rows * per_row + once


def groupby_bytes(flow: dict, rows_in: int, groups: int) -> int:
    """Bytes the group-by kernel needs: per input row one dense group id
    (when there are group keys) and each summed column; per group each
    sum and the count, written."""
    agg = _aggregate(flow)
    summed = {src for src, op in agg["aggs"].values() if op in ("sum", "avg")}
    key = WORD if agg["aggregate"] else 0
    return rows_in * (key + WORD * len(summed)) \
        + groups * WORD * (len(summed) + 1)
