"""Reference operator backend: plain numpy, bit-identical to the component
code it replaced (the inlined Filter/Lookup/Expression/Aggregate/Sort
bodies).  Every accelerated backend is property-tested against this one.

Segment fusion (``compile_segment``) uses the base class's composed host
runner unchanged: one vectorized pass over the fused op list with filter
masks applied eagerly, so a ``FusedSegment`` on this backend is the
loop-free reference the jitted jax segment kernel is checked against."""
from __future__ import annotations

from typing import Callable, Dict, List, Mapping, Optional, Sequence, Tuple

import numpy as np

from .. import wideint
from .base import AGG_OPS, Backend, no_groups


def _sum_dtype(vals: np.ndarray):
    """Integers sum exactly in int64, as the device's exact sums; anything
    else in float64."""
    return np.int64 if np.issubdtype(vals.dtype, np.integer) else np.float64


def _sum(acc: np.ndarray) -> np.ndarray:
    """A sum as every backend returns it (``wideint.exact_result`` for
    integer sums)."""
    return wideint.exact_result(acc) if acc.dtype == np.int64 else acc


class NumpyBackend(Backend):
    name = "numpy"
    batch_align = 1
    oracle_rtol = 1e-9
    #: host compaction is a free boolean index — nothing to defer, so the
    #: composed host runner keeps applying the keep-mask eagerly per chunk
    #: even when the optimizer marked the segment for mask deferral (output
    #: is byte-identical either way; only transfer counts differ on device
    #: backends)
    supports_segment_defer = False

    # ------------------------------------------------------------ array ops
    def asarray(self, x) -> np.ndarray:
        return np.asarray(x)

    def to_host(self, x) -> np.ndarray:
        return np.asarray(x)

    def concat(self, parts: Sequence) -> np.ndarray:
        return np.concatenate([np.asarray(p) for p in parts])

    # ------------------------------------------------------- operator kernels
    def filter_mask(self, predicate: Callable, cache, rows: slice) -> np.ndarray:
        return np.asarray(predicate(cache, rows), dtype=bool)

    def eval_expression(self, fn: Callable, cache, rows: slice) -> np.ndarray:
        return np.asarray(fn(cache, rows))

    def lookup(self, dim, vals, return_cols: Mapping[str, str], default,
               matched_flag: Optional[str] = None) -> Dict[str, np.ndarray]:
        idx, matched = dim.probe(np.asarray(vals))
        out = {}
        for out_name, dim_col in return_cols.items():
            col = dim.payload[dim_col]
            got = col[idx] if len(col) else np.zeros(len(idx), col.dtype)
            out[out_name] = np.where(matched, got,
                                     np.asarray(default, got.dtype))
        if matched_flag:
            out[matched_flag] = matched
        return out

    def groupby_reduce(self, keys: Sequence, values: Mapping[str, Tuple[object, str]],
                       n_rows: int, mask=None
                       ) -> Tuple[List[np.ndarray], Dict[str, np.ndarray]]:
        for out, (col, op) in values.items():
            if op not in AGG_OPS:
                raise ValueError(f"unknown agg op {op!r} for {out!r}")
        n = int(n_rows)
        if mask is not None:
            keep = np.asarray(mask, dtype=bool)
            keys = [np.asarray(k)[keep] for k in keys]
            values = {out: (np.asarray(col)[keep], op)
                      for out, (col, op) in values.items()}
            n = int(keep.sum())
        if n == 0:
            return no_groups(keys, values)
        if not keys:
            # global aggregation: one group over all rows
            aggs: Dict[str, np.ndarray] = {}
            for out, (col, op) in values.items():
                vals = np.asarray(col)
                if op == "count":
                    aggs[out] = np.array([n], dtype=np.int64)
                elif op == "sum":
                    aggs[out] = _sum(np.array([vals.astype(
                        _sum_dtype(vals)).sum()]))
                elif op == "avg":
                    total = vals.astype(_sum_dtype(vals)).sum()
                    aggs[out] = np.array([total / n])
                elif op == "min":
                    aggs[out] = np.array([vals.min()])
                elif op == "max":
                    aggs[out] = np.array([vals.max()])
            return [], aggs
        keys = [np.asarray(k) for k in keys]
        order = np.lexsort(keys[::-1])
        sk = [k[order] for k in keys]
        boundary = np.zeros(n, dtype=bool)
        boundary[0] = True
        for k in sk:
            boundary[1:] |= k[1:] != k[:-1]
        starts = np.flatnonzero(boundary)
        counts = np.diff(np.append(starts, n))
        group_cols = [k[starts] for k in sk]
        aggs = {}
        for out, (col, op) in values.items():
            if op == "count":
                aggs[out] = counts.astype(np.int64)
                continue
            vals = np.asarray(col)[order]
            if op in ("sum", "avg"):
                acc = np.add.reduceat(vals.astype(_sum_dtype(vals)), starts)
                aggs[out] = acc / counts if op == "avg" else _sum(acc)
            elif op == "min":
                aggs[out] = np.minimum.reduceat(vals, starts)
            elif op == "max":
                aggs[out] = np.maximum.reduceat(vals, starts)
        return group_cols, aggs

    def sort_rows(self, keys: Sequence, ascending: bool = True) -> np.ndarray:
        order = np.lexsort([np.asarray(k) for k in keys][::-1])
        return order if ascending else order[::-1]
