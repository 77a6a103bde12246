"""Accelerated operator backend: jitted JAX kernels + device-resident columns.

Kernels:
  - ``lookup`` — a dimension table's device form and the one traced read
    of it live in ``jax_dims``: slot ``key - min`` for dense integer keys,
    else fmix32 open addressing (``kernels/hash_join``), then one gather per
    returned column from slot-addressed arrays.  The unfused Lookup jits
    that read; the fused segment kernel inlines it.
  - ``groupby_reduce`` — dense integer key spaces route through
    ``kernels/radix_groupby`` (radix-partitioned one-hot matmul, no sort);
    sparse/non-integer/huge key spaces fall back to the legacy lexsort +
    ``kernels/segment_sum`` route (``REPRO_GROUPBY_IMPL=sort`` forces it;
    ``REPRO_SEGSUM_IMPL=interpret`` exercises the Pallas segment-sum body on
    CPU).  Integer sums and averages are exact: their inputs split into
    8-bit limbs summed in int32 in the same kernels' programs
    (``kernels/radix_groupby/exact.py``) and recombined on the host in
    int64.  Float sums accumulate in float32 — the MXU-native width — so
    engine-vs-oracle checks use ``oracle_rtol`` instead of float64 exactness.
  - ``filter_mask`` / ``eval_expression`` — user lambdas evaluated over a
    device view of the shared cache, so `c.col(...)` hands back jax arrays
    and the whole expression runs on device.
  - ``sort_rows`` — stable ``jnp.lexsort``.

Every host->device / device->host crossing is recorded in
``CacheStats`` (scoped ``record_transfer``) — the copy-cost
analogue of the paper's §3 scheme for the device tier.

Note: x64 stays disabled (jax default), so 64-bit host columns are
canonicalized to 32-bit on device; ``dtype_width`` reports the canonical
width so planner channel sizing matches what actually crosses an edge.  A
host integer column whose values do not fit raises ``IntRangeError``
naming it, at upload or pack, rather than wrap; an integer expression that
can leave int32 (bounded from its inputs' observed ranges) is computed in
``core.wideint``'s two-word form and kept as a ``WideColumn``.
"""
from __future__ import annotations

import os
import threading
import time
import weakref
from pathlib import Path
from typing import Callable, Dict, List, Mapping, Optional, Sequence, Tuple

import numpy as np

from ...obs import trace as obs_trace
from .. import config, faults, wideint
from ..expr import ColumnsView, Expr
from ..wideint import Wide, WideColumn
from ..shared_cache import (GLOBAL_ARENA, is_host_column,
                            record_segment_compile, record_transfer)
from .base import AGG_OPS, Backend, SegmentEnv
from .jax_dims import DimLookup, device_form


def _checkout_cache_dir() -> Optional[Path]:
    """``<checkout>/.jax_cache`` when ``repro`` is imported from a source
    checkout (``src/`` beside ``pyproject.toml``), else ``None``."""
    root = Path(__file__).resolve().parents[4]
    return root / ".jax_cache" if (root / "pyproject.toml").is_file() else None


#: persistent compile cache when ``JAX_COMPILATION_CACHE_DIR`` is unset: a
#: fixed directory in the source checkout (the path is part of every cache
#: key, so a per-run name would never hit).  An installed package has no
#: checkout, and then only ``JAX_COMPILATION_CACHE_DIR`` turns the cache on.
COMPILE_CACHE_DIR = _checkout_cache_dir()


def place_compile_cache(jax) -> None:
    """Turn on JAX's persistent compilation cache.  Where
    ``JAX_COMPILATION_CACHE_DIR`` (or ``jax_compilation_cache_dir``) is
    already set, JAX uses that and nothing is changed; otherwise the cache
    goes to ``COMPILE_CACHE_DIR``, if there is one."""
    if (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or jax.config.jax_compilation_cache_dir
            or COMPILE_CACHE_DIR is None):
        return
    jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE_DIR))
    # a compile before this point settled the cache as unused: re-check
    from jax.experimental.compilation_cache import compilation_cache
    compilation_cache.reset_cache()


def _narrow(col):
    """``col`` for a 32-bit device op: a ``WideColumn`` is refused."""
    if isinstance(col, WideColumn):
        raise NotImplementedError(
            f"min and max of a wide integer column ({col.bound[0]} to "
            f"{col.bound[1]}) are not supported on the device")
    return col


class _DeviceCacheView:
    """Read-only view of a SharedCache whose ``col`` returns device arrays
    (converted+cached on first touch), so user predicates/expressions written
    against the cache API compute on device.  One view is shared across a
    component's §4.3 row-range calls (see ``JaxBackend._view``), so each
    column is uploaded once per cache version, not once per range."""

    __slots__ = ("_backend", "_cache", "_cols", "_lock")

    def __init__(self, backend: "JaxBackend", cache):
        self._backend = backend
        self._cache = cache
        self._cols: Dict[str, object] = {}
        self._lock = threading.Lock()

    @property
    def n(self) -> int:
        return self._cache.n

    @property
    def names(self):
        return self._cache.names

    def col(self, name: str):
        got = self._cols.get(name)
        if got is None:
            with self._lock:       # concurrent row ranges: upload once
                got = self._cols.get(name)
                if got is None:
                    got = self._cols[name] = self._backend.asarray(
                        self._cache.col(name), name=name)
        return got

    def __getattr__(self, name):
        # API parity with SharedCache: anything beyond col/n/names
        # (split_index, columns, to_dict, ...) falls back to the underlying
        # cache — host compute, but the numpy-backend contract still holds
        return getattr(self._cache, name)


class JaxBackend(Backend):
    name = "jax"
    #: align chunks to the segment-sum row tile so jitted kernels see few
    #: distinct shapes (bounds retracing) and the Pallas grid has no ragged
    #: final tile in the common case
    batch_align = 512
    #: float32 accumulation (MXU width) vs the float64 oracles
    oracle_rtol = 1e-3
    #: fused row-sync chains may defer their combined keep-mask through a
    #: terminal Aggregate (the per-chunk d2h sync disappears; Aggregate.finish
    #: applies the mask once after the device-side concat)
    supports_segment_defer = True
    #: dense-groupby guards: past either, fall back to the sort route
    #: (float32 counts are exact below 2^24; the dense cell count bounds the
    #: group-id space the radix kernel partitions)
    _DENSE_MAX_ROWS = 1 << 24
    _DENSE_MAX_CELLS = 1 << 20
    #: the group-by's degradation ladder (left = fastest, right = safest):
    #: on a non-transient kernel failure the route walks ONE rung right and
    #: stays there for this backend instance's lifetime.  Every rung is
    #: bit-identical to its neighbours by the kernels' own equivalence tests.
    #: Interpret mode is no rung: on a chip it is a silent slowdown of orders
    #: of magnitude (it stays selectable explicitly, for CPU tests, and a
    #: failing explicit interpret route degrades like pallas).
    _GROUPBY_LADDER = ("pallas", "reference", "sort")

    def __init__(self) -> None:
        import jax                       # deferred: registry creates lazily
        import jax.numpy as jnp
        place_compile_cache(jax)
        from ...kernels.radix_groupby import radix_groupby
        from ...kernels.segment_sum import segment_sum
        self._jax = jax
        self._jnp = jnp
        self._segment_sum = segment_sum
        self._radix_groupby = radix_groupby
        self._segsum_impl = config.segsum_impl()
        self._lookup_jit = jax.jit(lambda look, vals: look(vals))
        # device views keyed by cache, invalidated by cache.version — a
        # stale view (pre-compact/add_column) is never reused
        self._views: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()
        self._views_lock = threading.Lock()
        # the sticky group-by rung; None => follow the env config
        self._groupby_route: Optional[str] = None

    def _degraded_impl(self, impl: str, exc: BaseException):
        """Next rung of the group-by ladder after ``impl`` failed with
        ``exc``, or ``None`` when the failure must propagate instead:
        transient faults escalate so chunk-level replay retries the SAME
        route; explicitly injected permanent/poison faults abort promptly;
        ``REPRO_DEGRADE=0`` disables ladders; the ladder floor has no next
        rung.  A chosen rung is recorded as a ``Degradation`` and sticks on
        this backend instance — later chunks skip the broken kernel."""
        if (faults.classify(exc) == "transient"
                or isinstance(exc, (faults.PermanentFault, faults.PoisonFault))
                or not config.degrade_enabled()):
            return None
        ladder = self._GROUPBY_LADDER
        impl = self._resolve_impl(impl)
        i = ladder.index(impl) if impl in ladder else 0   # interpret => rung 0
        if i + 1 >= len(ladder):
            return None
        nxt = ladder[i + 1]
        faults.record_degradation("kernel", src=f"groupby[{impl}]", dst=nxt,
                                  component="groupby", error=repr(exc))
        self._groupby_route = nxt
        return nxt

    def _resolve_impl(self, impl: str) -> str:
        """The rung ``auto`` stands for: the Pallas group-by on a TPU and
        its jnp reference elsewhere."""
        if impl != "auto":
            return impl
        return "pallas" if self._jax.default_backend() == "tpu" else "reference"

    def _view(self, cache) -> _DeviceCacheView:
        with self._views_lock:
            got = self._views.get(cache)
            if got is not None and got[0] == cache.version:
                return got[1]
            view = _DeviceCacheView(self, cache)
            self._views[cache] = (cache.version, view)
            return view

    # ------------------------------------------------------------ array ops
    def asarray(self, x, name: Optional[str] = None):
        """``x`` on the device.  A host integer column narrowed to 32 bits
        there must fit: else ``IntRangeError`` names it (``name``)."""
        if isinstance(x, np.ndarray):
            self._check_fits(x, name)
            # copy=True: jax on CPU zero-copies numpy arrays onto the
            # "device", aliasing the host memory — with CacheArena recycling
            # host buffers, an aliased device column would silently observe
            # the next borrower's bytes.  Forcing the copy restores the
            # ownership boundary the h2d accounting already models (real
            # accelerators copy on transfer regardless).
            with obs_trace.annotation("transfer", "h2d") as a:
                out = self._jnp.array(x, copy=True)
            record_transfer("h2d", x.nbytes, seconds=a.seconds)
            return out
        if isinstance(x, (self._jax.Array, WideColumn)):
            return x
        return self._jnp.asarray(x)

    def _check_fits(self, x: np.ndarray, name: Optional[str]) -> None:
        cd = np.dtype(self._jax.dtypes.canonicalize_dtype(x.dtype))
        if (x.size and np.issubdtype(x.dtype, np.integer)
                and cd.itemsize < x.dtype.itemsize):
            wideint.check_fits(name or "<unnamed>", cd,
                               *wideint.column_range(x))

    def _ranges(self, arrays: Sequence) -> List[Tuple[int, int]]:
        """``(min, max)`` of each integer device array, in one d2h."""
        if not arrays:
            return []
        jnp, lax = self._jnp, self._jax.lax
        rows = []
        for a in arrays:
            mm = (jnp.stack([jnp.min(a), jnp.max(a)]) if a.size
                  else jnp.zeros((2,), a.dtype))
            rows.append(lax.bitcast_convert_type(mm, jnp.int32)
                        if a.dtype == jnp.uint32 else mm.astype(jnp.int32))
        host = self.to_host(jnp.stack(rows))
        return [tuple(int(v) for v in (h.view(np.uint32)
                                       if a.dtype == jnp.uint32 else h))
                for a, h in zip(arrays, host)]

    def to_host(self, x) -> np.ndarray:
        if isinstance(x, np.ndarray):
            return x
        with obs_trace.annotation("transfer", "d2h") as a:
            out = np.asarray(x)
        record_transfer("d2h", out.nbytes, seconds=a.seconds)
        return out

    def concat(self, parts: Sequence):
        parts = list(parts)
        if len(parts) == 1:
            return self.asarray(parts[0])
        if any(isinstance(p, WideColumn) for p in parts):
            return WideColumn.concat([self._widen(p) for p in parts])
        return self._jnp.concatenate([self.asarray(p) for p in parts])

    def _widen(self, col) -> WideColumn:
        """An integer column as a ``WideColumn`` on the device."""
        if isinstance(col, WideColumn):
            return col
        if isinstance(col, np.ndarray):
            return wideint.from_host(col)
        w = wideint.widen(col)
        return WideColumn(w.lo, w.hi, self._ranges([col])[0])

    # --------------------------------------------------------------- sizing
    def dtype_width(self, dtype) -> int:
        # x64 disabled => int64/float64 host columns live as 4-byte device
        return int(np.dtype(self._jax.dtypes.canonicalize_dtype(dtype)).itemsize)

    def bucket_rows(self, n: int) -> int:
        """Pad target for a data-dependent row count: ``batch_align`` times
        the next power of two of the needed alignment units.  Keeps the
        number of DISTINCT jit shapes logarithmic in the row-count range —
        linear multiple-of-align bucketing retraces once per distinct chunk
        size, which under a resident serving session with varying tick sizes
        means unbounded warm-tick recompiles."""
        align = max(1, self.batch_align)
        units = max(1, -(-int(n) // align))
        return align * (1 << (units - 1).bit_length())

    # ---------------------------------------------------- DSL expression jit
    def _expr_runner(self, expr: Expr):
        """One jitted XLA computation per DSL expression: the whole AST
        traces into a single compiled kernel over exactly ``expr.columns()``
        device arrays — no host lambda round-trip, no per-op dispatch.  The
        compiled runner is cached on the expression node itself (expressions
        are long-lived component attributes), and jit's trace cache bounds
        retraces per argument shape."""
        got = expr.__dict__.get("_jax_compiled")
        if got is None:
            names = sorted(expr.columns())

            def run(*arrays):
                return expr.evaluate(ColumnsView(dict(zip(names, arrays))),
                                     slice(None))
            got = expr.__dict__["_jax_compiled"] = (names, self._jax.jit(run))
        return got

    def _eval_expr(self, expr: Expr, cache, rows: slice):
        """Run the jitted expression over the requested row range, padded to
        the backend's batch alignment so jit sees bucketed shapes — without
        this, every post-filter chunk (data-dependent length) would force a
        fresh XLA compile.  Safe because DSL ops are row-local: the zeroed
        pad rows are sliced off before anyone observes them.  Where a node
        can leave int32 (``wideint.plan`` over the inputs' observed
        ranges), the expression runs in the wide form instead."""
        jnp = self._jnp
        names, fn = self._expr_runner(expr)
        view = self._view(cache)
        cols = [view.col(name)[rows] for name in names]
        n = len(cols[0])
        bound, flags, _ = self._expr_plan(
            expr, names, [cache.col(name)[rows] for name in names], cols)
        if flags is not None:
            return self._eval_wide(expr, names, cols, flags, bound, n)
        pad = self.bucket_rows(n) - n
        if pad:
            cols = [jnp.concatenate(
                [c, jnp.zeros((pad,) + c.shape[1:], c.dtype)]) for c in cols]
        out = fn(*cols)
        return out[:n] if pad else out

    def _expr_plan(self, expr: Expr, names, sources, cols) -> wideint.Plan:
        """``wideint.plan`` of ``expr`` over the observed ranges of its
        input columns: ``sources`` as the cache holds them (host ranges
        on the host, one d2h for the device ones), ``cols`` on the
        device."""
        ranges, dev = {}, []
        for name, src, c in zip(names, sources, cols):
            if isinstance(c, WideColumn):
                ranges[name] = c.bound
            elif is_host_column(src):
                r = wideint.column_range(src)
                if r is not None:
                    ranges[name] = r
            elif c.dtype == np.bool_:
                ranges[name] = (0, 1)
            elif c.ndim == 1 and self._jnp.issubdtype(c.dtype,
                                                      self._jnp.integer):
                dev.append((name, c))
        ranges.update(zip([name for name, _ in dev],
                          self._ranges([c for _, c in dev])))
        return wideint.plan(expr, ranges, [
            name for name, c in zip(names, cols)
            if isinstance(c, WideColumn)])

    def _eval_wide(self, expr: Expr, names, cols, flags, bound, n: int):
        jnp = self._jnp
        wide_in = tuple(isinstance(c, WideColumn) for c in cols)
        key = (flags, wide_in)
        runners = expr.__dict__.setdefault("_jax_wide", {})
        fn = runners.get(key)
        if fn is None:
            def run(*arrays):
                return wideint.evaluate(
                    expr, ColumnsView(dict(zip(names, arrays))),
                    slice(None), flags)
            fn = runners[key] = self._jax.jit(run)
        pad = self.bucket_rows(n) - n

        def padded(a):
            return (jnp.concatenate([a, jnp.zeros((pad,), a.dtype)])
                    if pad else a)
        out = fn(*[Wide(padded(c.lo), padded(c.hi))
                   if isinstance(c, WideColumn) else padded(c)
                   for c in cols])
        if isinstance(out, Wide):
            return WideColumn(out.lo[:n], out.hi[:n], bound)
        return out[:n]

    # ------------------------------------------------------- operator kernels
    def filter_mask(self, predicate: Callable, cache, rows: slice):
        if isinstance(predicate, Expr) and predicate.columns():
            return self._eval_expr(predicate, cache, rows).astype(bool)
        mask = predicate(self._view(cache), rows)
        if isinstance(mask, np.ndarray):
            return mask.astype(bool)       # host-computed mask stays host
        # device array, or any sequence the numpy reference would accept
        return self._jnp.asarray(mask, dtype=bool)

    def eval_expression(self, fn: Callable, cache, rows: slice):
        if isinstance(fn, Expr) and fn.columns():
            return self._eval_expr(fn, cache, rows)
        out = fn(self._view(cache), rows)
        return out if isinstance(out, np.ndarray) else self._jnp.asarray(out)

    def lookup(self, dim, vals, return_cols: Mapping[str, str], default,
               matched_flag: Optional[str] = None) -> Dict[str, object]:
        look = DimLookup(device_form(dim, self.asarray), return_cols,
                         default, matched_flag)
        v = self.asarray(vals)
        n = v.shape[0]
        pad = self.bucket_rows(n) - n          # bound jit retraces per shape
        if pad:
            v = self._jnp.concatenate([v, self._jnp.zeros((pad,), v.dtype)])
        if faults.active():
            faults.inject("kernel", component="join")
        return {name: a[:n] for name, a in self._lookup_jit(look, v).items()}

    def groupby_reduce(self, keys: Sequence, values: Mapping[str, Tuple[object, str]],
                       n_rows: int) -> Tuple[List[object], Dict[str, object]]:
        for out, (col, op) in values.items():
            if op not in AGG_OPS:
                raise ValueError(f"unknown agg op {op!r} for {out!r}")
        jnp = self._jnp
        n = int(n_rows)
        if not keys:
            aggs: Dict[str, object] = {}
            cols, pos = self._exact_inputs(values)
            if cols:
                exact = self._exact_keyless(cols, n)
            for out, (col, op) in values.items():
                if op == "count":
                    aggs[out] = np.array([n], dtype=np.int64)
                    continue
                if out in pos:
                    s = exact[pos[out]]
                    aggs[out] = self._avg(s, [n]) if op == "avg" else s
                    continue
                vals = self.asarray(col)
                if op in ("sum", "avg"):
                    zeros = jnp.zeros((n,), dtype=jnp.int32)
                    s = self._segment_sum(zeros,
                                          vals.astype(jnp.float32)[:, None],
                                          1, impl=self._segsum_impl)[:, 0]
                    aggs[out] = self._avg(s, [n]) if op == "avg" else s
                elif op == "min":
                    aggs[out] = jnp.min(_narrow(vals))[None]
                elif op == "max":
                    aggs[out] = jnp.max(_narrow(vals))[None]
            return [], aggs
        keys_d = [self.asarray(k) for k in keys]
        impl = self._resolve_impl(self._groupby_route
                                  or config.groupby_impl())
        while impl != "sort":
            try:
                if faults.active():
                    faults.inject("kernel", component=f"groupby[{impl}]")
                dense = self._groupby_dense(keys_d, values, n, impl)
            except BaseException as e:
                nxt = self._degraded_impl(impl, e)
                if nxt is None:
                    raise
                impl = nxt
                continue
            if dense is not None:
                return dense
            break          # key space disqualified: legacy sort route
        order = jnp.lexsort(tuple(keys_d[::-1]))
        sk = [k[order] for k in keys_d]
        boundary = jnp.zeros((n,), dtype=bool).at[0].set(True)
        for k in sk:
            boundary = boundary.at[1:].set(boundary[1:] | (k[1:] != k[:-1]))
        seg = (jnp.cumsum(boundary) - 1).astype(jnp.int32)
        starts_h = np.flatnonzero(self.to_host(boundary))
        n_groups = len(starts_h)
        counts_h = np.diff(np.append(starts_h, n))
        starts = jnp.asarray(starts_h)
        group_cols = [k[starts] for k in sk]
        cols, pos = self._exact_inputs(values)
        if cols:
            cols = [c[order] for c in cols]
            _, _, exact = self._exact(
                self._segment_sum, seg, jnp.zeros((n, 0), jnp.float32),
                n_groups, cols, self._value_ranges(cols, []),
                impl=self._segsum_impl)
        aggs = {}
        for out, (col, op) in values.items():
            if op == "count":
                aggs[out] = counts_h.astype(np.int64)
                continue
            if out in pos:
                s = exact[pos[out]]
                aggs[out] = self._avg(s, counts_h) if op == "avg" else s
                continue
            vals = _narrow(self.asarray(col))[order]
            if op in ("sum", "avg"):
                # the repo's Pallas segment-sum op: one-hot matmul per row
                # tile on TPU, jnp segment_sum reference on CPU
                s = self._segment_sum(seg, vals.astype(jnp.float32)[:, None],
                                      n_groups, impl=self._segsum_impl)[:, 0]
                aggs[out] = self._avg(s, counts_h) if op == "avg" else s
            elif op == "min":
                aggs[out] = self._jax.ops.segment_min(vals, seg,
                                                      num_segments=n_groups)
            elif op == "max":
                aggs[out] = self._jax.ops.segment_max(vals, seg,
                                                      num_segments=n_groups)
        return group_cols, aggs

    def _exact_inputs(self, values: Mapping[str, Tuple[object, str]]
                      ) -> Tuple[list, Dict[str, int]]:
        """The distinct integer columns (by identity) that ``sum``/``avg``
        outputs read, on the device, and each such output's position among
        them: these are summed exactly."""
        cols: list = []
        seen: Dict[int, int] = {}
        pos: Dict[str, int] = {}
        for out, (col, op) in values.items():
            if op not in ("sum", "avg"):
                continue
            if id(col) not in seen:
                if not (isinstance(col, WideColumn)
                        or np.issubdtype(col.dtype, np.integer)):
                    continue
                seen[id(col)] = len(cols)
                cols.append(self.asarray(col))
            pos[out] = seen[id(col)]
        return cols, pos

    def _value_ranges(self, cols: list, ranges: List[Tuple[int, int]]
                      ) -> List[Tuple[int, int]]:
        """Each column's range: a ``WideColumn``'s bound, else the next of
        ``ranges`` (observed), or of one d2h where ``ranges`` is empty."""
        narrow = [c for c in cols if not isinstance(c, WideColumn)]
        it = iter(ranges or self._ranges(narrow))
        return [c.bound if isinstance(c, WideColumn) else next(it)
                for c in cols]

    def _exact(self, kernel, ids, vmat, n_groups: int, cols: list,
               ranges: List[Tuple[int, int]], **kw):
        """One group-by kernel call that also sums the integer ``cols``
        exactly, each less its minimum in 8-bit limbs: ``(float sums,
        counts, [exact int64 sums per column])``, the last two on the host
        (one d2h)."""
        offsets = [lo for lo, _ in ranges]
        limbs = tuple(wideint.limb_count(r) for r in ranges)
        ints = tuple((c.wide if isinstance(c, WideColumn) else c,
                      wideint.const(off)) for c, off in zip(cols, offsets))
        sums, counts, exact = kernel(ids, vmat, n_groups, ints=ints,
                                     limbs=limbs, **kw)
        host = self.to_host(self._jnp.concatenate(
            [counts[..., None], exact], axis=-1))
        if obs_trace.ACTIVE.get():
            obs_trace.counter(
                "exact", "groupby", rows=int(ids.shape[0]), columns=len(cols),
                limbs=sum(limbs),
                max_bits=max((hi - lo).bit_length() for lo, hi in ranges))
        counts_h = host[..., 0].astype(np.int64).sum(axis=0)
        return sums, counts_h, [
            wideint.exact_result(t) for t in wideint.recombine(
                host[..., 1:], host[..., 0], offsets, limbs)]

    def _exact_keyless(self, cols: list, n: int) -> List[np.ndarray]:
        """Exact int64 totals of the integer ``cols`` over all ``n``
        rows."""
        jnp = self._jnp
        _, _, exact = self._exact(
            self._segment_sum, jnp.zeros((n,), jnp.int32),
            jnp.zeros((n, 0), jnp.float32), 1, cols,
            self._value_ranges(cols, []), impl=self._segsum_impl)
        return exact

    def _avg(self, sums, counts) -> np.ndarray:
        """``sums / counts`` divided on the host, where IEEE division rounds
        once in the sum's dtype (float64 for the exact int64 sums).  The
        TPU's float32 divide is not correctly rounded, and serving emits and
        shard merges divide on the host, so this keeps every route's
        averages bit-identical."""
        s = self.to_host(sums)
        return s / np.asarray(counts).astype(s.dtype)

    def _groupby_dense(self, keys_d: List, values: Mapping[str, Tuple[object, str]],
                       n: int, impl: str):
        """Radix-partitioned groupby over a dense composite key id — no sort.

        Each key column is offset to zero and the tuple is flattened into one
        dense int32 id (FIRST key column most significant, so ascending id
        order IS the lexicographic group order the sort route emits).  All
        float sum/avg inputs stack into one [N, C] matrix and reduce in a
        single ``kernels/radix_groupby`` pass that also yields per-group
        counts; integer inputs are summed exactly in the same program, each
        distinct column once, offset by its minimum (the keys' min/max d2h
        carries the values' too).  Occupied cells are recovered from the
        counts (the only extra d2h) and group key columns are reconstructed
        arithmetically from the cell ids — the row data is never sorted and
        never leaves the device.

        Returns ``None`` when the key space doesn't qualify (empty input,
        non-integer keys, cell count past the VMEM-scaled bound, row count
        past float32-count exactness) — the caller falls back to the sort
        route.
        """
        jnp = self._jnp
        if n == 0 or n >= self._DENSE_MAX_ROWS:
            return None
        for k in keys_d:
            if not jnp.issubdtype(k.dtype, jnp.integer):
                return None
        cols, pos = self._exact_inputs(values)
        # one d2h for every key's and integer input's min/max
        narrow = [c for c in cols if not isinstance(c, WideColumn)]
        lo_hi = self._ranges(keys_d + narrow)
        mins = [lo for lo, _ in lo_hi[:len(keys_d)]]
        ranges = [hi - lo + 1 for lo, hi in lo_hi[:len(keys_d)]]
        cells = 1
        for r in ranges:
            cells *= r
            if cells > self._DENSE_MAX_CELLS:
                return None
        strides = [1] * len(keys_d)
        for i in range(len(keys_d) - 2, -1, -1):
            strides[i] = strides[i + 1] * ranges[i + 1]
        ids = jnp.zeros((n,), jnp.int32)
        for k, mn, st in zip(keys_d, mins, strides):
            ids = ids + (k.astype(jnp.int32) - mn) * st

        sum_outs = [out for out, (_, op) in values.items()
                    if op in ("sum", "avg") and out not in pos]
        mat = [self.asarray(values[out][0]).astype(jnp.float32)
               for out in sum_outs]
        vmat = (jnp.stack(mat, axis=1) if mat
                else jnp.zeros((n, 0), jnp.float32))
        if cols:
            sums, counts_h, exact = self._exact(
                self._radix_groupby, ids, vmat, cells, cols,
                self._value_ranges(cols, lo_hi[len(keys_d):]), impl=impl)
        else:
            sums, counts = self._radix_groupby(ids, vmat, cells, impl=impl)
            counts_h = np.rint(self.to_host(counts)).astype(np.int64)  # d2h
        occ = np.flatnonzero(counts_h)
        occ_d = jnp.asarray(occ.astype(np.int32))
        group_cols = [((occ_d // st) % rg + mn).astype(k.dtype)
                      for k, mn, st, rg in zip(keys_d, mins, strides, ranges)]
        aggs: Dict[str, object] = {}
        for out, (col, op) in values.items():
            if op == "count":
                aggs[out] = counts_h[occ]
            elif out in pos:
                s = exact[pos[out]][occ]
                aggs[out] = self._avg(s, counts_h[occ]) if op == "avg" else s
            elif op in ("sum", "avg"):
                s = sums[occ_d, sum_outs.index(out)]
                aggs[out] = self._avg(s, counts_h[occ]) if op == "avg" else s
            else:  # min / max: one segment reduce over the dense ids
                fn = (self._jax.ops.segment_min if op == "min"
                      else self._jax.ops.segment_max)
                aggs[out] = fn(_narrow(self.asarray(col)), ids,
                               num_segments=cells)[occ_d]
        return group_cols, aggs

    def sort_rows(self, keys: Sequence, ascending: bool = True):
        order = self._jnp.lexsort(tuple(self.asarray(k) for k in keys)[::-1])
        return order if ascending else order[::-1]

    # ------------------------------------------------------- segment fusion
    def compile_segment(self, segment) -> Callable:
        """One jitted kernel for the whole row-synchronized segment: the
        needed host input columns are packed into a single staging buffer
        (ONE h2d per chunk), every fused op runs on device inside one XLA
        computation with the filter masks deferred to a single combined
        keep-mask (the only d2h per chunk), and the produced columns stay
        device-resident for downstream consumers.  Tracing is bounded by a
        compile cache keyed on the packed layout (column names x canonical
        dtypes x padded chunk-size bucket) — jit's own trace cache keys on
        exactly that layout, so steady-state chunks replay a compiled
        executable with zero retracing."""
        return _JaxSegmentRunner(self, segment)


class _JaxSegmentRunner:
    """Compiled executor for one FusedSegment on the jax backend.

    Deferred-mask semantics: row-synchronized ops are row-local by the
    paper's §3 classification (each output row depends only on its own input
    row), so filters are evaluated as masks over the full padded chunk, ANDed
    into one keep-mask, and applied once at write-back — values of surviving
    rows are identical to the eagerly-compacted unfused chain."""

    def __init__(self, backend: "JaxBackend", segment):
        from .base import segment_final_live, segment_written_columns
        self._bk = backend
        self._jnp = backend._jnp
        self._jax = backend._jax
        self.ops = list(segment.ops)
        #: external columns the kernel needs uploaded; None => every cache
        #: column (some op has an undeclared read set)
        self.inputs = segment.kernel_input_columns()
        self._written = segment_written_columns(self.ops)
        self._final_live = segment_final_live
        #: mask deferral: when the optimizer fused this chain through its
        #: terminal Aggregate, skip the per-chunk compact (the chunk's only
        #: d2h) and hand the keep-mask downstream as a sentinel column
        self.defer_mask = bool(getattr(segment, "defer_cols", None))
        #: the terminal Aggregate's input columns: all a deferring segment
        #: hands on (its only consumer drops every other column)
        self._defer_cols = frozenset(getattr(segment, "defer_cols", None)
                                     or ())
        #: columns the kernel returns: those it writes, and the Aggregate's
        #: inputs it only reads, already on the device (so the Aggregate
        #: merges no host copy of them and uploads none again)
        self._outputs = self._written + sorted(
            self._defer_cols - set(self._written))
        #: per Lookup, in op order: the name of its scope and probe counter,
        #: and its key column
        self._lookup_names = [op[1].name or op[2] for op in self.ops
                              if op[0] == "lookup"]
        self._lookup_keys = [op[2] for op in self.ops if op[0] == "lookup"]
        #: columns the segment's DSL filters and expressions read
        self._expr_reads = frozenset().union(*(
            fn.columns() for fn in (op[1] if op[0] == "filter" else op[2]
                                    for op in self.ops
                                    if op[0] in ("filter", "expr"))
            if isinstance(fn, Expr)))
        self._jit = backend._jax.jit(self._kernel, static_argnums=(0,))
        self._layouts: set = set()
        #: per layout, ``(program, layout key, {ENTRY op: named scope})`` of
        #: the compiled kernel; built only while a tracer is in scope
        self._scope_maps: Dict[tuple, tuple] = {}
        #: per Lookup, in op order, its ``DimLookup`` (``device_dims``)
        self._dims: Optional[List[DimLookup]] = None
        #: (DimTable id, payload column) -> the payload's observed range
        self._payload_ranges: Dict[tuple, Optional[Tuple[int, int]]] = {}
        self.kernel_calls = 0

    # ----------------------------------------------------------- the kernel
    def _kernel(self, layout, packed, dev_cols, dims):
        # every op runs under a stable jax.named_scope (trace-time metadata
        # only: the compiled program is the same) so the profiler's device
        # ops can be traced back to the Lookup, filter or expression
        jnp = self._jnp
        scope = self._jax.named_scope
        bucket, entries = layout[:2]
        # the ops with a node past int32 (``_wide_plan``): preorder flags
        wide = dict(layout[2][0]) if len(layout) > 2 else {}
        env: Dict[str, object] = {}
        with scope("unpack"):
            for (name, dtype_str, off) in entries:
                dt = np.dtype(dtype_str)
                nb = bucket * dt.itemsize
                raw = packed[off:off + nb]
                if dt == np.bool_:
                    env[name] = raw != 0
                elif dt.itemsize == 1:
                    env[name] = self._jax.lax.bitcast_convert_type(raw, dt)
                else:
                    env[name] = self._jax.lax.bitcast_convert_type(
                        raw.reshape(bucket, dt.itemsize), dt)
        env.update(dev_cols)

        masks = []
        dim_i = 0
        rows = slice(None)
        for i, op in enumerate(self.ops):
            view = SegmentEnv(env.__getitem__, set(env), bucket)
            kind = op[0]
            if kind == "filter":
                name = f"filter.{len(masks)}"
                with scope(name):
                    if i in wide:
                        with scope(f"wide.{name}"):
                            m = wideint.evaluate(op[1], view, rows, wide[i])
                    else:
                        m = op[1](view, rows)
                    masks.append(jnp.asarray(m, dtype=bool))
            elif kind == "expr":
                with scope(f"expr.{op[1]}"):
                    if i in wide:
                        with scope(f"wide.{op[1]}"):
                            v = wideint.evaluate(op[2], view, rows, wide[i])
                        env[op[1]] = v if isinstance(v, Wide) \
                            else jnp.asarray(v)
                    else:
                        env[op[1]] = jnp.asarray(op[2](view, rows))
            elif kind == "lookup":
                with scope(f"lookup.{self._lookup_names[dim_i]}"):
                    env.update(dims[dim_i](env[op[2]]))
                dim_i += 1
            elif kind == "project":
                keep = set(op[1])
                for k in list(env):
                    if k not in keep:
                        del env[k]
            elif kind == "convert":
                for col, dt in op[1].items():
                    env[col] = env[col].astype(dt)
            else:  # pragma: no cover
                raise ValueError(f"unknown segment op kind {kind!r}")

        keep_mask = None
        with scope("mask"):
            for m in masks:
                keep_mask = m if keep_mask is None else (keep_mask & m)
        out = {name: env[name] for name in self._outputs if name in env}
        return out, keep_mask

    # ------------------------------------------------------------ execution
    def pack_layout(self, bucket: int, columns) -> Tuple[list, int]:
        """Staging-buffer layout of ``(name, host dtype)`` columns padded to
        ``bucket`` rows: ``([(name, device dtype str, byte offset)], total
        bytes)`` — the static half of the kernel's layout key."""
        entries = []
        off = 0
        for name, dtype in columns:
            cd = np.dtype(self._jax.dtypes.canonicalize_dtype(dtype))
            entries.append((name, cd.str, off))
            off += bucket * cd.itemsize
        return entries, off

    def device_dims(self) -> List[DimLookup]:
        """Each Lookup's read of its table's device form, in op order: the
        form is built and uploaded once per table (cached on the table),
        and the list once per runner, so the kernel's argument pytree is
        the same every call and per-chunk Python cost stays flat."""
        if self._dims is None:
            self._dims = [DimLookup(device_form(op[1], self._bk.asarray),
                                    *op[3:])
                          for op in self.ops if op[0] == "lookup"]
        return self._dims

    def __call__(self, cache) -> None:
        bk = self._bk
        jnp = self._jnp
        n = cache.n
        bucket = bk.bucket_rows(n)

        names = (sorted(self.inputs) if self.inputs is not None
                 else sorted(cache.names))
        packable = []              # 1-D host columns -> one staging buffer
        dev_cols: Dict[str, object] = {}
        wide_in: Dict[str, Tuple[int, int]] = {}
        for name in names:
            v = cache.col(name)
            if is_host_column(v) and v.ndim == 1:
                packable.append((name, v))
            else:
                # device-resident (or multi-dim host) input: pad to the
                # bucket on device so the kernel sees one shape per layout
                dev = bk.asarray(np.ascontiguousarray(v)
                                 if is_host_column(v) else v, name=name)
                pad = bucket - n

                def padded(a):
                    return (jnp.concatenate(
                        [a, jnp.zeros((pad,) + a.shape[1:], a.dtype)])
                        if pad else a)
                if isinstance(dev, WideColumn):
                    wide_in[name] = dev.bound
                    dev_cols[name] = Wide(padded(dev.lo), padded(dev.hi))
                else:
                    dev_cols[name] = padded(dev)
        ranges = self._input_ranges(packable, dev_cols, wide_in)
        wide_ops, wide_out, wide_counts = self._wide_plan(ranges, wide_in)

        # pack every 1-D host input into ONE staging buffer (canonical
        # device dtypes, zeroed pad tail) and upload it with a single h2d
        entries, total = self.pack_layout(
            bucket, [(name, v.dtype) for name, v in packable])
        if total:
            staging, root = GLOBAL_ARENA.acquire(np.uint8, (total,))
            with obs_trace.span("transfer", "h2d.pack", bytes=total):
                for (name, v), (_, dtype_str, off) in zip(packable, entries):
                    cd = np.dtype(dtype_str)
                    dst = staging[off:off + bucket * cd.itemsize].view(cd)
                    np.copyto(dst[:n], v, casting="same_kind")
                    dst[n:] = 0
            # copy=True + block: the device buffer must not alias the
            # staging memory, which goes straight back to the arena.  The
            # h2d transfer covers the upload and the wait, and the wait also
            # holds the device's queue ahead of the upload
            t0 = time.perf_counter() if obs_trace.ACTIVE.get() else 0.0
            with obs_trace.span("transfer", "h2d.upload"):
                packed = jnp.array(staging, copy=True)
            with obs_trace.span("wait", "h2d.ready"):
                packed.block_until_ready()
            record_transfer("h2d", total,
                            seconds=(time.perf_counter() - t0) if t0 else 0.0)
            GLOBAL_ARENA.release(root)
        else:
            packed = jnp.zeros((0,), np.uint8)

        layout = (bucket, tuple(entries))
        if wide_ops or wide_in:
            layout += ((wide_ops, tuple(sorted(wide_in))),)
        dims = self.device_dims()
        if layout not in self._layouts:
            # a layout never seen by this runner => the jit call below traces
            # and compiles a fresh executable for it
            self._layouts.add(layout)
            record_segment_compile()
        final_live = self._final_live(self.ops, cache.names)
        with obs_trace.span("dispatch", "segment"):
            out_cols, keep_mask = self._jit(layout, packed, dev_cols, dims)
            # the live outputs less the bucket's pad rows (dispatches too)
            out_cols = {name: (WideColumn(out_cols[name].lo[:n],
                                          out_cols[name].hi[:n],
                                          wide_out[name])
                               if name in wide_out else out_cols[name][:n])
                        for name in self._outputs
                        if name in out_cols and name in final_live}
            if keep_mask is not None:
                keep_mask = keep_mask[:n]
        self.kernel_calls += 1
        if obs_trace.ACTIVE.get():
            self._trace_call(layout, (packed, dev_cols, dims), n, bucket,
                             dict(packable), wide_counts)

        for name, col in out_cols.items():
            cache.add_column(name, col)
        if self.defer_mask:
            # fused-through-Aggregate: the per-chunk compact (this chunk's
            # ONLY d2h) is deferred — the keep-mask rides along as a device
            # sentinel column and Aggregate.finish applies it once to the
            # merged cache, and no column the Aggregate does not read goes
            # into its merge
            from .base import SEGMENT_KEEP_MASK
            final_live = final_live & self._defer_cols
            if keep_mask is not None:
                cache.add_column(SEGMENT_KEEP_MASK, keep_mask)
                final_live = final_live | {SEGMENT_KEEP_MASK}
            if final_live != set(cache.names):
                cache.keep_columns(
                    [k for k in cache.names if k in final_live])
            return
        if keep_mask is not None:
            cache.compact(keep_mask)
        if final_live != set(cache.names):
            cache.keep_columns([k for k in cache.names if k in final_live])

    def _input_ranges(self, packable, dev_cols, wide_in
                      ) -> Dict[str, Tuple[int, int]]:
        """Observed ranges of the call's integer inputs: host columns by
        their min and max (a column narrowed to 32 bits whose values do not
        fit raises ``IntRangeError`` naming it), wide inputs by their
        bounds, and the device integer inputs an expression reads by one
        d2h."""
        ranges = dict(wide_in)
        for name, v in packable:
            if v.dtype == np.bool_:
                ranges[name] = (0, 1)
            elif np.issubdtype(v.dtype, np.integer) and v.size:
                ranges[name] = r = wideint.column_range(v)
                cd = np.dtype(self._jax.dtypes.canonicalize_dtype(v.dtype))
                if cd.itemsize < v.dtype.itemsize:
                    wideint.check_fits(name, cd, *r)
        dev = [(name, a) for name, a in dev_cols.items()
               if name in self._expr_reads and not isinstance(a, Wide)
               and a.ndim == 1 and np.issubdtype(a.dtype, np.integer)]
        ranges.update(zip([name for name, _ in dev],
                          self._bk._ranges([a for _, a in dev])))
        return ranges

    def _payload_range(self, dim, col: str) -> Optional[Tuple[int, int]]:
        key = (id(dim), col)
        if key not in self._payload_ranges:
            self._payload_ranges[key] = wideint.column_range(
                dim.payload[col])
        return self._payload_ranges[key]

    def _wide_plan(self, ranges, wide_in):
        """Which ops compute a node past int32, from the inputs' observed
        ranges carried through the segment's expressions and Lookups:
        ``(((op index, wideint flags), ...), {wide output column: bound},
        [(counter name, bits, limbs)])``.  Empty where every node fits
        int32: the kernel is then the one compiled before."""
        ranges, wide = dict(ranges), set(wide_in)
        flags, bounds, counts = [], {}, []
        n_filters = 0
        for i, op in enumerate(self.ops):
            kind = op[0]
            if kind in ("filter", "expr"):
                fn = op[1] if kind == "filter" else op[2]
                name = f"filter.{n_filters}" if kind == "filter" else op[1]
                n_filters += kind == "filter"
                p = (wideint.plan(fn, ranges, wide)
                     if isinstance(fn, Expr) else None)
                if p is not None and p.flags:
                    flags.append((i, p.flags))
                    counts.append((name, p.bits, -(-p.bits // 8)))
                if kind == "expr":
                    ranges.pop(name, None)
                    wide.discard(name)
                    bounds.pop(name, None)
                    if p is not None and p.bound is not None:
                        ranges[name] = p.bound
                    if p is not None and p.flags and p.flags[0]:
                        wide.add(name)
                        bounds[name] = p.bound
            elif kind == "lookup":
                _, dim, key_col, return_cols, default, matched_flag = op
                if key_col in wide:
                    raise NotImplementedError(
                        f"Lookup key {key_col!r} is a wide integer column")
                for out, dcol in return_cols.items():
                    r = self._payload_range(dim, dcol)
                    ranges.pop(out, None)
                    wide.discard(out)
                    if r is not None and isinstance(default, (int,
                                                              np.integer)):
                        ranges[out] = (min(r[0], int(default)),
                                       max(r[1], int(default)))
                if matched_flag:
                    ranges[matched_flag] = (0, 1)
                    wide.discard(matched_flag)
            elif kind == "convert":
                for col in op[1]:
                    if col in wide:
                        raise NotImplementedError(
                            f"convert of wide integer column {col!r}")
                    ranges.pop(col, None)
        return tuple(flags), bounds, counts

    def _scope_map(self, layout, args) -> tuple:
        """``(program, layout key, {ENTRY op: named scope})`` of the kernel
        compiled for ``layout``, parsed once per layout from the compiled
        text (a lower and compile that hit jit's caches: XLA compiles
        nothing)."""
        got = self._scope_maps.get(layout)
        if got is None:
            with obs_trace.span("program", "scopes.build"):
                text = self._jit.lower(layout, *args).compile().as_text()
            program, ops = obs_trace.entry_scopes(text)
            bucket, entries = layout[:2]
            key = f"{bucket}:{','.join(name for name, _, _ in entries)}"
            got = self._scope_maps[layout] = (program, key, ops)
        return got

    def _trace_call(self, layout, args, n: int, bucket: int,
                    host_cols: Dict[str, np.ndarray],
                    wide_counts: Sequence[tuple] = ()) -> None:
        """A traced call's events: which compiled op belongs to which scope;
        per widened expression (or filter) the rows, the two's-complement
        bits of its widest node and the 8-bit limbs those bits make; and
        per Lookup the rows probed and its ``DimLookup.counts``: the passes
        its probe ran over them (1 on a ``direct`` table), the gathers a row
        makes outside that loop, and, where its key column is a host input
        of the call, the passes they need (``need``, walked on the host)."""
        program, key, ops = self._scope_map(layout, args)
        obs_trace.instant("program", "scopes", program=program, layout=key,
                          ops=ops)
        for name, bits, limbs in wide_counts:
            obs_trace.counter("wide", name, rows=n, bits=bits, limbs=limbs)
        with obs_trace.span("program", "probe.count"):
            for name, key_col, look in zip(
                    self._lookup_names, self._lookup_keys, self.device_dims()):
                vals = (host_cols.get(key_col)
                        if key_col not in self._written else None)
                obs_trace.counter("probe", name, rows=n, padded_rows=bucket,
                                  **look.counts(vals))

    def stats(self) -> Dict[str, int]:
        return {"kernel_calls": self.kernel_calls,
                "layouts": len(self._layouts)}
