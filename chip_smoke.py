"""Chip smoke test: the SSB dataflows at SF1 shapes through ``Session`` on a TPU.

    python chip_smoke.py             # one chip: the group-by kernels' sums,
                                     # every flow fused and unfused, then 8
                                     # resident-serving ticks
    python chip_smoke.py --chips 4   # four chips: Q4.1 over a 4-device mesh
                                     # beside the serial run

The flows' data is SSB at scale factor 1 (O'Neil et al.: lineorder 6M rows,
customer 30k, supplier 2k, part 200k), generated from ``--seed``.  Every sink
is checked against its query's independent numpy oracle within the jax
backend's ``oracle_rtol``; any degradation, retry or injected fault fails
the run.  Per flow and mode it prints rows, the oracle check, cold and warm
wall time, h2d/d2h counts, XLA compiles and the device's
``peak_bytes_in_use``.  These are smoke timings on the host clock (cold
includes compilation), not benchmark results.

The last line of a passing run is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Without a TPU, or on any failure, it exits non-zero and prints no such line.
The phase functions take the row count, so tests run them on the CPU at a
tiny size; only ``main()`` insists on a TPU.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import traceback
from pathlib import Path
from typing import Dict, List, Optional

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import numpy as np  # noqa: E402

import repro  # noqa: E402
from repro.etl import BUILDERS  # noqa: E402
from repro.etl.components import ArraySource  # noqa: E402
from repro.etl.ssb import generate  # noqa: E402

#: SSB scale factor 1 (O'Neil et al., "Star Schema Benchmark"): lineorder
#: SF x 6M rows, customer SF x 30k, supplier SF x 2k, part 200k
SF1_LINEORDER_ROWS = 6_000_000
SF1_DIMS = {"customers": 30_000, "suppliers": 2_000, "parts": 200_000}
SERVE_TICKS = 8
SERVE_TICK_ROWS = 262_144
KERNEL_ROWS = 262_144


class CompileCounter:
    """Counts XLA backend compiles (persistent-cache hits excluded) through
    ``jax.monitoring``, so a phase can tell a warm run from one that
    recompiled."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.count = 0

    def __call__(self, event: str, duration: float, **_) -> None:
        if event == self.EVENT:
            self.count += 1

    def register(self) -> "CompileCounter":
        import jax
        jax.monitoring.register_event_duration_secs_listener(self)
        return self


def make_data(lineorder_rows: int = SF1_LINEORDER_ROWS, seed: int = 42):
    """SSB tables with SF1 dimension sizes and ``lineorder_rows`` facts."""
    return generate(lineorder_rows=lineorder_rows, seed=seed, **SF1_DIMS)


# ---------------------------------------------------------------------------
#  checks
# ---------------------------------------------------------------------------
def compare(got: Dict[str, np.ndarray], expect: Dict[str, np.ndarray],
            rtol: float) -> Optional[str]:
    """``None`` when ``got`` matches ``expect``: integer oracle columns
    exactly, the others within ``rtol``; else what differs."""
    for k, e in expect.items():
        if k not in got:
            return f"column {k!r} missing (have {sorted(got)})"
        g, e = np.asarray(got[k]), np.asarray(e)
        if g.shape != e.shape:
            return f"column {k!r}: shape {g.shape} != oracle {e.shape}"
        if e.dtype.kind in "iu":
            if not np.array_equal(g, e):
                return f"column {k!r}: integer values differ from the oracle"
        elif not np.allclose(g, e, rtol=rtol, atol=0.0):
            return (f"column {k!r}: max relative error "
                    f"{max_rel_err(g, e):.3g} > rtol {rtol}")
    return None


def max_rel_err(got, expect) -> float:
    g = np.asarray(got, dtype=np.float64)
    e = np.asarray(expect, dtype=np.float64)
    if e.size == 0:
        return 0.0
    return float(np.max(np.abs(g - e) / np.maximum(np.abs(e), 1e-30)))


def table_rel_err(got, expect) -> float:
    return max((max_rel_err(got[k], e) for k, e in expect.items()
                if np.asarray(e).dtype.kind == "f" and k in got
                and np.shape(got[k]) == np.shape(e)), default=0.0)


def run_faults(stats) -> Optional[str]:
    """Degradations, retries or injected faults of a run (an ``EngineRun``
    or a cache-stats dict), or ``None`` when all are zero."""
    get = (stats.get if isinstance(stats, dict)
           else lambda k, d=0: getattr(stats, k, d))
    bad = {k: get(k, 0) for k in ("degradations", "retries",
                                  "faults_injected")}
    if any(bad.values()):
        events = getattr(stats, "degradation_events", None)
        return f"{bad}" + (f" {events}" if events else "")
    return None


def peak_bytes() -> Optional[int]:
    import jax
    stats = jax.devices()[0].memory_stats()
    return None if not stats else stats.get("peak_bytes_in_use")


def _emit(**fields) -> None:
    print("smoke " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


def _timed_run(session, qf, compiles: Optional[CompileCounter], **kw):
    import jax
    before = compiles.count if compiles else 0
    t0 = time.perf_counter()
    res = session.run(qf, engine="streaming", **kw)
    jax.block_until_ready(res.table)
    wall = time.perf_counter() - t0
    return res, wall, (compiles.count - before if compiles else None)


# ---------------------------------------------------------------------------
#  phases
# ---------------------------------------------------------------------------
def run_kernel_sums(rows: int = KERNEL_ROWS, seed: int = 42) -> List[str]:
    """The group-by kernels' one-hot matmul sums (``radix_groupby_pallas``,
    ``segment_sum_pallas``) against float64 numpy sums, at the kernels' own
    precision and, for the record, at ``Precision.DEFAULT``.  The first 256
    groups hold one row each, valued at an odd integer from 257 up, which
    bfloat16 cannot represent (an error of 1.3e-3 to 3.9e-3); the other 256
    hold many profit-like values.  Off a TPU the Pallas bodies run in
    interpret mode.  Returns failures."""
    import jax
    import jax.numpy as jnp
    from repro.core.backend import resolve_backend
    from repro.kernels.radix_groupby.kernel import radix_groupby_pallas
    from repro.kernels.segment_sum.kernel import segment_sum_pallas
    rtol = resolve_backend("jax").oracle_rtol
    interpret = jax.default_backend() != "tpu"
    singles, n_groups = 256, 512
    rng = np.random.default_rng(seed)
    ids = np.concatenate([np.arange(singles),
                          rng.integers(singles, n_groups, rows - singles)])
    vals = np.concatenate([257 + 2 * np.arange(singles),
                           rng.integers(-100_000, 1_000_000, rows - singles)])
    expect = np.bincount(ids, weights=vals.astype(np.float64),
                         minlength=n_groups)
    ids_d = jnp.asarray(ids.astype(np.int32))
    vals_d = jnp.asarray(vals.astype(np.float32)[:, None])
    kernels = {
        "radix_groupby": lambda prec: radix_groupby_pallas(
            ids_d, vals_d, n_groups, interpret=interpret,
            **prec)[0][:, 0],
        "segment_sum": lambda prec: segment_sum_pallas(
            ids_d, vals_d, n_groups, interpret=interpret, **prec)[:, 0],
    }
    failures: List[str] = []
    for name, fn in kernels.items():
        try:
            err = max_rel_err(fn({}), expect)
            err_default = max_rel_err(
                fn({"precision": jax.lax.Precision.DEFAULT}), expect)
            if not err <= rtol:
                failures.append(f"kernel {name}: max relative error {err:.3g}"
                                f" > rtol {rtol}")
            _emit(kernel=name, rows=rows, groups=n_groups,
                  oracle="ok" if err <= rtol else "FAIL", max_rel_err=err,
                  max_rel_err_at_default_precision=err_default)
        except Exception:
            traceback.print_exc()
            failures.append(f"kernel {name}: raised")
    return failures


def run_flows(data, fuse: bool, queries=None,
              compiles: Optional[CompileCounter] = None) -> List[str]:
    """Every BUILDERS flow through ``Session(backend="jax").run`` (streaming
    engine), cold then warm on the same flow object.  Returns failures."""
    from repro.core.backend import resolve_backend
    rtol = resolve_backend("jax").oracle_rtol
    mode = "fused" if fuse else "unfused"
    rows = len(data.lineorder["lo_orderkey"])
    failures: List[str] = []
    for name in (queries or BUILDERS):
        label = f"{name}/{mode}"
        try:
            qf = BUILDERS[name](data)
            expect = qf.oracle(data)
            session = repro.Session(backend="jax", metadata=None)
            cold, cold_s, cold_c = _timed_run(session, qf, compiles, fuse=fuse)
            warm, warm_s, warm_c = _timed_run(session, qf, compiles, fuse=fuse)
            oracle_ok = True
            for which, res in (("cold", cold), ("warm", warm)):
                bad = compare(res.table, expect, rtol)
                if bad:
                    oracle_ok = False
                    failures.append(f"{label} {which}: {bad}")
                bad = run_faults(res.run)
                if bad:
                    failures.append(f"{label} {which}: {bad}")
            _emit(flow=name, mode=mode, rows=rows,
                  out_rows=len(next(iter(expect.values()))),
                  oracle="ok" if oracle_ok else "FAIL",
                  max_rel_err=table_rel_err(warm.table, expect),
                  cold_s=cold_s, warm_s=warm_s,
                  h2d=warm.run.h2d_transfers, d2h=warm.run.d2h_transfers,
                  xla_compiles_cold=cold_c, xla_compiles_warm=warm_c,
                  peak_bytes=peak_bytes())
        except Exception:
            traceback.print_exc()
            failures.append(f"{label}: raised")
    return failures


def serving_oracle(lineorder, customer) -> Dict[str, np.ndarray]:
    """numpy reference of the serving flow: region < 3, profit in units of
    10k, grouped by customer nation (customer keys are dense 1..N)."""
    nation = customer["c_nation"][lineorder["lo_custkey"] - 1]
    region = customer["c_region"][lineorder["lo_custkey"] - 1]
    m = region < 3
    profit = (lineorder["lo_revenue"] - lineorder["lo_supplycost"]) // 10_000
    keys, inv = np.unique(nation[m], return_inverse=True)
    sums = np.bincount(inv, weights=profit[m])
    counts = np.bincount(inv)
    return {"c_nation": keys, "profit": sums, "avg_profit": sums / counts,
            "orders": counts}


def run_serving(data, ticks: int = SERVE_TICKS,
                tick_rows: int = SERVE_TICK_ROWS, fuse: bool = False,
                compiles: Optional[CompileCounter] = None) -> List[str]:
    """The serving flow of ``benchmarks/serving.py`` through
    ``Session.serve`` for ``ticks`` ticks of ``tick_rows`` lineorder rows.
    Replayed deltas must equal the batch run over the same rows and the
    numpy oracle; warm ticks must neither recompile a segment nor re-upload
    a dimension table.  Returns failures."""
    import jax
    from benchmarks.serving import build_flow
    from repro.core.backend import resolve_backend
    rtol = resolve_backend("jax").oracle_rtol
    mode = "fused" if fuse else "unfused"
    label = f"serve/{mode}"
    n = ticks * tick_rows
    lo = {c: a[:n] for c, a in data.lineorder.items()}
    if len(lo["lo_orderkey"]) != n:
        return [f"{label}: needs {n} lineorder rows"]
    failures: List[str] = []
    try:
        session = repro.Session(backend="jax", metadata=None)
        results, walls, tick_compiles = [], [], []
        with session.serve(build_flow(data), fuse=fuse) as srv:
            for t in range(ticks):
                batch = {c: a[t * tick_rows:(t + 1) * tick_rows]
                         for c, a in lo.items()}
                before = compiles.count if compiles else 0
                t0 = time.perf_counter()
                r = srv.tick(batch)
                jax.block_until_ready(r.delta)
                walls.append(time.perf_counter() - t0)
                tick_compiles.append(
                    compiles.count - before if compiles else None)
                results.append(r)
        for r in results:
            bad = run_faults(r.cache_stats) or (
                f"retries={r.retries}" if r.retries else None) or (
                "dead-lettered" if r.dead_lettered else None)
            if bad:
                failures.append(f"{label} tick {r.tick}: {bad}")
        warm = results[1:]
        warm_seg = sum(r.cache_stats.get("segment_compiles", 0) for r in warm)
        warm_dim = sum(r.cache_stats.get("dim_h2d_transfers", 0) for r in warm)
        if warm_seg or warm_dim:
            failures.append(f"{label}: warm ticks made {warm_seg} segment "
                            f"compiles and {warm_dim} dim-table uploads")

        replay = repro.replay_deltas(results, group_by=["c_nation"])
        batch_flow = build_flow(data, name="serve-ssb-batch")
        next(c for c in batch_flow.flow.vertices.values()
             if isinstance(c, ArraySource)).set_data(lo)
        batch = session.run(batch_flow, engine="streaming", fuse=fuse)
        bad = run_faults(batch.run)
        if bad:
            failures.append(f"{label} batch: {bad}")
        replay_ok = set(replay) == set(batch.table)
        if not replay_ok:
            failures.append(f"{label}: replay columns {sorted(replay)} != "
                            f"batch {sorted(batch.table)}")
        else:
            for k, v in batch.table.items():
                if (replay[k].dtype != v.dtype
                        or not np.array_equal(replay[k], v)):
                    replay_ok = False
                    failures.append(f"{label}: replayed {k!r} differs from "
                                    f"the batch run")
        expect = serving_oracle(lo, data.customer)
        bad = compare(replay, expect, rtol)
        if bad:
            failures.append(f"{label} oracle: {bad}")
        _emit(flow="serve", mode=mode, rows=n, ticks=ticks,
              out_rows=len(expect["c_nation"]),
              oracle="FAIL" if bad else "ok",
              replay_equals_batch="bitwise" if replay_ok else "no",
              max_rel_err=table_rel_err(replay, expect),
              cold_tick_s=walls[0],
              warm_tick_s_max=max(walls[1:], default=0.0),
              warm_tick_s_sum=sum(walls[1:]),
              warm_segment_compiles=warm_seg, warm_dim_h2d=warm_dim,
              h2d=sum(r.cache_stats.get("h2d_transfers", 0) for r in warm),
              d2h=sum(r.cache_stats.get("d2h_transfers", 0) for r in warm),
              xla_compiles_cold=tick_compiles[0],
              xla_compiles_warm=(sum(tick_compiles[1:]) if compiles
                                 else None),
              peak_bytes=peak_bytes())
    except Exception:
        traceback.print_exc()
        failures.append(f"{label}: raised")
    return failures


def run_sharded(data, shards: int = 4,
                compiles: Optional[CompileCounter] = None) -> List[str]:
    """Q4.1 at ``shards`` shards on the mesh route (partials merged with
    ``psum`` over a ``(data,)`` mesh of every device) beside the serial
    ``shards=1`` run.  The merge mesh must span every device.  Returns
    failures."""
    import jax
    from repro.core.backend import resolve_backend
    rtol = resolve_backend("jax").oracle_rtol
    n_devices = len(jax.devices())
    failures: List[str] = []
    try:
        qf = BUILDERS["Q4.1"](data)
        expect = qf.oracle(data)
        session = repro.Session(backend="jax", metadata=None)
        serial, serial_s, _ = _timed_run(session, qf, compiles, shards=1)
        serial_table = {k: np.array(v) for k, v in serial.table.items()}
        cold, cold_s, cold_c = _timed_run(session, qf, compiles,
                                          shards=shards, shard_impl="mesh")
        warm, warm_s, warm_c = _timed_run(session, qf, compiles,
                                          shards=shards, shard_impl="mesh")
        for which, res in (("serial", serial), ("sharded cold", cold),
                           ("sharded warm", warm)):
            bad = compare(res.table, expect, rtol) or run_faults(res.run)
            if bad:
                failures.append(f"Q4.1/{which}: {bad}")
        for which, res in (("cold", cold), ("warm", warm)):
            if res.run.shards != shards:
                failures.append(f"Q4.1/sharded {which}: ran "
                                f"{res.run.shards} shards, not {shards}")
            if len(res.run.merge_devices) != n_devices:
                failures.append(f"Q4.1/sharded {which}: merge mesh of "
                                f"{res.run.merge_devices}, not of all "
                                f"{n_devices} devices")
            bad = compare(res.table, serial_table, rtol)
            if bad:
                failures.append(f"Q4.1/sharded {which} vs serial: {bad}")
        identical = all(np.array_equal(warm.table[k], v)
                        for k, v in serial_table.items())
        _emit(flow="Q4.1", mode=f"mesh-shards={shards}",
              rows=len(data.lineorder["lo_orderkey"]),
              out_rows=len(next(iter(expect.values()))),
              oracle="ok" if not failures else "FAIL",
              max_rel_err=table_rel_err(warm.table, expect),
              equals_serial="bitwise" if identical else "within-rtol",
              serial_s=serial_s, cold_s=cold_s,
              warm_s=warm_s, h2d=warm.run.h2d_transfers,
              d2h=warm.run.d2h_transfers, xla_compiles_cold=cold_c,
              xla_compiles_warm=warm_c, peak_bytes=peak_bytes())
        print(f"smoke placement: shard passes' device columns on "
              f"{warm.run.shard_devices} (shard_rows={warm.run.shard_rows}); "
              f"merge mesh over {warm.run.merge_devices}", flush=True)
    except Exception:
        traceback.print_exc()
        failures.append("Q4.1/sharded: raised")
    return failures


# ---------------------------------------------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: every flow plus serving; 4: Q4.1 over a "
                         "4-device mesh beside the serial run")
    ap.add_argument("--seed", type=int, default=42)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r} "
              f"({dev.device_kind})", file=sys.stderr)
        return 2
    if len(devices) != args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX found {len(devices)}", file=sys.stderr)
        return 2
    print(f"smoke device: {dev.platform} {dev.device_kind} x{len(devices)}; "
          f"timings are host-clock smoke timings (cold includes compile), "
          f"not benchmark results", flush=True)
    compiles = CompileCounter().register()

    t0 = time.perf_counter()
    data = make_data(seed=args.seed)
    print(f"smoke data: SSB SF1 seed={args.seed} "
          f"lineorder={len(data.lineorder['lo_orderkey'])} "
          f"gen_s={time.perf_counter() - t0}", flush=True)
    if args.chips == 4:
        failures = run_sharded(data, shards=4, compiles=compiles)
    else:
        failures = run_kernel_sums(seed=args.seed)
        for fuse in (False, True):
            failures += run_flows(data, fuse=fuse, compiles=compiles)
        for fuse in (False, True):
            failures += run_serving(data, fuse=fuse, compiles=compiles)
    if failures:
        for f in failures:
            print(f"smoke FAIL {f}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
