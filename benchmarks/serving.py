"""Resident serving: sustained micro-batch throughput + tick-latency tails.

The section opens one ``Session.serve`` loop per backend over an SSB-shaped
flow (customer lookup -> filter -> derived profit -> terminal aggregate),
feeds the fact table through it in fixed-size micro-batch ticks, and reports
sustained rows/s plus the p50/p99 tick latency — the serving-path BENCH
numbers (latency distribution, not a wall-time race).

Emits CSV:
  serving.ssb,backend,ticks,rows_per_s,tick_p50_ms,tick_p99_ms,cold_ms
  serving.ssb.counters,backend,cold_compiles,cold_dim_h2d,warm_compiles,warm_dim_h2d

The ``--smoke serving`` part ENFORCES the resident-state contract on the
active backend: after the cold first tick, every warm tick must record ZERO
segment-kernel recompiles and ZERO dimension-table h2d re-uploads
(``CacheStats.segment_compiles`` / ``dim_h2d_transfers``), and replaying the
emitted deltas must be byte-identical to the one-shot streaming batch run.
Returns ``(failures, extras)``; extras carries the cold/warm counters for
``bench_diff`` to lock in, plus the latency tails.
"""
from __future__ import annotations

import time

import numpy as np

import repro
from repro.core import available_backends

from .common import BENCH_REPEATS, BENCH_ROWS, ssb_data

BACKENDS = ("numpy", "jax")
TICKS = 16


def _percentile(walls, q: float) -> float:
    if not walls:
        return 0.0
    return float(np.percentile(np.asarray(walls, dtype=np.float64), q))


def build_flow(data, name: str = "serve-ssb"):
    """Serving flow over the lineorder schema: customer-nation lookup,
    region filter, derived profit, terminal group-by aggregate."""
    cust = (data.customer["c_custkey"],
            {"c_nation": data.customer["c_nation"],
             "c_region": data.customer["c_region"]})
    empty = {c: a[:0] for c, a in data.lineorder.items()}
    return (repro.flow(name)
            .source(empty)
            .lookup(cust, "lo_custkey", {"c_nation": "c_nation",
                                         "c_region": "c_region"})
            .filter(repro.col("c_region") < 3)
            # profit in units of 10k: keeps every per-group float32 partial
            # sum exactly representable (< 2^24), so incremental tick merges
            # stay byte-identical to the one-shot batch reduction
            .derive("profit",
                    (repro.col("lo_revenue") - repro.col("lo_supplycost"))
                    // 10_000)
            .aggregate(["c_nation"], {"profit": ("profit", "sum"),
                                      "avg_profit": ("profit", "avg"),
                                      "orders": ("profit", "count")})
            .sink())


def _batch_flow(data, name: str = "serve-ssb-batch"):
    f = build_flow(data, name)
    src = next(c for c in f.flow.vertices.values()
               if type(c).__name__ == "ArraySource")
    src.set_data(data.lineorder)
    return f


def _tick_batches(lineorder, ticks: int = TICKS):
    n = len(next(iter(lineorder.values())))
    splits = np.array_split(np.arange(n), ticks)
    return [{c: a[idx] for c, a in lineorder.items()} for idx in splits]


def _serve_loop(data, backend, ticks: int = TICKS):
    """Run one full serve loop; returns (tick_results, summary)."""
    session = repro.Session(backend=backend, metadata=None)
    results = []
    with session.serve(build_flow(data)) as srv:
        for t, batch in enumerate(_tick_batches(data.lineorder, ticks)):
            results.append(srv.tick(batch, watermark=time.time()))
        srv.close()
    return results


def run(rows: int = None) -> list:
    rows = rows or max(200_000, BENCH_ROWS // 4)
    data = ssb_data(rows)
    out = ["serving.ssb,backend,ticks,rows_per_s,tick_p50_ms,tick_p99_ms,"
           "cold_ms"]
    backends = [b for b in BACKENDS if b in available_backends()]
    for backend in backends:
        best = None
        for _ in range(max(1, BENCH_REPEATS)):
            results = _serve_loop(data, backend)
            warm = results[1:] or results
            total_rows = sum(r.rows_in for r in warm)
            total_wall = sum(r.wall_s for r in warm)
            rps = total_rows / max(total_wall, 1e-9)
            if best is None or rps > best[0]:
                best = (rps, results)
        rps, results = best
        warm_walls = [r.wall_s for r in results[1:]]
        out.append(
            f"serving.ssb,{backend},{len(results)},{rps:.0f},"
            f"{_percentile(warm_walls, 50) * 1e3:.2f},"
            f"{_percentile(warm_walls, 99) * 1e3:.2f},"
            f"{results[0].wall_s * 1e3:.2f}")
        cold, warm = results[0].cache_stats, results[1:]
        out.append(
            f"serving.ssb.counters,{backend},"
            f"{cold.get('segment_compiles', 0)},"
            f"{cold.get('dim_h2d_transfers', 0)},"
            f"{sum(r.cache_stats.get('segment_compiles', 0) for r in warm)},"
            f"{sum(r.cache_stats.get('dim_h2d_transfers', 0) for r in warm)}")
    return out


def smoke(data):
    """CI part: the resident-state contract on the active backend — warm
    ticks perform zero segment recompiles and zero dim-table h2d re-uploads,
    and the concatenated deltas replay byte-identically to the one-shot
    streaming batch run.  Returns ``(failures, extras)``."""
    import traceback

    failures = 0
    extras = {}
    try:
        results = _serve_loop(data, backend=None, ticks=8)
        cold, warm = results[0], results[1:]
        assert warm, "serving smoke needs at least two ticks"
        warm_compiles = sum(r.cache_stats.get("segment_compiles", 0)
                            for r in warm)
        warm_dim_h2d = sum(r.cache_stats.get("dim_h2d_transfers", 0)
                           for r in warm)
        assert warm_compiles == 0, \
            (f"warm ticks recompiled {warm_compiles} segment kernels — "
             f"resident serving must keep compiled segments hot")
        assert warm_dim_h2d == 0, \
            (f"warm ticks re-uploaded {warm_dim_h2d} dim tables — "
             f"resident serving must keep device dim caches hot")

        # replayed deltas == one-shot batch run, byte for byte
        fb = _batch_flow(data)
        ref = repro.Session(metadata=None).run(fb, engine="streaming").table
        rep = repro.replay_deltas(results, group_by=["c_nation"])
        assert set(rep) == set(ref), \
            f"column sets differ: {sorted(rep)} vs {sorted(ref)}"
        for k in ref:
            assert rep[k].dtype == ref[k].dtype, \
                f"column {k}: dtype {rep[k].dtype} != batch {ref[k].dtype}"
            assert rep[k].tobytes() == ref[k].tobytes(), \
                f"column {k}: replayed deltas differ from the batch run"

        warm_walls = [r.wall_s for r in warm]
        extras = {
            "counters": {
                "ticks": len(results),
                "cold_segment_compiles":
                    cold.cache_stats.get("segment_compiles", 0),
                "cold_dim_h2d_transfers":
                    cold.cache_stats.get("dim_h2d_transfers", 0),
                "warm_segment_compiles": warm_compiles,
                "warm_dim_h2d_transfers": warm_dim_h2d,
            },
            "rows_per_s": round(sum(r.rows_in for r in warm)
                                / max(sum(warm_walls), 1e-9), 1),
            "tick_p50_ms": round(_percentile(warm_walls, 50) * 1e3, 3),
            "tick_p99_ms": round(_percentile(warm_walls, 99) * 1e3, 3),
        }
        print(f"smoke.serving,ok,ticks={len(results)},"
              f"cold_compiles={extras['counters']['cold_segment_compiles']},"
              f"warm_compiles=0,warm_dim_h2d=0,"
              f"p99_ms={extras['tick_p99_ms']}")
    except Exception:
        traceback.print_exc()
        failures += 1
        print("smoke.serving,FAIL")
    return failures, extras


if __name__ == "__main__":
    print("\n".join(run()))
