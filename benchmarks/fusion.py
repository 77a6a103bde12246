"""Segment fusion + CacheArena vs the PR-3 adaptive path, both backends.

For each flow x backend the section runs the streaming engine twice at
``optimize_level=2`` — fusion OFF (the PR-3 adaptive baseline) and fusion ON
(``fuse_segments=True``: maximal row-synchronized chains collapsed into
single compiled kernels, per-chunk buffers recycled through the arena) —
verifies the fused run's sink output is byte-identical to the baseline, and
reports wall time, backend dispatch calls, h2d/d2h transfer counts and the
arena hit/miss/bytes-reused counters.

Emits CSV:
  fusion.flow,backend,mode,wall_s,dispatch_calls,h2d_n,d2h_n,arena_hits,arena_misses,arena_MB_reused
  fusion.flow.speedup,backend,fused_vs_unfused,<x>

The ``--smoke fusion`` part additionally ENFORCES the reduction: fused
dispatch calls must drop versus unfused, and on the jax backend the h2d
transfer count must drop (and d2h not grow) — the acceptance gate for the
fused-kernel path.
"""
from __future__ import annotations

import numpy as np

from repro.core import OptimizeOptions, StreamingEngine, available_backends
from repro.etl import BUILDERS

from .common import BENCH_REPEATS, BENCH_ROWS, ssb_data

FLOWS = ("Q4.1", "Q4.1s")
BACKENDS = ("numpy", "jax")
NUM_SPLITS = 8
CALIBRATION_ROWS = 65_536


def _run(qname: str, data, backend, fused: bool, num_splits: int = NUM_SPLITS,
         calibration_rows: int = CALIBRATION_ROWS):
    qf = BUILDERS[qname](data)
    run = StreamingEngine(qf.flow, OptimizeOptions(
        num_splits=num_splits, backend=backend, optimize_level=2,
        calibration_rows=calibration_rows, fuse_segments=fused)).run()
    return run, qf.sink.result()


def _assert_identical(fused, baseline, label: str) -> None:
    assert set(fused) == set(baseline), f"{label}: column sets differ"
    for k in baseline:
        assert fused[k].dtype == baseline[k].dtype, f"{label}: dtype of {k}"
        np.testing.assert_array_equal(fused[k], baseline[k],
                                      err_msg=f"{label} column {k}")


def _csv(prefix: str, backend, mode: str, r) -> str:
    return (f"{prefix},{backend},{mode},{r.wall_time:.4f},"
            f"{r.dispatch_calls},{r.h2d_transfers},{r.d2h_transfers},"
            f"{r.arena_hits},{r.arena_misses},"
            f"{r.arena_bytes_reused/1e6:.1f}")


def run(rows: int = None) -> list:
    rows = rows or max(200_000, BENCH_ROWS // 4)
    data = ssb_data(rows)
    out = ["fusion.flow,backend,mode,wall_s,dispatch_calls,h2d_n,d2h_n,"
           "arena_hits,arena_misses,arena_MB_reused"]
    backends = [b for b in BACKENDS if b in available_backends()]
    for flow in FLOWS:
        for backend in backends:
            best = {}
            results = {}
            for fused, mode in ((False, "unfused"), (True, "fused")):
                for _ in range(max(1, BENCH_REPEATS)):
                    r, res = _run(flow, data, backend, fused)
                    if mode not in best or r.wall_time < best[mode].wall_time:
                        best[mode] = r
                        results[mode] = res
                out.append(_csv(f"fusion.{flow}", backend, mode, best[mode]))
            _assert_identical(results["fused"], results["unfused"],
                              f"{flow}/{backend}")
            speedup = (best["unfused"].wall_time
                       / max(best["fused"].wall_time, 1e-9))
            out.append(f"fusion.{flow}.speedup,{backend},fused_vs_unfused,"
                       f"{speedup:.3f}")
    return out


def smoke(data):
    """CI part: fused-vs-unfused byte equality on Q4.1/Q4.1s under the
    active backend, with the reductions ENFORCED — fewer backend dispatch
    calls always; on a mask-deferring backend (jax) fewer h2d transfers,
    STRICTLY fewer d2h transfers, and the per-chunk keep-mask syncs gone:
    deferral replaces one mask compact per chunk with the terminal
    Aggregate's masked reduce, so unfused_d2h - fused_d2h >= num_splits - 1.

    Returns ``(failures, extras)`` where extras carries the per-flow
    transfer counters for the bench JSON (``bench_diff`` locks them in
    against the committed baselines)."""
    import traceback

    from repro.core import get_default_backend
    backend = get_default_backend()
    failures = 0
    counters = {}
    num_splits = 4
    for flow in FLOWS:
        try:
            r_u, unfused = _run(flow, data, backend=None, fused=False,
                                num_splits=num_splits,
                                calibration_rows=8_192)
            r_f, fused = _run(flow, data, backend=None, fused=True,
                              num_splits=num_splits, calibration_rows=8_192)
            _assert_identical(fused, unfused, flow)
            assert any(x["rule"] == "fuse-segment" for x in r_f.rewrites), \
                f"{flow}: no fuse-segment rewrite applied"
            assert any(x["rule"] == "fuse-segment-aggregate"
                       for x in r_f.rewrites), \
                f"{flow}: no fuse-segment-aggregate (mask deferral) rewrite"
            assert r_f.dispatch_calls < r_u.dispatch_calls, \
                (f"{flow}: fused dispatch calls {r_f.dispatch_calls} !< "
                 f"unfused {r_u.dispatch_calls}")
            if backend.supports_segment_defer:
                assert r_f.h2d_transfers < r_u.h2d_transfers, \
                    (f"{flow}: fused h2d transfers {r_f.h2d_transfers} !< "
                     f"unfused {r_u.h2d_transfers}")
                assert r_f.d2h_transfers < r_u.d2h_transfers, \
                    (f"{flow}: fused d2h transfers {r_f.d2h_transfers} !< "
                     f"unfused {r_u.d2h_transfers}")
                # zero per-chunk keep-mask syncs: the unfused run pays one
                # mask compact per chunk, the fused run none (the
                # Aggregate's reduce applies the mask on the device)
                saved = r_u.d2h_transfers - r_f.d2h_transfers
                assert saved >= num_splits - 1, \
                    (f"{flow}: only {saved} d2h syncs eliminated; expected "
                     f">= {num_splits - 1} (per-chunk keep-mask compacts)")
            counters[flow] = {
                "unfused": {"dispatch_calls": r_u.dispatch_calls,
                            "h2d_transfers": r_u.h2d_transfers,
                            "d2h_transfers": r_u.d2h_transfers},
                "fused": {"dispatch_calls": r_f.dispatch_calls,
                          "h2d_transfers": r_f.h2d_transfers,
                          "d2h_transfers": r_f.d2h_transfers},
            }
        except Exception:
            traceback.print_exc()
            failures += 1
            print(f"smoke.fusion.{flow},FAIL")
            continue
        print(f"smoke.fusion.{flow},rows_ok,"
              f"dispatch={r_u.dispatch_calls}->{r_f.dispatch_calls},"
              f"h2d_n={r_u.h2d_transfers}->{r_f.h2d_transfers},"
              f"d2h_n={r_u.d2h_transfers}->{r_f.d2h_transfers},"
              f"arena_hits={r_f.arena_hits}")
    return failures, {"counters": counters}


if __name__ == "__main__":
    print("\n".join(run()))
