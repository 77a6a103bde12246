"""Record a small profiler trace of a cell, for the trace reduction's tests.

    python3 bench/record_trace.py --workload ssb_sf1.q4.1 --rows 65536 \\
        --requests 2 --out bench/testdata/q4.1.xplane.pb

It runs the cell's loop on ``--rows`` fact rows (serving cells: ticks of
``--rows`` rows) with the profiler on around ``--requests`` requests inside
a ``bench.window`` annotation, as ``bench/run.py --trace 1`` does, and
copies the ``.xplane.pb`` to ``--out``.  It needs a TPU.
"""
from __future__ import annotations

import argparse
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rows", type=int, required=True)
    ap.add_argument("--requests", type=int, default=2)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import events, loops, registry
    from bench.trace import Profile, find_xplane, reduce_xplane

    cell = registry.cell(args.workload)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return 2
    cfg = dict(cell.config, lineorder_rows=args.rows)
    traffic = dict(cell.traffic)
    if "tick_rows" in traffic:
        cfg["lineorder_rows"] = 2 * args.rows
        traffic["tick_rows"] = args.rows
    data = registry.generator(cfg["generator"])(cfg, args.seed)
    loop = loops.make(cfg, traffic, cell.flow, data)
    loop.warm_up()
    log_dir = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        profile = Profile(log_dir)
        profile.start()
        try:
            with jax.profiler.TraceAnnotation(events.WINDOW):
                for _ in range(args.requests):
                    loop.step()
        finally:
            profile.stop()
        loop.close()
        path = find_xplane(log_dir)
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        shutil.copyfile(path, args.out)
        summary = reduce_xplane(args.out, cell.chips)
        print(f"record_trace: {args.out} {Path(args.out).stat().st_size} "
              f"bytes; window {summary.window_ns} ns, busy "
              f"{summary.busy_ns} ns; programs "
              f"{sorted(summary.modules_ns.items(), key=lambda kv: -kv[1])[:8]}"
              f"; ops {sorted(summary.ops_ns.items(), key=lambda kv: -kv[1])[:8]}"
              f"; gaps {summary.gaps[:5]}", flush=True)
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
