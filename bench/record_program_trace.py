"""Record a small profiler trace of a cell together with the program's own
trace events (``repro.obs``) of the same window, for the tests of the
readers that join the two.

    python3 bench/record_program_trace.py --workload ssb_sf1.q4.1 \\
        --rows 65536 --requests 2 --out bench/testdata/q4.1_65k_program

It runs the cell's loop on ``--rows`` fact rows with the profiler on and a
``repro.obs`` tracer in scope around ``--requests`` requests, inside a
``bench.window`` annotation, as ``bench/run.py --trace 1`` does.  It writes
``<out>.xplane.pb.gz`` (the profiler's trace, gzipped) and
``<out>.events.json`` (``{"window": [start, end]`` in host-clock seconds,
``"events": [...]}``), then prints the cell's per-layer metrics read from
the two.  It needs a TPU.
"""
from __future__ import annotations

import argparse
import gzip
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def record(cell, rows: int, requests: int, seed: int, out: str) -> dict:
    """Run and record; returns ``{"xplane", "events"}``, the two paths."""
    import jax
    from bench import events, loops, registry
    from bench.trace import Profile, find_xplane
    from repro.obs import Tracer, trace_scope

    cfg = dict(cell.config, lineorder_rows=rows)
    data = registry.generator(cfg["generator"])(cfg, seed)
    loop = loops.make(cfg, cell.traffic, cell.flow, data)
    loop.warm_up()
    log_dir = tempfile.mkdtemp(prefix="bench-trace-")
    paths = {"xplane": f"{out}.xplane.pb.gz", "events": f"{out}.events.json"}
    try:
        profile = Profile(log_dir)
        tracer = Tracer(name="bench", max_events=0)
        profile.start()
        try:
            with trace_scope(tracer), \
                    jax.profiler.TraceAnnotation(events.WINDOW):
                w0 = time.perf_counter()
                for _ in range(requests):
                    loop.step()
                w1 = time.perf_counter()
        finally:
            profile.stop()
        loop.close()
        Path(out).parent.mkdir(parents=True, exist_ok=True)
        with open(find_xplane(log_dir), "rb") as src, \
                gzip.open(paths["xplane"], "wb") as dst:
            shutil.copyfileobj(src, dst)
        Path(paths["events"]).write_text(json.dumps(
            {"window": [w0, w1], "events": tracer.events}))
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)
    return paths


def read_metrics(cell, paths: dict) -> dict:
    """The cell's per-layer metrics that the two recordings can give."""
    from bench import registry
    from bench.run import Context
    from bench.trace import reduce_xplane
    summary = reduce_xplane(paths["xplane"], cell.chips)
    program = json.loads(Path(paths["events"]).read_text())
    ctx = Context(cell=cell, setup_s=0.0, window=tuple(program["window"]),
                  records=[], window_compiles=0, trace=summary,
                  spans=program["events"], peaks={}, shapes={}, dim_rows={})
    out = {}
    for metric in cell.per_layer:
        try:
            out[metric["name"]] = registry.reader(metric["name"])(ctx)
        except (KeyError, ZeroDivisionError, TypeError):
            out[metric["name"]] = "needs a full run"
    out["gaps"] = summary.gaps[:5]
    out["ops"] = sorted(summary.ops_ns.items(), key=lambda kv: -kv[1])[:8]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rows", type=int, required=True)
    ap.add_argument("--requests", type=int, default=2)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import registry

    cell = registry.cell(args.workload)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("record_program_trace: needs a TPU", file=sys.stderr)
        return 2
    paths = record(cell, args.rows, args.requests, args.seed, args.out)
    sizes = {k: Path(p).stat().st_size for k, p in paths.items()}
    print(f"record_program_trace: {paths} {sizes} bytes; "
          f"{json.dumps(read_metrics(cell, paths), default=str)}",
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
