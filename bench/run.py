"""Run one benchmark cell once, on the chip this process finds.

    python3 bench/run.py --workload ssb_sf1.q4.1 --seed 7 --seconds 40 --trace 0

Set-up (JAX start, data from ``--seed``, building the flow, the traffic's
warm-up requests) is timed as ``setup_s``.  Then the traffic's closed loop
runs for ``--seconds``; a request started inside the window is finished
and counted.  After the window the program's state is freed, every answer
is checked against the numpy reference, and the last line of standard
output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer metrics, read from a profiler trace of the window and the
program's own spans), ``device``, ``breakdown`` (traced runs) and
``checks``, each compared number beside its limit, which also end
standard error.

Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import json
import os
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, List, Optional, Tuple

ROOT = Path(__file__).resolve().parent.parent


class CompileCounter:
    """Counts, through ``jax.monitoring``, every XLA program that jit's
    in-memory cache missed (``count``: compiled, or loaded from the
    persistent cache) and the persistent cache's hits among them
    (``hits``)."""

    def __init__(self, event: str, hit_event: str):
        self.event, self.hit_event = event, hit_event
        self.count = self.hits = 0

    def __call__(self, event: str, duration: float, **_) -> None:
        if event == self.event:
            self.count += 1

    def hit(self, event: str, **_) -> None:
        if event == self.hit_event:
            self.hits += 1


@dataclass
class Context:
    """What a metric reader may read."""
    cell: object
    setup_s: float
    #: host-clock start and end of the measured window
    window: Tuple[float, float]
    records: list
    window_compiles: int
    #: the reduced profiler trace (``--trace 1``), else None
    trace: Optional[object]
    #: the program's own trace events in the window (``--trace 1``)
    spans: Optional[List[dict]]
    peaks: dict
    #: per fact table: rows the group-by received and groups it made
    shapes: Dict[int, dict]
    dim_rows: Dict[str, int]


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def _compile_cache(jax) -> None:
    """Keep every compiled program in ``<checkout>/.jax_cache``, a fixed
    path, so a second run of a cell compiles nothing.  No size limit: with
    one, every write scans the whole directory, and a cell whose warm-up
    compiles hundreds of small programs slows by minutes a run as the
    cache grows."""
    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def _peak_bytes(devices) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def measure(cell, seed: int, seconds: float, trace: bool, used: list,
            chip_peaks: dict, t_start: float) -> Tuple[dict, List[str]]:
    """Set up, measure and check one run of ``cell`` on the devices
    ``used``; returns the result object and the lines to print before
    it."""
    import jax
    from bench import events
    compiles = CompileCounter(events.COMPILE_EVENT, events.CACHE_HIT_EVENT)
    jax.monitoring.register_event_duration_secs_listener(compiles)
    jax.monitoring.register_event_listener(compiles.hit)
    try:
        return _measure(cell, seed, seconds, trace, used, chip_peaks,
                        t_start, compiles)
    finally:
        jax.monitoring.unregister_event_duration_listener(compiles)
        jax.monitoring.unregister_event_listener(compiles.hit)


def _measure(cell, seed, seconds, trace, used, chip_peaks, t_start,
             compiles):
    import jax
    from bench import check, events, loops, registry
    from bench.trace import Profile

    readers = registry.readers(cell.per_layer if trace else cell.end_to_end)
    cfg = cell.config
    t_data = time.perf_counter()
    data = registry.generator(cfg["generator"])(cfg, seed)
    data_s = time.perf_counter() - t_data
    loop = loops.make(cfg, cell.traffic, cell.flow, data)
    warm = loop.warm_up()
    setup_s = time.perf_counter() - t_start
    setup_compiles, setup_hits = compiles.count, compiles.hits

    log_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    profile = Profile(log_dir) if log_dir else None
    tracer = None
    scope = contextlib.nullcontext()
    if trace:
        from repro.obs import Tracer, trace_scope
        tracer = Tracer(name="bench", max_events=0)
        scope = trace_scope(tracer)
        profile.start()
    records = []
    try:
        with scope, jax.profiler.TraceAnnotation(events.WINDOW):
            w0 = time.perf_counter()
            while time.perf_counter() - w0 < seconds:
                records.append(loop.step())
            w1 = time.perf_counter()
    finally:
        if profile:
            profile.stop()
    window_compiles = compiles.count - setup_compiles
    peak = _peak_bytes(used)
    loop.close()
    gc.collect()

    summary = None
    if profile:
        try:
            summary = profile.summary(len(used))
        finally:
            shutil.rmtree(log_dir, ignore_errors=True)
    t_ref = time.perf_counter()
    readings = loop.check()
    failed = sum(not r.ok for r in records)
    numbers = check.worst(readings, failed + sum(not r.ok for r in warm))
    checks = check.verdict(numbers, cfg["limits"])
    ref_s = time.perf_counter() - t_ref

    ctx = Context(cell=cell, setup_s=setup_s, window=(w0, w1),
                  records=records, window_compiles=window_compiles,
                  trace=summary,
                  spans=list(tracer.events) if tracer else None,
                  peaks=chip_peaks,
                  shapes=loop.shapes(loop.n) if trace else {},
                  dim_rows={k: len(next(iter(v.values())))
                            for k, v in data.dims.items()})
    units = {m["name"]: m["unit"] for m in (cell.per_layer if trace
                                             else cell.end_to_end)}
    metrics = {}
    for name, read in readers.items():
        value = read(ctx)
        if value is not None:
            metrics[name] = {"value": value, "unit": units[name]}

    device = {"platform": used[0].platform, "kind": used[0].device_kind,
              "count": len(used), "memory_peak_bytes": peak}
    result = {"correct": check.passed(checks), "attempted": len(records),
              "failed": failed, "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary.mean_busy_ns / 1e9
        device["window_s"] = summary.window_ns / 1e9
        top_ops = sorted(summary.ops_ns.items(), key=lambda kv: -kv[1])[:10]
        result["breakdown"] = {
            "device_ops": [[n, t / 1e9] for n, t in top_ops],
            "idle_gaps": [[n, t / 1e9] for n, t in summary.gaps]}
    result["checks"] = checks

    info = [f"bench cell={cell.name} seed={seed} trace={int(trace)} "
            f"setup_s={setup_s} jax_start_s={t_data - t_start} "
            f"data_s={data_s} "
            f"warm_up={len(warm)} setup_compiles={setup_compiles} "
            f"setup_cache_hits={setup_hits} "
            f"window_s={w1 - w0} {loop.unit}s={len(records)} "
            f"window_compiles={window_compiles} peak_bytes={peak} "
            f"checked={len(readings)} reference_s={ref_s}",
            "bench durations_s " + json.dumps(
                [r.end - r.start for r in warm + records])]
    if summary is not None:
        top_mod = sorted(summary.modules_ns.items(), key=lambda kv: -kv[1])
        info.append("bench programs " + json.dumps(
            [[n, t / 1e9] for n, t in top_mod[:10]]))
    return result, info


def main(argv=None) -> int:
    t_start = time.perf_counter()
    args = parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    from bench import check, peaks, registry

    cell = registry.cell(args.workload)
    import jax
    _compile_cache(jax)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"bench: {args.workload} needs {cell.chips} TPU chip(s); JAX "
              f"found {len(devices)} {devices[0].platform} device(s) "
              f"({devices[0].device_kind})", file=sys.stderr)
        return 2
    used = devices[:cell.chips]
    result, info = measure(cell, args.seed, args.seconds, bool(args.trace),
                           used, peaks.peaks(used[0].device_kind), t_start)
    for line in info:
        print(line, flush=True)
    for line in check.lines(result["checks"]):
        print(line, file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
