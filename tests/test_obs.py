"""Observability: tracer scoping, metric reconciliation, Perfetto export,
the attribution report, and the zero-cost disabled path."""
import dataclasses
import json

import numpy as np
import pytest

from repro.core import (OptimizedEngine, OptimizeOptions, OrdinaryEngine,
                        StreamingEngine)
from repro.core.executor import SharedWorkerPool
from repro.etl.queries import build_q4
from repro.etl.ssb import generate
from repro.obs import metrics as obs_metrics
from repro.obs import report as obs_report
from repro.obs import trace as obs_trace


@pytest.fixture(scope="module")
def data():
    return generate(lineorder_rows=5000, customers=100, suppliers=40,
                    parts=60, seed=7)


# ---------------------------------------------------------------------------
#  Metrics registry
# ---------------------------------------------------------------------------
def test_metrics_registry_counters_gauges_histograms():
    m = obs_metrics.MetricsRegistry()
    m.inc("a")
    m.inc("a", 4)
    m.gauge_set("g", 2.5)
    m.gauge_max("hw", 3)
    m.gauge_max("hw", 1)           # max keeps the high water
    m.observe("lat", 0.001)
    m.observe("lat", 0.002)
    snap = m.snapshot()
    assert snap["counters"]["a"] == 5
    assert snap["gauges"]["g"] == 2.5
    assert snap["gauges"]["hw"] == 3
    h = snap["histograms"]["lat"]
    assert h["count"] == 2
    assert h["sum_s"] == pytest.approx(0.003)
    assert sum(n for _, n in h["buckets"]) + h["overflow"] == 2


def test_histogram_bucket_monotone():
    h = obs_metrics.Histogram()
    for s in (1e-6, 1e-4, 1e-2, 1.0):
        h.observe(s)
    snap = h.snapshot()
    les = [le for le, _ in snap["buckets"]]
    assert les == sorted(les)
    assert snap["min_s"] == pytest.approx(1e-6)
    assert snap["max_s"] == pytest.approx(1.0)


# ---------------------------------------------------------------------------
#  Tracer scoping
# ---------------------------------------------------------------------------
def test_trace_scope_disabled_is_null():
    assert not obs_trace.active()
    s1 = obs_trace.span("compute", "x")
    s2 = obs_trace.span("compute", "y")
    assert s1 is s2                      # shared no-op singleton: no alloc
    with s1:
        pass
    assert s1.seconds == 0.0


class _CountingAnnotation:
    """Stands in for ``jax.profiler.TraceAnnotation`` and counts entries."""
    entered: list = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        self.entered.append(self.name)
        return self

    def __exit__(self, *exc):
        return False


def test_span_annotates_the_profiler_only_while_traced(monkeypatch):
    import jax
    entered = []
    monkeypatch.setattr(_CountingAnnotation, "entered", entered)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _CountingAnnotation)
    with obs_trace.span("wait", "h2d.ready"), \
            obs_trace.annotation("transfer", "d2h") as a:  # no tracer
        pass
    assert entered == [] and a.seconds == 0.0
    with obs_trace.trace_scope() as tr:
        with obs_trace.span("wait", "h2d.ready") as sp:
            pass
        with obs_trace.span("phase", "execute"), obs_trace.span("tick", "t"):
            pass                             # whole runs stay off it
        with obs_trace.span("wait", "channel.drain"):
            pass
    assert entered == ["repro.wait.h2d.ready", "repro.wait.channel.drain"]
    assert sp.seconds >= 0.0
    assert [(e["cat"], e["name"]) for e in tr.events] == [
        ("wait", "h2d.ready"), ("tick", "t"), ("phase", "execute"),
        ("wait", "channel.drain")]


def test_annotation_is_the_profiler_half_of_a_span(monkeypatch):
    """A transfer's interval is one ``repro.obs`` event (``record_transfer``
    records it with the annotation's ``seconds``) and one profiler
    annotation, not two events."""
    import jax
    from repro.core.shared_cache import _to_host
    entered = []
    monkeypatch.setattr(_CountingAnnotation, "entered", entered)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _CountingAnnotation)
    with obs_trace.trace_scope() as tr:
        with obs_trace.annotation("transfer", "d2h") as a:
            pass
        out = _to_host(jax.numpy.arange(4))
    assert a.seconds >= 0.0 and out.tolist() == [0, 1, 2, 3]
    assert entered == ["repro.transfer.d2h", "repro.transfer.d2h"]
    assert [(e["ph"], e["cat"], e["name"]) for e in tr.events] == [
        ("X", "transfer", "d2h")]


def test_span_is_one_interval_on_both_clocks(tmp_path):
    """A span is a host-plane event of a CPU profiler trace and a
    ``repro.obs`` event; a phase span is only the latter."""
    import glob
    import time
    import warnings
    import jax
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs_trace.trace_scope() as tr:
            with obs_trace.span("wait", "channel.get", channel="c"):
                time.sleep(0.002)
            with obs_trace.span("phase", "execute"):
                pass
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        data = ProfileData.from_file(path)
        host = [(e.name, e.duration_ns) for p in data.planes
                if p.name == "/host:CPU" for line in p.lines
                for e in line.events]
    profiled = [d for n, d in host if n == "repro.wait.channel.get"]
    assert len(profiled) == 1 and profiled[0] >= 2e6
    assert not any(n.startswith("repro.phase") for n, _ in host)
    ev = tr.events[0]
    assert (ev["cat"], ev["name"], ev["args"]) == ("wait", "channel.get",
                                                  {"channel": "c"})
    assert ev["dur"] >= 2e3                  # µs, the same interval


def test_trace_scope_records_spans_and_nests():
    with obs_trace.trace_scope() as outer:
        with obs_trace.span("phase", "outer-span"):
            with obs_trace.trace_scope() as inner:
                with obs_trace.span("compute", "inner-span", rows=3):
                    pass
    names = [e["name"] for e in outer.events]
    assert "outer-span" in names and "inner-span" in names   # outer sees all
    assert [e["name"] for e in inner.events] == ["inner-span"]
    ev = inner.events[0]
    assert ev["ph"] == "X" and ev["cat"] == "compute"
    assert ev["args"]["rows"] == 3
    assert ev["dur"] >= 0
    assert not obs_trace.active()


def test_scope_propagates_through_worker_pool():
    """SharedWorkerPool runs tasks under the submitter's contextvars, so a
    span emitted on a pool thread lands in the submitting scope's tracer."""
    pool = SharedWorkerPool(width=2, name="obs-test")
    try:
        with obs_trace.trace_scope() as tr:
            fut = pool.submit(lambda: obs_trace.complete(
                "compute", "pool-task", 0.0, 0.001))
            fut.result()
        assert [e["name"] for e in tr.events] == ["pool-task"]
        assert tr.events[0]["tid"] != 0
    finally:
        pool.shutdown()


def test_run_scope_yields_none_when_disabled(monkeypatch):
    monkeypatch.delenv("REPRO_TRACE", raising=False)
    with obs_trace.run_scope(flow="f") as tr:
        assert tr is None


# ---------------------------------------------------------------------------
#  Engine integration + exact reconciliation
# ---------------------------------------------------------------------------
def _reconcile(run):
    c = run.metrics.get("counters", {})
    for field in ("copies", "bytes_copied", "h2d_transfers", "h2d_bytes",
                  "d2h_transfers", "d2h_bytes", "dispatch_calls",
                  "arena_hits", "arena_misses", "arena_bytes_reused"):
        assert c.get(field, 0) == getattr(run, field), field


@pytest.mark.parametrize("engine_cls", [OptimizedEngine, StreamingEngine])
def test_engine_metrics_reconcile_exactly(data, engine_cls):
    qf = build_q4(data, staged=engine_cls is StreamingEngine)
    with obs_trace.trace_scope() as tr:
        run = engine_cls(qf.flow, OptimizeOptions(num_splits=4)).run()
    _reconcile(run)
    # every component dispatch produced exactly one compute span
    dispatch_spans = [e for e in tr.events if e["cat"] == "compute"
                     and not (e.get("args") or {}).get("phase")]
    assert len(dispatch_spans) == run.dispatch_calls
    # the execute phase span exists and has real width
    phases = [e["name"] for e in tr.events if e["cat"] == "phase"]
    assert "execute" in phases and "plan" in phases
    # run identity is present
    assert len(run.run_id) == 32
    assert run.created.endswith("+00:00")
    # gauges were derived
    g = run.metrics["gauges"]
    assert g["pool_width"] >= 1
    assert "arena_pooled_bytes" in g


def test_ordinary_engine_traces_and_reconciles(data):
    qf = build_q4(data)
    with obs_trace.trace_scope():
        run = OrdinaryEngine(qf.flow, chunk_rows=2048).run()
    _reconcile(run)
    assert run.copies > 0                  # copy-everywhere baseline
    assert run.metrics["counters"]["copies"] == run.copies


def test_adaptive_run_calibration_outside_measure_window(data):
    """optimize_level=2 calibrates inside the tracer scope but OUTSIDE the
    metric window: dispatch_calls must still reconcile exactly."""
    qf = build_q4(data)
    with obs_trace.trace_scope() as tr:
        run = OptimizedEngine(qf.flow, OptimizeOptions(
            num_splits=2, optimize_level=2, calibration_rows=512)).run()
    _reconcile(run)
    phases = [e["name"] for e in tr.events if e["cat"] == "phase"]
    for expect in ("calibrate", "optimize", "plan", "execute"):
        assert expect in phases, expect


def test_untraced_run_has_empty_metrics(data, monkeypatch):
    monkeypatch.delenv("REPRO_TRACE", raising=False)
    qf = build_q4(data)
    run = OptimizedEngine(qf.flow, OptimizeOptions(num_splits=2)).run()
    assert run.metrics == {}
    assert run.trace_file is None
    assert len(run.run_id) == 32           # identity is always on


# ---------------------------------------------------------------------------
#  Export + report
# ---------------------------------------------------------------------------
def test_trace_file_export_and_report(data, tmp_path, monkeypatch):
    path = tmp_path / "trace.json"
    monkeypatch.setenv("REPRO_TRACE", "1")
    monkeypatch.setenv("REPRO_TRACE_PATH", str(path))
    qf = build_q4(data)
    run = OptimizedEngine(qf.flow, OptimizeOptions(num_splits=2)).run()
    assert run.trace_file == str(path)

    payload = json.loads(path.read_text())
    events = payload["traceEvents"]
    assert events, "empty trace"
    # Chrome-trace shape: process metadata + X spans with ts/dur
    assert any(e.get("ph") == "M" and e.get("name") == "process_name"
               for e in events)
    spans = [e for e in events if e.get("ph") == "X"]
    assert all("ts" in e and "dur" in e for e in spans)
    run_meta = payload["otherData"]["runs"]
    assert run_meta and run_meta[-1]["run_id"] == run.run_id

    result = obs_report.analyze(payload)
    rep = result["runs"][-1]
    assert rep["meta"]["run_id"] == run.run_id
    cats = rep["categories"]
    assert cats["compute"] > 0             # self-time µs per class
    assert set(rep["components"])           # per-component attribution
    text = obs_report.render(result)
    assert "compute" in text and run.run_id[:8] in text

    # CLI entry point: --json round trip
    rc = obs_report.main([str(path), "--json"])
    assert rc == 0


def test_trace_file_accumulates_runs_as_processes(data, tmp_path,
                                                  monkeypatch):
    path = tmp_path / "multi.json"
    monkeypatch.setenv("REPRO_TRACE", "1")
    monkeypatch.setenv("REPRO_TRACE_PATH", str(path))
    r1 = OptimizedEngine(build_q4(data).flow,
                         OptimizeOptions(num_splits=2)).run()
    r2 = StreamingEngine(build_q4(data, staged=True).flow,
                         OptimizeOptions(num_splits=2)).run()
    payload = json.loads(path.read_text())
    pids = {e["pid"] for e in payload["traceEvents"] if e.get("ph") == "X"}
    ids = [m["run_id"] for m in payload["otherData"]["runs"]]
    assert len(pids) >= 2                   # one Perfetto process per run
    assert r1.run_id in ids and r2.run_id in ids


def test_report_self_time_subtracts_nesting():
    """A child span's time is attributed to the child, not double-counted
    in the parent (stack-based self-time)."""
    with obs_trace.trace_scope() as tr:
        obs_trace.complete("phase", "parent", 0.0, 0.010)
        obs_trace.complete("compute", "child", 0.002, 0.004)
    tr.meta = {"run_id": "x" * 32}
    payload = {"traceEvents": tr.to_chrome(pid=1),
               "otherData": {"runs": [tr.meta]}}
    rep = obs_report.analyze(payload)["runs"][0]
    assert rep["categories"]["overhead"] == pytest.approx(6000, rel=0.01)
    assert rep["categories"]["compute"] == pytest.approx(4000, rel=0.01)
    # 10ms parent minus the 4ms nested child = 6ms coordination overhead


# ---------------------------------------------------------------------------
#  Disabled-path cost guard
# ---------------------------------------------------------------------------
def test_results_identical_traced_vs_untraced(data):
    qf1 = build_q4(data)
    run1 = OptimizedEngine(qf1.flow, OptimizeOptions(num_splits=4)).run()
    base = qf1.sink.result()
    qf2 = build_q4(data)
    with obs_trace.trace_scope():
        run2 = OptimizedEngine(qf2.flow, OptimizeOptions(num_splits=4)).run()
    got = qf2.sink.result()
    assert set(got) == set(base)
    for k in base:
        np.testing.assert_array_equal(got[k], base[k])
    # instrumentation must not change the deterministic counters either
    for field in ("copies", "bytes_copied", "h2d_transfers", "d2h_transfers",
                  "dispatch_calls"):
        assert getattr(run1, field) == getattr(run2, field), field


# ---------------------------------------------------------------------------
#  Bounded retention (resident serving must not leak trace memory)
# ---------------------------------------------------------------------------
def test_tracer_event_cap_rotates_oldest_half():
    tr = obs_trace.Tracer(max_events=100)
    for i in range(1000):
        tr.emit("X", "t", f"ev{i}", ts_us=float(i), dur_us=1.0)
    assert len(tr.events) <= 100
    assert tr.dropped_events == 1000 - len(tr.events)
    # the SURVIVORS are the newest events, in order
    names = [e["name"] for e in tr.events]
    assert names == [f"ev{i}" for i in range(1000 - len(names), 1000)]


def test_tracer_cap_zero_disables_rotation():
    tr = obs_trace.Tracer(max_events=0)
    for i in range(500):
        tr.emit("X", "t", "e", ts_us=float(i))
    assert len(tr.events) == 500 and tr.dropped_events == 0


def test_tracer_cap_defaults_from_env(monkeypatch):
    monkeypatch.setenv("REPRO_TRACE_MAX_EVENTS", "7")
    assert obs_trace.Tracer().max_events == 7


def test_trace_file_rotates_oldest_runs(tmp_path, monkeypatch):
    """The process trace file keeps at most REPRO_TRACE_MAX_EVENTS events
    across runs: old runs rotate out, the newest run always survives."""
    monkeypatch.setenv("REPRO_TRACE_MAX_EVENTS", "50")
    path = tmp_path / "rot.json"
    tf = obs_trace._TraceFile()
    for r in range(10):
        tr = obs_trace.Tracer(name=f"run{r}", max_events=0)
        tr.meta = {"flow": f"run{r}"}
        for i in range(20):
            tr.emit("X", "t", "e", ts_us=float(i), dur_us=1.0)
        tf.add_and_flush(tr, str(path))
    assert tf.rotated_runs == 8              # 10 runs of 20 events, cap 50
    payload = json.loads(path.read_text())
    kept = [m["flow"] for m in payload["otherData"]["runs"]]
    assert kept == ["run8", "run9"]          # newest runs retained, in order
    assert payload["otherData"]["rotated_runs"] == 8


def test_trace_file_keeps_oversized_newest_run(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_TRACE_MAX_EVENTS", "10")
    path = tmp_path / "big.json"
    tf = obs_trace._TraceFile()
    small = obs_trace.Tracer(name="small", max_events=0)
    small.emit("X", "t", "e", ts_us=0.0)
    tf.add_and_flush(small, str(path))
    big = obs_trace.Tracer(name="big", max_events=0)
    big.meta = {"flow": "big"}
    for i in range(100):                     # alone it already exceeds the cap
        big.emit("X", "t", "e", ts_us=float(i))
    tf.add_and_flush(big, str(path))
    payload = json.loads(path.read_text())
    assert [m["flow"] for m in payload["otherData"]["runs"]] == ["big"]


# ---------------------------------------------------------------------------
#  The fused segment's named scopes and probe counters
# ---------------------------------------------------------------------------
Q41_SEGMENT = ["lookup_customer", "lookup_supplier", "lookup_part",
               "lookup_date", "filter_unmatched", "project", "profit_expr"]


def _q41_runner(data):
    pytest.importorskip("jax")
    from repro.core.backend.jax_backend import JaxBackend
    from repro.etl.components import FusedSegment
    qf = build_q4(data)
    seg = FusedSegment.from_components(
        [qf.flow.component(m) for m in Q41_SEGMENT])
    return JaxBackend().compile_segment(seg)


@pytest.fixture(scope="module")
def q41_runner(data):
    return _q41_runner(data)


#: spreads the part keys (in the part table and the fact rows alike) so
#: far apart that the part table falls back to fmix32
SPARSE_PART = 1009


def _sparse_part(data):
    part = dict(data.part, p_partkey=data.part["p_partkey"] * SPARSE_PART)
    lo = dict(data.lineorder,
              lo_partkey=data.lineorder["lo_partkey"] * SPARSE_PART)
    return dataclasses.replace(data, part=part, lineorder=lo)


def _segment_call(runner, data, traced: bool):
    from repro.core.shared_cache import SharedCache
    cache = SharedCache({k: v.copy() for k, v in data.lineorder.items()})
    if not traced:
        runner(cache)
        return None
    with obs_trace.trace_scope() as tr:
        runner(cache)
    return tr.events


@pytest.mark.parametrize("part_keys", ["dense", "sparse"])
def test_fused_q41_scope_map_names_each_lookups_probe(q41_runner, data,
                                                      part_keys):
    """The compiled segment's top-level ops carry their scopes.  An fmix32
    table's probe is a ``while`` under ``lookup.<dim>/probe``; a direct
    table's one pass has no loop (on the CPU it fuses into the Lookup's
    gathers; the v5e compile keeps probe ops of its own,
    ``tests/test_tpu_compile.py``)."""
    if part_keys == "sparse":
        data = _sparse_part(data)
        q41_runner = _q41_runner(data)
    _segment_call(q41_runner, data, traced=False)
    assert q41_runner._scope_maps == {}      # built only while traced
    events = _segment_call(q41_runner, data, traced=True)
    (ev,) = [e for e in events if (e["ph"], e["cat"], e["name"])
             == ("i", "program", "scopes")]
    args = ev["args"]
    assert args["program"] == "jit__kernel"
    assert args["layout"].startswith("8192:lo_custkey,")
    scopes = set(args["ops"].values())
    loops = {s for op, s in args["ops"].items() if op.startswith("%while")}
    assert loops == ({"lookup.part/probe"} if part_keys == "sparse"
                     else set())
    # XLA may fuse a gather into the filter: some stay top-level ops
    assert {"lookup.customer/gather", "filter.0", "expr.profit"} <= scopes
    # the map is built once per layout and reused by the next call
    events = _segment_call(q41_runner, data, traced=True)
    assert not [e for e in events if e["name"] == "scopes.build"]


@pytest.mark.parametrize("keys,vals", [
    (np.arange(100, 400), np.r_[np.arange(100, 400), 99, 400, -7]),  # dense
    (np.arange(0, 10**6, 997), np.arange(-5, 10**6, 3001)),         # sparse
    (np.arange(0, 0), np.arange(5)),                                 # empty
])
def test_probe_need_is_the_walk_of_every_row(keys, vals):
    """A traced call's ``need``, whether looked up per key over a dense key
    range or walked, is the host walk's sum over the rows."""
    from repro.core.backend.jax_backend import JaxBackend
    from repro.core.backend.jax_dims import device_form
    from repro.etl.components import DimTable
    from repro.kernels.hash_join import hash_build, probe_lengths_np
    keys = keys.astype(np.int64)
    form = device_form(DimTable(keys, {"v": keys}), JaxBackend().asarray)
    want = int(probe_lengths_np(hash_build((keys,)), (vals,)).sum())
    assert form.need(vals) == want
    assert form.need(vals) == want   # cached lengths
    assert (form._lengths[1] is None) == (len(keys) != 300)


@pytest.mark.parametrize("part_keys", ["dense", "sparse"])
def test_fused_q41_counts_probe_passes_per_lookup(q41_runner, data,
                                                  part_keys):
    """SSB's dense keys make every table direct: one pass a row, and each
    row needs it.  A sparse part table keeps fmix32 and its walk."""
    if part_keys == "sparse":
        data = _sparse_part(data)
        q41_runner = _q41_runner(data)
    events = _segment_call(q41_runner, data, traced=True)
    probes = {e["name"]: e["args"] for e in events
              if (e["ph"], e["cat"]) == ("C", "probe")}
    assert set(probes) == {"customer", "supplier", "part", "date"}
    from repro.kernels.hash_join import hash_build, probe_lengths_np
    table = hash_build((data.part["p_partkey"],))
    need = probe_lengths_np(table, (data.lineorder["lo_partkey"],)).sum()
    dense = part_keys == "dense"
    assert probes["part"] == {
        "rows": 5000, "padded_rows": 8192, "passes": table["max_probes"],
        "direct": int(dense), "mean_probes": table["mean_probes"],
        "slots": table["table_size"], "gathers": 1, "need": need}
    for name, p in probes.items():
        assert p["gathers"] == 1
        if name == "part" and part_keys == "sparse":
            assert p["passes"] > 1 and p["rows"] < p["need"]
        else:
            assert (p["passes"], p["direct"], p["need"]) == (1, 1, 5000)
    # each row of each Lookup needs at least one pass, at most all
    for p in probes.values():
        assert p["rows"] <= p["need"] <= p["rows"] * p["passes"]
    # the leaf spans of the call, each on both clocks while traced
    names = {(e["cat"], e["name"]) for e in events if e["ph"] == "X"}
    assert {("transfer", "h2d.pack"), ("transfer", "h2d.upload"),
            ("wait", "h2d.ready"), ("transfer", "h2d"),
            ("dispatch", "segment"), ("transfer", "d2h")} <= names


@pytest.mark.parametrize("part_keys", ["dense", "sparse"])
def test_probe_counter_counts_gathers_and_slot_addressing(data, part_keys):
    """A Lookup reads slot-addressed arrays, on a direct table and on a
    hashed one alike: one gather a row per returned column, or one where
    it returns none (Q1.1's date Lookup returns one column and its match
    bit rides on it).  On a hashed table these follow the probe loop."""
    from repro.etl.components import DimTable, Lookup
    part = _sparse_part(data) if part_keys == "sparse" else data
    table = DimTable(part.part["p_partkey"],
                     {"p_mfgr": part.part["p_mfgr"],
                      "p_brand1": part.part["p_brand1"]}, name="part")
    lookups = [
        Lookup("lk_date", DimTable(data.date["d_datekey"],
                                   {"d_year": data.date["d_year"]},
                                   name="date"),
               "lo_orderdate", {"d_year": "d_year"}, matched_flag="d_ok"),
        Lookup("lk_part", table, "lo_partkey",
               {"p_mfgr": "p_mfgr", "p_brand1": "p_brand1"}),
        Lookup("lk_supp", DimTable(data.supplier["s_suppkey"], {},
                                   name="supplier"),
               "lo_suppkey", {}, matched_flag="s_ok"),
    ]
    from repro.core.backend.jax_backend import JaxBackend
    from repro.etl.components import FusedSegment
    runner = JaxBackend().compile_segment(
        FusedSegment.from_components(lookups))
    events = _segment_call(runner, part, traced=True)
    probes = {e["name"]: e["args"] for e in events
              if (e["ph"], e["cat"]) == ("C", "probe")}
    got = {name: (p["direct"], p["gathers"]) for name, p in probes.items()}
    sparse = part_keys == "sparse"
    assert got == {"date": (1, 1), "supplier": (1, 1),
                   "part": (int(not sparse), 2)}


# ---------------------------------------------------------------------------
#  Wide expressions and exact group sums
# ---------------------------------------------------------------------------
def _q1_segment_and_rows(n=3000, seed=5):
    pytest.importorskip("jax")
    from repro import col
    from repro.core.backend.jax_backend import JaxBackend
    from repro.etl.components import Expression, Filter, FusedSegment
    rng = np.random.default_rng(seed)
    qty = rng.integers(1, 51, n)
    table = {"l_extendedprice": qty * rng.integers(90_000, 209_901, n),
             "l_discount": rng.integers(0, 11, n),
             "l_tax": rng.integers(0, 9, n),
             "l_shipdate": rng.integers(19920102, 19981201, n)}
    disc = col("l_extendedprice") * (100 - col("l_discount"))
    seg = FusedSegment.from_components([
        Filter("ship", col("l_shipdate") <= 19980902),
        Expression("disc", "disc_price", disc),
        Expression("charge", "charge", disc * (100 + col("l_tax")))])
    return JaxBackend().compile_segment(seg), table


def test_wide_expression_scope_and_counter():
    """A traced segment call names the widened expression's ops
    ``wide.<col>`` and counts it: rows, bits of its widest node, and the
    8-bit limbs those bits make; the expression that fits int32 gets
    neither."""
    from repro.core.shared_cache import SharedCache
    runner, table = _q1_segment_and_rows()
    cache = SharedCache({k: v.copy() for k, v in table.items()})
    with obs_trace.trace_scope() as tr:
        runner(cache)
    wide = [e for e in tr.events if (e["ph"], e["cat"]) == ("C", "wide")]
    ext = table["l_extendedprice"]
    hi = int(ext.max()) * 100 * 108
    assert [(e["name"], e["args"]) for e in wide] == [
        ("charge", {"rows": 3000, "bits": hi.bit_length() + 1,
                    "limbs": -(-(hi.bit_length() + 1) // 8)})]
    (scopes,) = [e for e in tr.events if e["name"] == "scopes"]
    names = set(scopes["args"]["ops"].values())
    assert any(s.startswith("expr.charge/wide.charge") for s in names), names
    assert not any("wide.disc_price" in s for s in names)
    # untraced calls emit nothing and build no scope map
    runner2, _ = _q1_segment_and_rows()
    runner2(SharedCache({k: v.copy() for k, v in table.items()}))
    assert runner2._scope_maps == {}


def test_exact_groupby_counter_and_scope():
    """Each group-by call that sums integers exactly counts its rows, its
    distinct integer inputs (a column read by ``sum`` and ``avg`` once), the
    limb columns summed and the widest span; its program names the limb
    split ``groupby.exact``."""
    pytest.importorskip("jax")
    import jax.numpy as jnp
    from repro.core.backend import get_backend
    from repro.kernels.radix_groupby import radix_groupby
    bk = get_backend("jax")
    keys = np.arange(4000) % 6
    qty = (np.arange(4000) % 50) + 1                 # 1..50: one limb
    price = qty * 200_000                            # span < 2**24: 3
    with obs_trace.trace_scope() as tr:
        bk.groupby_reduce([keys], {"s": (qty, "sum"), "a": (qty, "avg"),
                                   "p": (price, "sum"),
                                   "n": (qty, "count")}, 4000)
    (ev,) = [e for e in tr.events if (e["ph"], e["cat"], e["name"])
             == ("C", "exact", "groupby")]
    assert ev["args"] == {"rows": 4000, "columns": 2, "limbs": 4,
                          "max_bits": int(price.max() - price.min())
                          .bit_length()}
    from repro.core import wideint
    ids = jnp.asarray(keys, jnp.int32)
    text = radix_groupby.lower(
        ids, jnp.zeros((4000, 0)), 6, impl="reference",
        ints=((jnp.asarray(qty, jnp.int32), wideint.const(1)),),
        limbs=(1,)).as_text(debug_info=True)
    assert "groupby.exact" in text
