"""Sharded execution subsystem (core/shard): partitioner, planner,
partial→merge aggregation, replay, degradations and engine wiring.

Byte-identity of sharded vs serial runs over *random* flows lives in
test_optimizer_equivalence.py (test_sharded_flow_equivalence); this file
covers the subsystem's unit behavior and its failure/fallback edges.
"""
import numpy as np
import pytest

from repro.core import (MetadataStore, OptimizeOptions, ServingEngine,
                        StreamingEngine, cache_stats_scope, config, faults,
                        partition, plan_runtime, plan_shards, resolve_backend)
from repro.core.engine import _assign_backend
from repro.core.shard import ShardRunner, choose_shards
from repro.core.shard.partitioner import (hash_shard_ids, range_bounds,
                                          shard_tables, table_rows)
from repro.core.shard.planner import MAX_AUTO_SHARDS, MIN_SHARD_ROWS
from repro.etl import BUILDERS
from repro.etl.components import Aggregate, ArraySource, CollectSink

ROWS = 12_000


def _table(seed=0, rows=ROWS):
    r = np.random.RandomState(seed)
    return {"g": r.randint(0, 7, rows).astype(np.int64),
            "h": r.randint(0, 3, rows).astype(np.int64),
            "v": r.randint(-1000, 1000, rows).astype(np.int64),
            "f": r.uniform(-1.0, 1.0, rows)}


def _agg_flow(ops, group=("g",), seed=0, rows=ROWS, name="aggflow"):
    """src -> Aggregate(group, ops) -> sink, picklable (no lambdas)."""
    from repro.core import Dataflow
    flow = Dataflow(name)
    sink = CollectSink("sink")
    flow.chain(ArraySource("src", _table(seed, rows)),
               Aggregate("agg", list(group), dict(ops)),
               sink)
    return flow, sink


def _run(flow, sink, **opt_kw):
    run = StreamingEngine(flow, OptimizeOptions(num_splits=4, **opt_kw)).run()
    return run, sink.result()


def _assert_tables_equal(got, want, label=""):
    assert set(got) == set(want), label
    for k in want:
        assert got[k].dtype == want[k].dtype, f"{label}: dtype of {k}"
        np.testing.assert_array_equal(got[k], want[k],
                                      err_msg=f"{label}: column {k}")


# ---------------------------------------------------------------- partitioner
def test_range_bounds_cover_exactly():
    for rows, shards in [(0, 3), (1, 4), (10, 3), (12_000, 7)]:
        b = range_bounds(rows, shards)
        assert b[0] == 0 and b[-1] == rows
        assert (np.diff(b) >= 0).all()
    with pytest.raises(ValueError):
        range_bounds(10, 0)


def test_hash_shard_ids_deterministic_and_bounded():
    r = np.random.RandomState(3)
    a = r.randint(0, 1 << 40, 50_000).astype(np.int64)
    b = r.randint(-5, 5, 50_000).astype(np.int64)
    ids = hash_shard_ids([a, b], 5)
    assert ids.min() >= 0 and ids.max() < 5
    np.testing.assert_array_equal(ids, hash_shard_ids([a, b], 5))
    # chained mixing: key order matters
    assert not np.array_equal(ids, hash_shard_ids([b, a], 5))
    # splitmix64 spreads even low-cardinality keys across all shards
    assert len(np.unique(ids)) == 5


def test_hash_partition_is_exact_disjoint_cover():
    src = _table(seed=1)
    parts = shard_tables({"src": src}, 4, "hash", key=("g", "h"))
    assert sum(table_rows(p["src"]) for p in parts) == ROWS
    # same key tuple always lands on the same shard => group-disjoint
    seen = {}
    for k, p in enumerate(parts):
        for pair in zip(p["src"]["g"].tolist(), p["src"]["h"].tolist()):
            assert seen.setdefault(pair, k) == k
    # per-shard relative order of v is a subsequence of the original
    cat = np.concatenate([p["src"]["v"] for p in parts])
    assert sorted(cat.tolist()) == sorted(src["v"].tolist())


def test_range_partition_is_contiguous():
    src = _table(seed=2)
    parts = shard_tables({"src": src}, 3, "range")
    cat = np.concatenate([p["src"]["v"] for p in parts])
    np.testing.assert_array_equal(cat, src["v"])


# -------------------------------------------------------------------- planner
def test_choose_shards_bounds():
    assert choose_shards(100, 4, cores=8) == 1          # rows floor
    assert choose_shards(MIN_SHARD_ROWS * 100, 4, cores=8) == 4
    assert choose_shards(MIN_SHARD_ROWS * 100, 64, cores=64) == MAX_AUTO_SHARDS
    assert choose_shards(0, 0, cores=1) == 1


def test_plan_shards_serial_and_degradations():
    flow, _ = _agg_flow([("s", ("v", "sum"))])
    bk = resolve_backend("numpy")
    _assign_backend(flow, bk)
    g_tau = partition(flow)
    opts = OptimizeOptions(num_splits=4)
    assert plan_shards(flow, g_tau, 1, "auto", opts, bk) is None

    plan = plan_shards(flow, g_tau, 3, "auto", opts, bk)
    assert plan is not None and plan.shards == 3 and plan.impl == "inline"
    assert plan.mode == "hash" and plan.key == ("g",)

    with pytest.raises(ValueError):
        plan_shards(flow, g_tau, 2, "threads", opts, bk)

    # a chunk-sensitive source cannot be re-partitioned: serial + recorded
    flow.component("src").chunk_sensitive = True
    with faults.fault_recorder() as frec:
        assert plan_shards(flow, g_tau, 2, "auto", opts, bk) is None
    assert any(d.kind == "shard_plan" for d in frec.degradations)


def test_plan_shards_global_agg_takes_range_mode():
    flow, _ = _agg_flow([("s", ("v", "sum"))], group=())
    bk = resolve_backend("numpy")
    _assign_backend(flow, bk)
    plan = plan_shards(flow, partition(flow), 2, "inline",
                       OptimizeOptions(num_splits=4), bk)
    assert plan is not None and plan.mode == "range" and plan.key == ()


# ------------------------------------------------------- partial→merge ops
@pytest.mark.parametrize("op", ["sum", "min", "max", "count", "avg"])
def test_partial_merge_every_agg_op(op):
    ops = [("a", ("v", op)), ("b", ("f", op))]
    flow_s, sink_s = _agg_flow(ops, group=("g", "h"))
    _, serial = _run(flow_s, sink_s, shards=1)
    for shards in (2, 3):
        flow_n, sink_n = _agg_flow(ops, group=("g", "h"))
        run, got = _run(flow_n, sink_n, shards=shards, shard_impl="inline")
        assert run.shards == shards
        _assert_tables_equal(got, serial, f"op={op} shards={shards}")


def test_mesh_route_on_jax_backend():
    pytest.importorskip("jax")
    ops = [("s", ("v", "sum")), ("m", ("v", "min")),
           ("x", ("f", "max")), ("a", ("f", "avg"))]
    flow_s, sink_s = _agg_flow(ops)
    _, serial = _run(flow_s, sink_s, shards=1, backend="jax")
    flow_n, sink_n = _agg_flow(ops)
    run, got = _run(flow_n, sink_n, shards=2, shard_impl="mesh",
                    backend="jax")
    assert run.shards == 2
    _assert_tables_equal(got, serial, "mesh route")
    import jax
    # placement as the run saw it: the merge mesh spans every device; the
    # cut's input came straight from a host source, so no device columns
    assert run.merge_devices == [f"{d.platform}:{d.id}"
                                 for d in jax.devices()]
    assert run.shard_devices == [[], []]


def test_mesh_combiner_records_its_dtype_refusal():
    pytest.importorskip("jax")
    from repro.core.shard.mesh import make_combiner
    combine = make_combiner()
    keys = np.array([0, 1, 0], dtype=np.int64)
    fits = np.array([1, 2, 3], dtype=np.int64)
    with faults.fault_recorder() as rec:
        groups, parts = combine({"g": keys, "v": fits}, ["g"], {"v": "sum"})
        # an int64 partial past int32 cannot reduce on a 32-bit device
        wide = np.array([1, 2, 1 << 40], dtype=np.int64)
        assert combine({"g": keys, "v": wide}, ["g"], {"v": "sum"}) is None
    np.testing.assert_array_equal(groups[0], [0, 1])
    np.testing.assert_array_equal(parts["v"], [4, 2])
    assert [(d.kind, d.src, d.dst, d.component)
            for d in rec.degradations] == [("shard_impl", "mesh",
                                            "host-merge", "v")]


def test_global_aggregate_sharded():
    # avg over the INTEGER column: exact partial sums → the one division
    # rounds identically on the serial and the partial→merge path; a float
    # avg reduced on-device (jax runs float32) is only ulp-close across
    # different chunkings, checked separately below
    ops = [("s", ("v", "sum")), ("m", ("f", "min")),
           ("c", ("v", "count")), ("a", ("v", "avg"))]
    flow_s, sink_s = _agg_flow(ops, group=())
    _, serial = _run(flow_s, sink_s, shards=1)
    flow_n, sink_n = _agg_flow(ops, group=())
    run, got = _run(flow_n, sink_n, shards=3, shard_impl="inline")
    assert run.shards == 3
    _assert_tables_equal(got, serial, "global agg")


def test_global_float_avg_sharded_ulp_close():
    # device backends reduce float sums in their native dtype, so serial
    # (one kernel over all rows) and sharded (per-shard kernels + host
    # merge) round differently — agreement is to ulp, not byte-identity
    ops = [("a", ("f", "avg"))]
    flow_s, sink_s = _agg_flow(ops, group=())
    _, serial = _run(flow_s, sink_s, shards=1)
    flow_n, sink_n = _agg_flow(ops, group=())
    _, got = _run(flow_n, sink_n, shards=3, shard_impl="inline")
    np.testing.assert_allclose(got["a"], serial["a"], rtol=1e-5)


def test_empty_shards_more_shards_than_groups():
    # one distinct key tuple => hash mode puts every row on ONE shard;
    # the other shards run empty passes and must not perturb the merge
    rows = 5_000
    cols = {"g": np.ones(rows, dtype=np.int64),
            "v": np.arange(rows, dtype=np.int64)}
    from repro.core import Dataflow

    def build():
        flow = Dataflow("onekey")
        sink = CollectSink("sink")
        flow.chain(ArraySource("src", dict(cols)),
                   Aggregate("agg", ["g"], {"s": ("v", "sum")}), sink)
        return flow, sink

    flow_s, sink_s = build()
    _, serial = _run(flow_s, sink_s, shards=1)
    flow_n, sink_n = build()
    run, got = _run(flow_n, sink_n, shards=4, shard_impl="inline")
    assert run.shards == 4
    assert sorted(run.shard_rows) == [0, 0, 0, rows]
    _assert_tables_equal(got, serial, "one-key hash")


# ----------------------------------------------------------- fault replay
def test_shard_failure_replays_to_identical_output(monkeypatch):
    monkeypatch.setenv("REPRO_RETRY_BACKOFF", "0.001")
    monkeypatch.setenv("REPRO_CACHE_GUARD", "1")
    monkeypatch.delenv(config.ENV_FAULTS, raising=False)
    flow_s, sink_s = _agg_flow([("s", ("v", "sum")), ("a", ("f", "avg"))])
    _, serial = _run(flow_s, sink_s, shards=1)
    plan = faults.FaultPlan(
        [faults.FaultRule(site="shard", kind="transient", count=2)],
        seed=5)
    flow_n, sink_n = _agg_flow([("s", ("v", "sum")), ("a", ("f", "avg"))])
    with faults.fault_scope(plan):
        run, got = _run(flow_n, sink_n, shards=3, shard_impl="inline")
    assert plan.injected == 2
    assert run.faults_injected == 2
    assert run.retries >= 2                        # whole-shard replays
    _assert_tables_equal(got, serial, "shard replay")


def test_merge_pass_failure_replays(monkeypatch):
    monkeypatch.setenv("REPRO_RETRY_BACKOFF", "0.001")
    monkeypatch.delenv(config.ENV_FAULTS, raising=False)
    flow_s, sink_s = _agg_flow([("s", ("v", "sum"))])
    _, serial = _run(flow_s, sink_s, shards=1)
    # the merge attempt injects with split=None only after every shard
    # pass took its own injection, so a rule skipping the first
    # ``shards`` matching calls targets the coordinator merge exactly
    plan = faults.FaultPlan(
        [faults.FaultRule(site="shard", kind="transient", count=1,
                          after=2)], seed=1)
    flow_n, sink_n = _agg_flow([("s", ("v", "sum"))])
    with faults.fault_scope(plan):
        run, got = _run(flow_n, sink_n, shards=2, shard_impl="inline")
    assert plan.injected == 1 and run.retries >= 1
    _assert_tables_equal(got, serial, "merge replay")


# ------------------------------------------------------- degrade / refuse
def test_process_route_degrades_under_fault_scope():
    # scoped fault plans cannot cross a process boundary: the runner must
    # fall back to inline (recorded) rather than silently lose injections
    plan = faults.FaultPlan([faults.FaultRule(site="chunk", count=0)], seed=1)
    flow, sink = _agg_flow([("s", ("v", "sum"))])
    with faults.fault_scope(plan):
        run, got = _run(flow, sink, shards=2, shard_impl="process")
    assert run.shards == 2
    assert any(d["kind"] == "shard_impl" and d["dst"] == "inline"
               for d in run.degradation_events)
    flow_s, sink_s = _agg_flow([("s", ("v", "sum"))])
    _, serial = _run(flow_s, sink_s, shards=1)
    _assert_tables_equal(got, serial, "process degrade")


def test_unpicklable_flow_degrades_to_inline():
    from repro.etl.components import Filter
    from repro.core import Dataflow
    flow = Dataflow("unpick")
    sink = CollectSink("sink")
    flow.chain(ArraySource("src", _table()),
               Filter("keep", lambda c, rows: c.col("v")[rows] > 0,
                      reads=["v"]),
               sink)
    run = StreamingEngine(flow, OptimizeOptions(
        num_splits=4, shards=2, shard_impl="process")).run()
    assert run.shards == 2
    assert any(d["kind"] == "shard_impl" for d in run.degradation_events)
    got = sink.result()["v"]
    src = _table()["v"]
    np.testing.assert_array_equal(got, src[src > 0])


@pytest.mark.parametrize("shards", [2, 3])
def test_sharded_staged_flow_preserves_row_order(shards):
    # a semi-block cut feeds a row-sync tail: the sink harvests streamed
    # shard-pass caches whose arrival order is scheduler-dependent, so
    # reassembly must restore (shard, split) order — regression for the
    # shard-major renumber erasing split_index before sorting
    from repro.core import Dataflow, StageBoundary
    from repro.etl.components import Filter
    rows = 20_000
    flow = Dataflow("staged_order")
    sink = CollectSink("sink")
    flow.chain(ArraySource("src", {"x": np.arange(rows, dtype=np.int64)}),
               Filter("keep_even", lambda c, r: c.col("x")[r] % 2 == 0,
                      reads=["x"]),
               StageBoundary("cut"),
               Filter("keep_div4", lambda c, r: c.col("x")[r] % 4 == 0,
                      reads=["x"]),
               sink)
    run = StreamingEngine(flow, OptimizeOptions(
        num_splits=8, shards=shards, shard_impl="inline")).run()
    assert run.shards == shards
    np.testing.assert_array_equal(sink.result()["x"],
                                  np.arange(0, rows, 4))


def test_serving_engine_refuses_shards():
    flow, _ = _agg_flow([("s", ("v", "sum"))])
    eng = ServingEngine(flow, OptimizeOptions(num_splits=2, shards=2))
    with pytest.raises(ValueError, match="shard"):
        eng.tick()


# --------------------------------------------------- counters and metadata
def test_per_shard_counters_sum_to_run_total():
    flow, sink = _agg_flow([("s", ("v", "sum"))])
    bk = resolve_backend("numpy")
    _assign_backend(flow, bk)
    g_tau = partition(flow)
    opts = OptimizeOptions(num_splits=4, shards=3, shard_impl="inline")
    rplan = plan_runtime(flow, g_tau, num_splits=4, m_prime=4, backend=bk)
    plan = plan_shards(flow, g_tau, 3, "inline", opts, bk)
    assert plan is not None
    with cache_stats_scope() as stats:
        res = ShardRunner(flow, g_tau, opts, rplan, plan).execute()
    total = stats.snapshot()
    by_parts = {}
    for snap in res.shard_stats + [res.merge_stats]:
        for k, v in snap.items():
            by_parts[k] = by_parts.get(k, 0) + v
    assert len(res.shard_stats) == 3
    for k in ("copies", "bytes_copied", "h2d_bytes", "d2h_bytes",
              "arena_hits", "arena_misses"):
        assert by_parts[k] == total[k], \
            f"{k}: per-shard {by_parts[k]} != run total {total[k]}"
    assert res.shuffle_bytes > 0
    assert res.scatter_bytes <= res.source_bytes
    assert sum(res.shard_rows) == ROWS
    assert sink.result()  # merge delivered


def test_env_vars_drive_shards(monkeypatch):
    monkeypatch.setenv(config.ENV_SHARDS, "2")
    monkeypatch.setenv(config.ENV_SHARD_IMPL, "inline")
    flow, sink = _agg_flow([("s", ("v", "sum"))])
    run, _ = _run(flow, sink)
    assert run.shards == 2 and len(run.shard_rows) == 2
    assert "shards=2" in run.summary()


def test_explicit_opts_override_env(monkeypatch):
    monkeypatch.setenv(config.ENV_SHARDS, "4")
    flow, sink = _agg_flow([("s", ("v", "sum"))])
    run, _ = _run(flow, sink, shards=1)
    assert run.shards == 1 and run.shard_rows == []


def test_metadata_records_shard_layout_xml_roundtrip():
    store = MetadataStore()
    flow, sink = _agg_flow([("s", ("v", "sum"))])
    StreamingEngine(flow, OptimizeOptions(num_splits=4, shards=2,
                                          shard_impl="inline"),
                    metadata=store).run()
    spec = store.runs[flow.name]
    assert spec["shards"] == 2 and len(spec["shard_rows"]) == 2
    back = MetadataStore.from_xml(store.to_xml()).runs[flow.name]
    assert back["shards"] == 2
    assert back["shard_rows"] == spec["shard_rows"]


# ------------------------------------------------------------ tracing path
def test_sharded_run_emits_shard_and_merge_spans():
    from repro.obs import trace as obs_trace
    tr = obs_trace.Tracer(name="shardtrace")
    flow, sink = _agg_flow([("s", ("v", "sum"))])
    with obs_trace.trace_scope(tr):
        StreamingEngine(flow, OptimizeOptions(num_splits=4, shards=2,
                                              shard_impl="inline")).run()
    names = [e.get("name") for e in tr.events]
    assert "shard-merge" in names
    assert "shard-0" in names and "shard-1" in names


def test_shard_runner_attaches_per_shard_subtracers():
    from repro.obs import trace as obs_trace
    flow, sink = _agg_flow([("s", ("v", "sum"))])
    bk = resolve_backend("numpy")
    _assign_backend(flow, bk)
    g_tau = partition(flow)
    opts = OptimizeOptions(num_splits=4, shards=2, shard_impl="inline")
    rplan = plan_runtime(flow, g_tau, num_splits=4, m_prime=4, backend=bk)
    plan = plan_shards(flow, g_tau, 2, "inline", opts, bk)
    tr = obs_trace.Tracer(name="shardtrace", measuring=False)
    tr.meta = {"flow": flow.name}
    with obs_trace.trace_scope(tr):
        ShardRunner(flow, g_tau, opts, rplan, plan, tracer=tr).execute()
    # each shard pass exports as its own shard-tagged sub-tracer (own
    # Perfetto pid, see obs.trace._TraceFile.add_and_flush)
    assert len(tr.shard_tracers) == 2
    for k, sub in enumerate(tr.shard_tracers):
        assert sub.meta["shard"] == k
        assert sub.meta["flow"] == f"{flow.name}[shard{k}]"
        assert any(e.get("name") == f"shard-{k}" for e in sub.events)


def test_q41_sharded_process_route_byte_identical(ssb_tiny):
    """The acceptance query: Q4.1 at shards=2 over the process route must
    be byte-identical to serial (and actually fan out, not degrade)."""
    qf = BUILDERS["Q4.1"](ssb_tiny)
    StreamingEngine(qf.flow, OptimizeOptions(num_splits=2)).run()
    serial = qf.sink.result()

    qf2 = BUILDERS["Q4.1"](ssb_tiny)
    run = StreamingEngine(qf2.flow, OptimizeOptions(
        num_splits=2, shards=2, shard_impl="process")).run()
    assert run.shards == 2
    assert not any(d["kind"] == "shard_impl"
                   for d in run.degradation_events), "process route degraded"
    _assert_tables_equal(qf2.sink.result(), serial, "Q4.1 process")
