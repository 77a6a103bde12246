"""Property-based flow-equivalence harness for the cost-based optimizer.

A hypothesis-driven generator draws random single-source dataflow chains of
Filter / Lookup / Expression / Aggregate / Sort components (plus explicit
StageBoundary cuts) over synthetic columnar caches, then asserts that
running the flow with ``optimize_level=2`` — calibration, statistics-driven
graph rewriting, measured re-partitioning/re-planning — produces
BYTE-IDENTICAL sink output (same columns, same dtypes, same rows, same
order) as the untouched static flow.

The engine backend follows ``REPRO_BACKEND`` (the CI matrix runs this file
under both ``numpy`` and ``jax``), so every rewrite is exercised against
both operator backends.  ``REPRO_OPTEQ_EXAMPLES`` scales the example count
(default 100 per engine property, per the acceptance bar).
"""
import warnings

import numpy as np
import pytest

try:
    import hypothesis.strategies as st
    from hypothesis import given, settings
except ImportError:        # pragma: no cover — env without the `test` extra
    from _hypothesis_compat import given, settings, st

from repro.core import (OptimizeOptions, OptimizedEngine, OrdinaryEngine,
                        StreamingEngine, config, partition)
from repro.core.component import StageBoundary
from repro.etl.components import (Aggregate, ArraySource, CollectSink,
                                  DimTable, Expression, Filter, Lookup, Sort)

N_EXAMPLES = config.opteq_examples()
ROWS = 400                 # fixed size keeps jitted-kernel shapes stable
KEYSPACE = 40


# ---------------------------------------------------------------------------
#  spec -> flow builder (rebuildable: engines mutate flows and sinks)
# ---------------------------------------------------------------------------
def build_flow(spec):
    """Construct a fresh Dataflow + sink from a drawn spec.  Deterministic:
    the same spec always builds the same flow over the same data."""
    seed, num_splits, ops = spec
    r = np.random.RandomState(seed)
    cols = {
        "k0": r.randint(1, KEYSPACE + 1, ROWS).astype(np.int64),
        "k1": r.randint(1, KEYSPACE + 1, ROWS).astype(np.int64),
        "g": r.randint(0, 4, ROWS).astype(np.int64),
        "v0": r.randint(0, 1000, ROWS).astype(np.int64),
        "v1": r.randint(-50, 50, ROWS).astype(np.int64),
    }
    from repro.core import Dataflow
    flow = Dataflow(f"rand-{seed}")
    comps = [ArraySource("src", cols)]
    avail = list(cols.keys())

    for i, op in enumerate(ops):
        kind = op[0]
        if kind == "filter":
            col_i, thresh, declared = op[1:]
            col = avail[col_i % len(avail)]
            reads = [col] if declared else None
            with warnings.catch_warnings():
                if not declared:
                    # the undeclared-reads path is deliberately part of the
                    # property space (rewrites must REFUSE on it) — silence
                    # the contract DeprecationWarning for these specs only
                    warnings.simplefilter("ignore", DeprecationWarning)
                comps.append(Filter(
                    f"filter{i}",
                    # default-arg binding: each lambda captures ITS column
                    lambda c, rows, col=col, t=thresh:
                        c.col(col)[rows] % 97 < t,
                    reads=reads))
        elif kind == "lookup":
            dim_seed, key_i, drop = op[1:]
            keyish = [c for c in avail if c.startswith("k")] or avail
            key = keyish[key_i % len(keyish)]
            rd = np.random.RandomState(dim_seed)
            nk = KEYSPACE if not drop else KEYSPACE // 2   # some unmatched
            dim = DimTable(np.arange(1, nk + 1, dtype=np.int64),
                           {"pay": rd.randint(0, 9, nk).astype(np.int64)})
            out = f"l{i}"
            comps.append(Lookup(f"lookup{i}", dim, key, {out: "pay"}))
            avail.append(out)
        elif kind == "expr":
            a_i, b_i, mul = op[1:]
            a, b = avail[a_i % len(avail)], avail[b_i % len(avail)]
            out = f"e{i}"
            if mul:
                fn = (lambda c, rows, a=a, b=b:
                      c.col(a)[rows] * (c.col(b)[rows] % 7 + 1))
            else:
                fn = (lambda c, rows, a=a, b=b:
                      c.col(a)[rows] + c.col(b)[rows])
            comps.append(Expression(f"expr{i}", out, fn, reads=[a, b]))
            avail.append(out)
        elif kind == "boundary":
            comps.append(StageBoundary(f"cut{i}"))
        elif kind == "agg":
            g_i, v_i, agg_op = op[1:]
            group = avail[g_i % len(avail)]
            val = avail[v_i % len(avail)]
            comps.append(Aggregate(f"agg{i}", [group],
                                   {f"a{i}": (val, agg_op)}))
            avail = [group, f"a{i}"]
        elif kind == "sort":
            by_i = op[1]
            comps.append(Sort(f"sort{i}", [avail[by_i % len(avail)]]))
    sink = CollectSink("sink")
    comps.append(sink)
    flow.chain(*comps)
    return flow, sink


@st.composite
def flow_spec(draw):
    seed = draw(st.integers(0, 10_000))
    num_splits = draw(st.sampled_from([1, 2, 4]))
    n_ops = draw(st.integers(1, 6))
    ops = []
    for _ in range(n_ops):
        kind = draw(st.sampled_from(
            ["filter", "lookup", "lookup", "expr", "expr", "boundary",
             "agg", "sort"]))
        if kind == "filter":
            ops.append(("filter", draw(st.integers(0, 9)),
                        draw(st.integers(10, 90)),
                        draw(st.sampled_from([True, True, False]))))
        elif kind == "lookup":
            ops.append(("lookup", draw(st.integers(0, 1000)),
                        draw(st.integers(0, 3)),
                        draw(st.sampled_from([True, False]))))
        elif kind == "expr":
            ops.append(("expr", draw(st.integers(0, 9)),
                        draw(st.integers(0, 9)),
                        draw(st.sampled_from([True, False]))))
        elif kind == "boundary":
            ops.append(("boundary",))
        elif kind == "agg":
            ops.append(("agg", draw(st.integers(0, 9)),
                        draw(st.integers(0, 9)),
                        draw(st.sampled_from(["sum", "min", "max", "count"]))))
        else:
            ops.append(("sort", draw(st.integers(0, 9))))
    return (seed, num_splits, ops)


# ---------------------------------------------------------------------------
#  the property
# ---------------------------------------------------------------------------
def _assert_byte_identical(spec, engine_cls):
    _, num_splits, _ = spec
    flow_s, sink_s = build_flow(spec)
    engine_cls(flow_s, OptimizeOptions(num_splits=num_splits)).run()
    static = sink_s.result()

    flow_a, sink_a = build_flow(spec)
    run = engine_cls(flow_a, OptimizeOptions(num_splits=num_splits,
                                             optimize_level=2,
                                             calibration_rows=128)).run()
    adaptive = sink_a.result()

    assert set(adaptive.keys()) == set(static.keys()), \
        f"column sets differ after rewrites {run.rewrites}"
    for k in static:
        assert adaptive[k].dtype == static[k].dtype, \
            f"dtype of {k} changed: {run.rewrites}"
        np.testing.assert_array_equal(
            adaptive[k], static[k],
            err_msg=f"column {k} differs after rewrites {run.rewrites} "
                    f"(spec={spec})")
    # the rewritten flow must still be a valid partitionable dataflow
    partition(flow_a)


@given(flow_spec())
@settings(max_examples=N_EXAMPLES, deadline=None)
def test_rewritten_flow_equivalence_streaming(spec):
    """optimize_level=2 (calibrate + rewrite + re-plan) on the STREAMING
    engine is byte-identical to the static flow, for every generated DAG."""
    _assert_byte_identical(spec, StreamingEngine)


@given(flow_spec())
@settings(max_examples=max(N_EXAMPLES // 4, 10), deadline=None)
def test_rewritten_flow_equivalence_optimized(spec):
    """Same property on the non-streaming OptimizedEngine (exercises the
    remove-boundary path: cuts never pay off without streaming)."""
    _assert_byte_identical(spec, OptimizedEngine)


# ---------------------------------------------------------------------------
#  segment fusion: fused flows must be byte-identical too
# ---------------------------------------------------------------------------
def _assert_fused_identical(spec, engine_cls, adaptive=False):
    """Fusion (OptimizeOptions.fuse_segments) — alone or stacked on the
    optimize_level=2 adaptive rewrites — produces byte-identical sink output
    versus the untouched static flow, for every generated DAG."""
    _, num_splits, _ = spec
    flow_s, sink_s = build_flow(spec)
    # fuse_segments=False pins the baseline even under REPRO_FUSION=1
    engine_cls(flow_s, OptimizeOptions(num_splits=num_splits,
                                       fuse_segments=False)).run()
    static = sink_s.result()

    flow_f, sink_f = build_flow(spec)
    opts = OptimizeOptions(num_splits=num_splits, fuse_segments=True)
    if adaptive:
        opts = OptimizeOptions(num_splits=num_splits, fuse_segments=True,
                               optimize_level=2, calibration_rows=128)
    run = engine_cls(flow_f, opts).run()
    fused = sink_f.result()

    assert set(fused.keys()) == set(static.keys()), \
        f"column sets differ after rewrites {run.rewrites}"
    for k in static:
        assert fused[k].dtype == static[k].dtype, \
            f"dtype of {k} changed: {run.rewrites}"
        np.testing.assert_array_equal(
            fused[k], static[k],
            err_msg=f"column {k} differs after rewrites {run.rewrites} "
                    f"(spec={spec})")
    partition(flow_f)


@given(flow_spec())
@settings(max_examples=N_EXAMPLES, deadline=None)
def test_fused_flow_equivalence_streaming(spec):
    """Segment fusion on the STREAMING engine is byte-identical to the
    static flow, for every generated DAG (both backends via REPRO_BACKEND)."""
    _assert_fused_identical(spec, StreamingEngine)


@given(flow_spec())
@settings(max_examples=max(N_EXAMPLES // 4, 10), deadline=None)
def test_fused_adaptive_flow_equivalence_streaming(spec):
    """Fusion stacked on the full optimize_level=2 adaptive path (commutes,
    expression fusion, boundary cuts, re-planning) stays byte-identical."""
    _assert_fused_identical(spec, StreamingEngine, adaptive=True)


def test_fused_equivalence_all_rules_fire_together():
    spec = (7, 4, [("lookup", 3, 0, True),
                   ("expr", 3, 4, False),
                   ("expr", 5, 0, True),
                   ("filter", 4, 30, True),
                   ("agg", 2, 5, "sum"),
                   ("sort", 0)])
    _assert_fused_identical(spec, StreamingEngine, adaptive=True)


def test_fused_equivalence_undeclared_reads_fall_back():
    """Undeclared read sets force the whole-cache upload fallback on device
    backends — results must still be byte-identical."""
    spec = (13, 4, [("lookup", 5, 1, False), ("filter", 2, 40, False),
                    ("expr", 1, 6, False)])
    _assert_fused_identical(spec, StreamingEngine)


# ---------------------------------------------------------------------------
#  deterministic regressions: shapes the generator rarely lands on exactly
# ---------------------------------------------------------------------------
def test_equivalence_all_rules_fire_together():
    """One flow where commute + fusion + boundary-insert can all apply."""
    spec = (7, 4, [("lookup", 3, 0, True),
                   ("expr", 3, 4, False),
                   ("expr", 5, 0, True),
                   ("filter", 4, 30, True),
                   ("agg", 2, 5, "sum"),
                   ("sort", 0)])
    _assert_byte_identical(spec, StreamingEngine)


def test_equivalence_boundary_only_chain():
    spec = (11, 2, [("boundary",), ("expr", 0, 3, True), ("boundary",)])
    _assert_byte_identical(spec, StreamingEngine)


def test_equivalence_filter_drops_everything():
    # threshold 10 over % 97 keeps ~10%; two stacked filters can drop all
    spec = (3, 2, [("filter", 3, 10, True), ("filter", 4, 10, True),
                   ("agg", 1, 2, "count")])
    _assert_byte_identical(spec, StreamingEngine)


def test_equivalence_single_component_flow():
    spec = (5, 1, [])
    _assert_byte_identical(spec, StreamingEngine)


# ---------------------------------------------------------------------------
#  AST-vs-lambda: DSL-built SSB flows are byte-identical to the legacy
#  lambda-built flows — both backends, every engine, levels 0/2, fused and
#  unfused (the api_redesign acceptance matrix)
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def ssb_dsl_data():
    from repro.etl.ssb import generate
    return generate(lineorder_rows=3000, customers=200, suppliers=40,
                    parts=150, seed=17)


def _dsl_backends():
    from repro.core import available_backends, get_backend
    out = ["numpy"]
    if "jax" in available_backends():
        try:
            get_backend("jax")
            out.append("jax")
        except Exception:      # pragma: no cover — jax present in-container
            pass
    return out


#: (engine, optimize_level, fuse_segments); the ordinary baseline has no
#: optimizer/fusion knobs
_DSL_MATRIX = [("ordinary", None, None)] + [
    (eng, lvl, fuse)
    for eng in ("optimized", "streaming")
    for lvl in (0, 2)
    for fuse in (False, True)]


@pytest.mark.parametrize("qname", ["Q1.1", "Q2.1", "Q3.1", "Q4.1", "Q4.1s"])
def test_dsl_vs_lambda_ssb_byte_identical(qname, ssb_dsl_data):
    """Every SSB builder constructed via the expression DSL produces
    byte-identical sink output to the pre-DSL lambda builder, on both
    operator backends, across Ordinary/Optimized/Streaming engines at
    optimize levels 0 and 2 with segment fusion off and on."""
    from repro.etl import BUILDERS
    for backend in _dsl_backends():
        for engine, level, fuse in _DSL_MATRIX:
            tables = {}
            for use_dsl in (True, False):
                qf = BUILDERS[qname](ssb_dsl_data, use_dsl=use_dsl)
                if engine == "ordinary":
                    OrdinaryEngine(qf.flow, chunk_rows=1024,
                                   backend=backend).run()
                else:
                    cls = (StreamingEngine if engine == "streaming"
                           else OptimizedEngine)
                    cls(qf.flow, OptimizeOptions(
                        num_splits=2, backend=backend,
                        optimize_level=level, calibration_rows=256,
                        fuse_segments=fuse)).run()
                tables[use_dsl] = qf.sink.result()
            label = f"{qname}/{backend}/{engine}/lvl={level}/fuse={fuse}"
            dsl_t, lam_t = tables[True], tables[False]
            assert set(dsl_t) == set(lam_t), f"{label}: column sets differ"
            for k in lam_t:
                assert dsl_t[k].dtype == lam_t[k].dtype, \
                    f"{label}: dtype of {k} differs"
                np.testing.assert_array_equal(
                    dsl_t[k], lam_t[k],
                    err_msg=f"{label}: column {k} differs (DSL vs lambda)")


# ---------------------------------------------------------------------------
#  kernel impl routes: the dense radix groupby must be byte-identical to the
#  legacy sort route AND, with the jax Lookup, to the numpy-backend oracle,
#  across the same property harness
# ---------------------------------------------------------------------------
def _run_with_impls(spec, backend, groupby_impl, fuse=False):
    import os
    _, num_splits, _ = spec
    saved = {k: os.environ.get(k) for k in (config.ENV_GROUPBY_IMPL,)}
    os.environ[config.ENV_GROUPBY_IMPL] = groupby_impl
    try:
        flow, sink = build_flow(spec)
        StreamingEngine(flow, OptimizeOptions(
            num_splits=num_splits, backend=backend,
            fuse_segments=fuse)).run()
        return sink.result()
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def _assert_tables_equal(got, oracle, label, check_dtype=True):
    """check_dtype=False for cross-backend comparisons: jax computes narrow
    ints where numpy keeps int64 (a backend property, not a route property)
    — there the oracle is the VALUES, not the width."""
    assert set(got) == set(oracle), f"{label}: column sets differ"
    for k in oracle:
        if check_dtype:
            assert got[k].dtype == oracle[k].dtype, f"{label}: dtype of {k}"
        np.testing.assert_array_equal(got[k], oracle[k],
                                      err_msg=f"{label}: column {k}")


@given(flow_spec())
@settings(max_examples=max(N_EXAMPLES // 4, 10), deadline=None)
def test_kernel_impl_routes_byte_identical(spec):
    """For every generated DAG: the jax backend under the dense-groupby
    route produces byte-identical sinks to the legacy sort route, and both
    its Lookup and its group-by agree with the numpy-backend oracle."""
    if "jax" not in _dsl_backends():      # pragma: no cover
        pytest.skip("jax backend unavailable")
    oracle = _run_with_impls(spec, "numpy", "sort")
    legacy = _run_with_impls(spec, "jax", "sort")
    kernel = _run_with_impls(spec, "jax", "reference")
    # within-backend: new routes vs legacy routes, dtypes strict
    _assert_tables_equal(kernel, legacy, f"kernel-vs-legacy (spec={spec})")
    # cross-backend: values vs the numpy oracle (int widths differ by design)
    _assert_tables_equal(kernel, oracle, f"kernel-vs-oracle (spec={spec})",
                         check_dtype=False)


def test_kernel_impl_interpret_route_fused():
    """The Pallas group-by kernel BODY (interpret mode) behind the same
    flows, with segment fusion on — the fused runner inlines the Lookup,
    the Aggregate rides the dense groupby."""
    if "jax" not in _dsl_backends():      # pragma: no cover
        pytest.skip("jax backend unavailable")
    spec = (7, 4, [("lookup", 3, 0, True),
                   ("expr", 3, 4, False),
                   ("filter", 4, 30, True),
                   ("agg", 2, 5, "sum"),
                   ("sort", 0)])
    legacy = _run_with_impls(spec, "jax", "sort")
    got = _run_with_impls(spec, "jax", "interpret", fuse=True)
    _assert_tables_equal(got, legacy, "interpret-routes+fusion")


# ---------------------------------------------------------------------------
#  sharded execution: hash/range-partitioned N-shard runs (partial →
#  shuffle → merge, core/shard) must be byte-identical to the serial run
#  for every generated DAG — the backend follows REPRO_BACKEND, so the CI
#  matrix exercises this under both numpy and jax
# ---------------------------------------------------------------------------
def _assert_sharded_identical(spec, shards, fuse=False):
    _, num_splits, _ = spec
    flow_s, sink_s = build_flow(spec)
    StreamingEngine(flow_s, OptimizeOptions(num_splits=num_splits,
                                            fuse_segments=fuse)).run()
    serial = sink_s.result()

    flow_n, sink_n = build_flow(spec)
    run = StreamingEngine(flow_n, OptimizeOptions(
        num_splits=num_splits, fuse_segments=fuse,
        shards=shards, shard_impl="inline")).run()
    sharded = sink_n.result()

    label = f"spec={spec} shards={shards} fuse={fuse}"
    assert set(sharded) == set(serial), f"{label}: column sets differ"
    for k in serial:
        assert sharded[k].dtype == serial[k].dtype, \
            f"{label}: dtype of {k} differs"
        np.testing.assert_array_equal(
            sharded[k], serial[k], err_msg=f"{label}: column {k} differs")
    if run.shards > 1:
        # every source row lands in exactly one shard
        assert sum(run.shard_rows) == ROWS, label


@given(flow_spec(), st.sampled_from([1, 2, 3]),
       st.sampled_from([False, True]))
@settings(max_examples=max(N_EXAMPLES // 4, 10), deadline=None)
def test_sharded_flow_equivalence(spec, shards, fuse):
    """For every generated DAG, running partitioned over N shards (N=1 is
    the serial fast path) produces byte-identical sink output to serial,
    with and without segment fusion stacked on top."""
    _assert_sharded_identical(spec, shards, fuse)


def test_sharded_equivalence_all_rules_fire_together():
    """Deterministic shape where lookup/expr/filter/agg/sort all appear —
    the aggregate is keyed on a source column, so this exercises the HASH
    partitioning mode (group-disjoint shards)."""
    spec = (7, 4, [("lookup", 3, 0, True),
                   ("expr", 3, 4, False),
                   ("filter", 4, 30, True),
                   ("agg", 2, 5, "sum"),
                   ("sort", 0)])
    _assert_sharded_identical(spec, 3)


def test_sharded_equivalence_boundary_and_empty():
    spec = (11, 2, [("boundary",), ("expr", 0, 3, True), ("boundary",)])
    _assert_sharded_identical(spec, 2)
    # two stacked filters can drop every row of a shard
    spec = (3, 2, [("filter", 3, 10, True), ("filter", 4, 10, True),
                   ("agg", 1, 2, "count")])
    _assert_sharded_identical(spec, 3)


# ---------------------------------------------------------------------------
#  fault tolerance: under any seeded plan of TRANSIENT faults the retried
#  run produces byte-identical sink output to the fault-free run — chunk
#  replay, run-level replay, edge faults and arena degradation all covered,
#  fused and unfused, both backends via REPRO_BACKEND
# ---------------------------------------------------------------------------
@st.composite
def fault_rules(draw):
    """1-3 transient single-fire rules.  Component is left None (fusion
    renames components, and the property must hold wherever the fault
    lands); per-rule count=1 keeps the worst-case failures at one dispatch
    (all rules hitting the same chunk) within the default REPRO_RETRY_MAX."""
    n = draw(st.integers(1, 3))
    rules = []
    for _ in range(n):
        rules.append(dict(
            site=draw(st.sampled_from(["chunk", "chunk", "kernel", "edge",
                                       "arena"])),
            kind="transient", count=1,
            after=draw(st.integers(0, 4)),
            split=draw(st.sampled_from([None, None, 0, 1]))))
    return rules


def _assert_fault_tolerant(spec, rule_kws, fuse):
    import os

    from repro.core import faults
    _, num_splits, _ = spec
    flow_b, sink_b = build_flow(spec)
    StreamingEngine(flow_b, OptimizeOptions(num_splits=num_splits,
                                            fuse_segments=fuse)).run()
    baseline = sink_b.result()

    saved = os.environ.get(config.ENV_RETRY_BACKOFF)
    os.environ[config.ENV_RETRY_BACKOFF] = "0.001"
    # the exact-attribution assertion below needs OUR plan to be the only
    # fault source — drop any ambient plan (the CI chaos leg exports one)
    saved_faults = os.environ.pop(config.ENV_FAULTS, None)
    try:
        plan = faults.FaultPlan([faults.FaultRule(**kw) for kw in rule_kws],
                                seed=1)
        flow_f, sink_f = build_flow(spec)
        with faults.fault_scope(plan):
            run = StreamingEngine(flow_f, OptimizeOptions(
                num_splits=num_splits, fuse_segments=fuse)).run()
        faulty = sink_f.result()
    finally:
        if saved is None:
            os.environ.pop(config.ENV_RETRY_BACKOFF, None)
        else:
            os.environ[config.ENV_RETRY_BACKOFF] = saved
        if saved_faults is not None:
            os.environ[config.ENV_FAULTS] = saved_faults
    label = f"spec={spec} rules={rule_kws} fuse={fuse}"
    assert set(faulty) == set(baseline), f"{label}: column sets differ"
    for k in baseline:
        assert faulty[k].dtype == baseline[k].dtype, \
            f"{label}: dtype of {k} differs"
        np.testing.assert_array_equal(
            faulty[k], baseline[k],
            err_msg=f"{label}: column {k} differs under fault plan")
    # every fired rule is attributed to the run's counters
    assert run.faults_injected == plan.injected, label


@given(flow_spec(), fault_rules(), st.sampled_from([True, False]))
@settings(max_examples=max(N_EXAMPLES // 4, 10), deadline=None)
def test_transient_fault_plans_byte_identical(spec, rule_kws, fuse):
    """For every generated DAG and every seeded transient fault plan, the
    retried/degraded run's sink output is byte-identical to fault-free."""
    _assert_fault_tolerant(spec, rule_kws, fuse)


def test_fault_plan_run_level_replay_deterministic():
    """Source + accumulate + edge faults all escalate to run-level replay
    (none is replay_safe); the rerun is byte-identical — a deterministic
    shape the generator rarely lands on exactly."""
    spec = (7, 4, [("lookup", 3, 0, True),
                   ("expr", 3, 4, False),
                   ("boundary",),
                   ("filter", 4, 30, True),
                   ("agg", 2, 5, "sum"),
                   ("sort", 0)])
    rules = [dict(site="chunk", kind="transient", count=1, after=0),
             dict(site="edge", kind="transient", count=1),
             dict(site="chunk", kind="transient", count=1, after=7)]
    _assert_fault_tolerant(spec, rules, fuse=True)


def test_dsl_flows_report_no_undeclared_refusals(ssb_dsl_data):
    """On DSL-built SSB flows the cost-based optimizer never refuses a
    rewrite for an undeclared read/write set (provenance is derived from
    the AST) — the silent-opt-out failure mode of the lambda API."""
    from repro.etl import BUILDERS
    for qname in ("Q1.1", "Q2.1", "Q3.1", "Q4.1", "Q4.1s"):
        qf = BUILDERS[qname](ssb_dsl_data, use_dsl=True)
        run = StreamingEngine(qf.flow, OptimizeOptions(
            num_splits=2, optimize_level=2, calibration_rows=256,
            fuse_segments=True)).run()
        bad = [r for r in run.refusals if "undeclared" in r["detail"]]
        assert not bad, f"{qname}: undeclared-read refusals on a DSL flow: {bad}"
