"""Segment fusion + CacheArena: discovery/refusal rules, fused-vs-unfused
engine equality, arena reuse + buffer-poisoning guards, split-aliasing
checks and scoped per-run statistics.

Backend follows ``REPRO_BACKEND`` (the CI matrix runs this file under both
``numpy`` and ``jax``); jax-specific assertions are gated on the active
backend.
"""
import os
import re

import numpy as np
import pytest

from repro.core import (GLOBAL_ARENA, GLOBAL_CACHE_STATS, CacheArena,
                        Dataflow, MetadataStore, OptimizeOptions,
                        OptimizedEngine, SharedCache, StreamingEngine,
                        cache_stats_scope, discover_segments,
                        fuse_segments_flow, get_default_backend, partition)
from repro.core import faults
from repro.core.component import StageBoundary
from repro.core.shared_cache import assert_views_disjoint
from repro.etl import BUILDERS
from repro.etl.components import (Aggregate, ArraySource, CollectSink,
                                  Converter, DimTable, Expression, Filter,
                                  FusedSegment, Lookup, Project)
from repro.etl.ssb import generate


# ---------------------------------------------------------------------------
#  helpers
# ---------------------------------------------------------------------------
def _data():
    return generate(lineorder_rows=12_000, customers=500, suppliers=80,
                    parts=300, seed=11)


def _chain_flow(*comps):
    flow = Dataflow("t")
    flow.chain(*comps)
    return flow


def _src(n=100, seed=0):
    r = np.random.RandomState(seed)
    return ArraySource("src", {
        "k": r.randint(1, 20, n).astype(np.int64),
        "v": r.randint(0, 100, n).astype(np.int64)})


def _expr(name, out="e"):
    return Expression(name, out, lambda c, r: c.col("v")[r] + 1, reads=["v"])


def _filt(name):
    return Filter(name, lambda c, r: c.col("v")[r] % 2 == 0, reads=["v"])


# ---------------------------------------------------------------------------
#  discovery + refusal rules
# ---------------------------------------------------------------------------
def test_discover_q41_single_segment():
    qf = BUILDERS["Q4.1"](_data())
    segs = discover_segments(qf.flow)
    assert segs == [["lookup_customer", "lookup_supplier", "lookup_part",
                     "lookup_date", "filter_unmatched", "project",
                     "profit_expr"]]


def test_discover_refuses_stage_boundary():
    """Q4.1s: the explicit StageBoundary cut splits the chain in two."""
    qf = BUILDERS["Q4.1s"](_data())
    segs = discover_segments(qf.flow)
    assert segs == [["lookup_customer", "lookup_supplier", "lookup_part",
                     "lookup_date"],
                    ["filter_unmatched", "project", "profit_expr"]]


def test_discover_refuses_block_and_singletons():
    """An Aggregate terminates the chain; a lone fusable component is not a
    segment (length >= 2)."""
    agg = Aggregate("agg", ["k"], {"s": ("v", "sum")})
    flow = _chain_flow(_src(), _expr("e1"), agg, _expr("e2", out="e2"),
                       CollectSink("sink"))
    assert discover_segments(flow) == []


def test_discover_refuses_order_sensitive():
    e1, e2, e3 = _expr("e1", "a"), _expr("e2", "b"), _expr("e3", "c")
    e2.order_sensitive = True
    flow = _chain_flow(_src(), e1, e2, e3, CollectSink("sink"))
    assert discover_segments(flow) == []


def test_discover_refuses_chunk_sensitive():
    e1, e2, e3 = _expr("e1", "a"), _expr("e2", "b"), _expr("e3", "c")
    e2.chunk_sensitive = True        # data semantics depend on chunking
    flow = _chain_flow(_src(), e1, e2, e3, CollectSink("sink"))
    assert discover_segments(flow) == []


def test_discover_refuses_fan_out():
    flow = Dataflow("fan")
    src, e1 = _src(), _expr("e1", "a")
    f1, f2 = _filt("f1"), _filt("f2")
    s1, s2 = CollectSink("s1"), CollectSink("s2")
    flow.chain(src, e1)
    flow.add(f1), flow.add(f2), flow.add(s1), flow.add(s2)
    flow.connect(e1, f1), flow.connect(e1, f2)
    flow.connect(f1, s1), flow.connect(f2, s2)
    # e1 fans out: no chain crosses it; f1/f2 are singletons
    assert discover_segments(flow) == []


def test_discover_through_terminal_aggregate():
    """``through_aggregates=True`` extends a chain through the single
    Aggregate that consumes it — the planner's marker for keep-mask
    deferral.  Default discovery is unchanged."""
    qf = BUILDERS["Q4.1"](_data())
    segs = discover_segments(qf.flow, through_aggregates=True)
    assert segs == [["lookup_customer", "lookup_supplier", "lookup_part",
                     "lookup_date", "filter_unmatched", "project",
                     "profit_expr", "groupby_sum"]]
    # the appended tail really is the Aggregate, not a fusable member
    agg = qf.flow.component("groupby_sum")
    assert getattr(agg, "segment_terminal_aggregate", False)


def test_discover_through_aggregate_requires_direct_single_edge():
    """No extension when something sits between the chain and the
    Aggregate, or when the Aggregate has fan-in."""
    agg = Aggregate("agg", ["k"], {"s": ("v", "sum")})
    flow = _chain_flow(_src(), _expr("e1", "a"), _expr("e2", "b"), agg,
                       CollectSink("sink"))
    assert discover_segments(flow, through_aggregates=True) == [
        ["e1", "e2", "agg"]]

    # fan-in: a second producer also feeds the Aggregate
    flow2 = Dataflow("fanin")
    src, e1, e2 = _src(), _expr("e1", "a"), _expr("e2", "b")
    agg2 = Aggregate("agg", ["k"], {"s": ("v", "sum")})
    side = _src(50, seed=3)
    side.name = "side"
    flow2.chain(src, e1, e2, agg2, CollectSink("sink"))
    flow2.add(side)
    flow2.connect(side, agg2)
    assert discover_segments(flow2, through_aggregates=True) == [
        ["e1", "e2"]]


def test_fuse_segments_flow_defers_mask_to_aggregate():
    """The fuse-segment-aggregate rewrite: the Aggregate stays a separate
    vertex, the FusedSegment carries the deferral metadata."""
    agg = Aggregate("agg", ["k"], {"s": ("v", "sum")})
    flow = _chain_flow(_src(), _expr("e1", "a"), _filt("f1"), agg,
                       CollectSink("sink"))
    rewrites = fuse_segments_flow(flow)
    assert [r.rule for r in rewrites] == ["fuse-segment",
                                          "fuse-segment-aggregate"]
    fused = flow.component("fusedseg(e1+f1)")
    assert fused.defer_to == "agg"
    assert fused.defer_cols == agg.consumed_columns()
    assert "defer_mask_to" in fused.spec()
    assert "agg" in set(flow.vertices)     # aggregate NOT collapsed
    partition(flow)


def test_deferring_segment_hands_on_only_the_aggregates_columns():
    """On the jax backend a segment that defers its keep-mask to the
    Aggregate leaves in the cache only the columns the Aggregate reads
    and the mask: the others would be merged for nothing."""
    pytest.importorskip("jax")
    from repro.core.backend import SEGMENT_KEEP_MASK, get_backend
    from repro.core.shared_cache import SharedCache
    agg = Aggregate("agg", ["k"], {"s": ("a", "sum")})
    flow = _chain_flow(_src(), _expr("e1", "a"), _filt("f1"), agg,
                       CollectSink("sink"))
    fuse_segments_flow(flow)
    fused = flow.component("fusedseg(e1+f1)")
    cache = SharedCache({k: v.copy()
                         for k, v in flow.component("src").columns.items()})
    get_backend("jax").compile_segment(fused)(cache)
    assert set(cache.names) == {"k", "a", SEGMENT_KEEP_MASK}


def test_fused_segment_provenance_and_spec():
    lk = Lookup("lk", DimTable(np.arange(1, 5, dtype=np.int64),
                               {"p": np.arange(4, dtype=np.int64)}),
                "k", {"p": "p"})
    ex = Expression("ex", "y", lambda c, r: c.col("p")[r] * 2, reads=["p"])
    fl = Filter("fl", lambda c, r: c.col("y")[r] > 0, reads=["y"])
    seg = FusedSegment.from_components([lk, ex, fl])
    assert seg.produced_columns() == frozenset({"p", "y"})
    # p and y are internal to the segment; only k is an external read
    assert seg.consumed_columns() == frozenset({"k"})
    assert seg.kernel_input_columns() == frozenset({"k"})
    assert not seg.row_preserving          # contains a row-dropper
    assert seg.spec()["members"] == "lk,ex,fl"
    # undeclared reads poison the declared sets (and warn by contract)
    with pytest.warns(DeprecationWarning, match="reads="):
        ex2 = Expression("ex2", "z", lambda c, r: c.col("y")[r])
    seg2 = FusedSegment.from_components([lk, ex, ex2])
    assert seg2.consumed_columns() is None
    assert seg2.kernel_input_columns() is None
    assert seg2.row_preserving


def test_from_components_rejects_unfusable():
    agg = Aggregate("agg", ["k"], {"s": ("v", "sum")})
    with pytest.raises(ValueError, match="cannot join"):
        FusedSegment.from_components([_expr("e1"), agg])


def test_fuse_segments_flow_rewrites_graph():
    flow = _chain_flow(_src(), _expr("e1", "a"), _expr("e2", "b"),
                       _filt("f1"), CollectSink("sink"))
    rewrites = fuse_segments_flow(flow)
    assert [r.rule for r in rewrites] == ["fuse-segment"]
    assert set(flow.vertices) == {"src", "fusedseg(e1+e2+f1)", "sink"}
    partition(flow)                 # still a valid partitionable dataflow


# ---------------------------------------------------------------------------
#  engine equality + instrumentation
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("qname", ["Q4.1", "Q4.1s"])
def test_fused_engine_byte_identical(qname):
    data = _data()
    qf_s = BUILDERS[qname](data)
    # fuse_segments=False pins the baseline even under REPRO_FUSION=1
    r_s = StreamingEngine(qf_s.flow, OptimizeOptions(
        num_splits=4, fuse_segments=False)).run()
    static = qf_s.sink.result()

    qf_f = BUILDERS[qname](data)
    r_f = StreamingEngine(qf_f.flow, OptimizeOptions(
        num_splits=4, fuse_segments=True)).run()
    fused = qf_f.sink.result()

    assert set(fused) == set(static)
    for k in static:
        assert fused[k].dtype == static[k].dtype
        np.testing.assert_array_equal(fused[k], static[k], err_msg=k)
    assert any(x["rule"] == "fuse-segment" for x in r_f.rewrites)
    # both SSB Q4 flows end their row-sync chain in groupby_sum: the
    # keep-mask deferral rewrite must fire alongside plain fusion
    assert any(x["rule"] == "fuse-segment-aggregate" for x in r_f.rewrites)
    # the headline: the whole row-sync chain dispatches once per chunk
    assert r_f.dispatch_calls < r_s.dispatch_calls
    if get_default_backend().name == "jax":
        assert r_f.h2d_transfers < r_s.h2d_transfers
        # deferral removes the per-chunk keep-mask syncs: the Aggregate's
        # reduce applies the mask on the device, in place of num_splits
        # per-chunk compacts
        assert r_s.d2h_transfers - r_f.d2h_transfers >= 4 - 1


def test_fusion_env_var_and_metadata_run_record(monkeypatch):
    monkeypatch.setenv("REPRO_FUSION", "1")
    data = _data()
    qf = BUILDERS["Q4.1"](data)
    md = MetadataStore()
    run = OptimizedEngine(qf.flow, OptimizeOptions(num_splits=2),
                          metadata=md).run()
    assert any(x["rule"] == "fuse-segment" for x in run.rewrites)
    rec = md.runs["ssb-q4.1"]
    assert rec["dispatch_calls"] == run.dispatch_calls
    assert rec["arena_hits"] == run.arena_hits
    # JSON roundtrip keeps the run record
    assert MetadataStore.from_json(md.to_json()).runs["ssb-q4.1"] == rec


def test_fused_segment_lying_read_declaration(monkeypatch):
    """A declared read set that misses a column the lambda touches: the host
    reference runner pulls the column lazily from the cache and stays
    correct; the jax kernel (which uploads exactly the declared set) fails —
    the degradation ladder falls back to the reference runner and records a
    VISIBLE kernel Degradation (never silently wrong rows), and with
    ``REPRO_DEGRADE=0`` the failure raises loudly as before."""
    monkeypatch.delenv("REPRO_FAULTS", raising=False)
    def build():
        ex = Expression("ex", "y",
                        lambda c, r: c.col("v")[r] + c.col("k")[r],
                        reads=["v"])          # lies: also reads k
        return _chain_flow(_src(), ex, _filt("fl"), CollectSink("sink"))

    if get_default_backend().name == "jax":
        monkeypatch.setenv("REPRO_DEGRADE", "0")
        flow = build()
        fuse_segments_flow(flow)
        with pytest.raises(Exception, match="not visible|k"):
            StreamingEngine(flow, OptimizeOptions(num_splits=2)).run()

        monkeypatch.delenv("REPRO_DEGRADE")
        flow_s = build()
        sink_s = flow_s.component("sink")
        StreamingEngine(flow_s, OptimizeOptions(
            num_splits=2, fuse_segments=False)).run()
        flow_d = build()
        sink_d = flow_d.component("sink")
        assert fuse_segments_flow(flow_d)
        run = StreamingEngine(flow_d, OptimizeOptions(num_splits=2)).run()
        assert run.degradations >= 1
        assert any(d["kind"] == "kernel" and d["dst"] == "reference"
                   for d in run.degradation_events)
        for k, v in sink_s.result().items():
            np.testing.assert_array_equal(sink_d.result()[k], v, err_msg=k)
    else:
        flow_s = build()
        sink_s = flow_s.component("sink")
        StreamingEngine(flow_s, OptimizeOptions(num_splits=2)).run()
        flow_f = build()
        sink_f = flow_f.component("sink")
        assert fuse_segments_flow(flow_f)
        StreamingEngine(flow_f, OptimizeOptions(num_splits=2)).run()
        for k, v in sink_s.result().items():
            np.testing.assert_array_equal(sink_f.result()[k], v, err_msg=k)


def test_fused_segment_does_not_resurrect_projected_columns():
    """A component reading a column an earlier Project dropped fails inside
    the fused segment exactly like the unfused chain (KeyError) — the host
    runner must not silently re-read it from the underlying cache."""
    def build():
        proj = Project("proj", ["k"])                 # drops v
        conv = Converter("conv", {"v": np.float32})   # reads dropped v
        return _chain_flow(_src(), proj, conv, CollectSink("sink"))

    flow_u = build()
    with pytest.raises(KeyError):
        StreamingEngine(flow_u, OptimizeOptions(
            num_splits=2, fuse_segments=False)).run()

    flow_f = build()
    assert fuse_segments_flow(flow_f)
    with pytest.raises(KeyError):
        StreamingEngine(flow_f, OptimizeOptions(num_splits=2)).run()


# ---------------------------------------------------------------------------
#  Lookups read slot-addressed columns, on direct and hashed tables (jax)
# ---------------------------------------------------------------------------
_I32 = np.iinfo(np.int32)
_SRNG = np.random.default_rng(16)


def _slot_case(keys, payload, probes, default=-1, flag="ok", where=None,
               cols=("v",), carrier="v"):
    """``carrier``: the column the match bit rides on, None where a
    ``matched`` array is gathered instead (or no flag is asked for)."""
    return dict(keys=np.asarray(keys, np.int64), payload=payload,
                probes=np.asarray(probes, np.int64), default=default,
                flag=flag, where=where, cols=cols,
                carrier=carrier if flag else None)


_SLOT_CASES = {
    # keys below, inside and above the span, with and without the flag
    "span_edges": _slot_case(np.arange(100, 400), _SRNG.integers(0, 50, 300),
                             np.arange(40, 470)),
    "span_edges_unflagged": _slot_case(
        np.arange(100, 400), _SRNG.integers(0, 50, 300), np.arange(40, 470),
        flag=None),
    "empty_slots": _slot_case(
        np.sort(_SRNG.choice(4_000, 2_500, replace=False)),
        _SRNG.integers(0, 9, 2_500), np.arange(-10, 4_100)),
    "unqualified_rows": _slot_case(
        np.arange(0, 600), _SRNG.integers(0, 7, 600), np.arange(-5, 610),
        where=_SRNG.random(600) < 0.5),
    "duplicate_keys": _slot_case(
        np.r_[np.arange(10, 90), np.arange(10, 90, 3), np.arange(40, 50)],
        np.arange(117), np.arange(0, 100)),
    "negative_payloads": _slot_case(
        np.arange(-300, 300, 2), _SRNG.integers(-1_000, 0, 300),
        np.arange(-320, 320)),
    "default_inside_range": _slot_case(
        np.arange(0, 500), _SRNG.integers(0, 50, 500), np.arange(-20, 520),
        default=5),
    "float_payload": _slot_case(
        np.arange(7, 407), _SRNG.normal(size=400), np.arange(0, 420),
        carrier=None),
    "float_payload_unflagged": _slot_case(
        np.arange(7, 407), _SRNG.normal(size=400), np.arange(0, 420),
        flag=None),
    "no_returned_column": _slot_case(
        np.arange(50, 250, 3), np.zeros(67, np.int64), np.arange(0, 300),
        cols=(), carrier=None),
    "full_int32_span": _slot_case(
        np.arange(1, 301),
        np.r_[_I32.min, _I32.max, _SRNG.integers(-9, 9, 298)],
        np.arange(-3, 310), carrier=None),
    "two_columns": _slot_case(
        np.arange(1, 301), _SRNG.normal(size=300), np.arange(-3, 310),
        cols=("v", "w"), carrier="w"),
}


def _slot_lookup(case):
    """The outputs of a fused segment of one Lookup (on the slot function
    the build picks) over ``case``'s probes, its runner and its table."""
    from repro.core.backend.jax_backend import JaxBackend
    payload = {"v": case["payload"],
               "w": np.arange(len(case["keys"])) * 7 - 500}
    dim = DimTable(case["keys"], payload, row_filter=case["where"],
                   name="d")
    returns = {f"out_{c}": c for c in case["cols"]}
    lk = Lookup("lk", dim, "fk", returns, default=case["default"],
                matched_flag=case["flag"])
    runner = JaxBackend().compile_segment(FusedSegment.from_components([lk]))
    cache = SharedCache({"fk": case["probes"].copy()})
    runner(cache)
    names = sorted(returns) + ([case["flag"]] if case["flag"] else [])
    return {n: np.asarray(cache.col(n)) for n in names}, runner, dim


@pytest.mark.parametrize("case", sorted(_SLOT_CASES))
def test_lookup_on_both_table_forms_matches_the_host_probe(case,
                                                           monkeypatch):
    """A Lookup reads slot-addressed columns on a direct table and on an
    fmix32 one (the same keys forced off direct addressing), fused and
    unfused: the four outputs and match flags are byte-identical, and agree
    with the host reference's searchsorted probe (``DimTable.probe``)."""
    pytest.importorskip("jax")
    from repro.core.backend.jax_backend import JaxBackend
    c = _SLOT_CASES[case]
    got, runner, dim = _slot_lookup(c)
    (look,) = runner.device_dims()
    assert look.form.base is not None
    # the match bit rides on an integer column whose span leaves room
    assert (look.plan.carrier and look.plan.carrier[0]) == c["carrier"]
    with monkeypatch.context() as m:
        m.setattr("repro.kernels.hash_join.ref.DIRECT_MAX_RATIO", 0)
        hashed, hrunner, hdim = _slot_lookup(c)
        (hlook,) = hrunner.device_dims()
        assert hlook.form.base is None
        assert hlook.plan.carrier == look.plan.carrier
    returns = {f"out_{col}": col for col in c["cols"]}
    unfused = {}
    for name, d in (("unfused", dim), ("unfused hash", hdim)):
        out = JaxBackend().lookup(d, c["probes"], returns, c["default"],
                                  c["flag"])
        unfused[name] = {k: np.asarray(v) for k, v in out.items()}
    idx, matched = dim.probe(c["probes"])
    for name, want in (("hash", hashed), *unfused.items()):
        assert got.keys() == want.keys(), name
        for col in got:
            assert got[col].dtype == want[col].dtype, (name, col)
            assert got[col].tobytes() == want[col].tobytes(), (name, col)
    if c["flag"]:
        np.testing.assert_array_equal(got[c["flag"]], matched)
    for col in c["cols"]:
        out = got[f"out_{col}"]
        want = np.where(matched, dim.payload[col][idx],
                        np.asarray(c["default"])).astype(out.dtype)
        np.testing.assert_array_equal(out, want, err_msg=col)


@pytest.mark.parametrize("cols,flag", [(("v",), None), ((), "ok"),
                                       (("v", "w"), "ok")])
def test_lookup_into_an_empty_table_answers_every_row_unmatched(cols, flag):
    """A table with no rows answers every probe unmatched, with the
    Lookup's ``default`` in each returned column: fused and unfused on the
    jax backend, and on the numpy reference, as ``DimTable.probe`` says."""
    pytest.importorskip("jax")
    from repro.core.backend.jax_backend import JaxBackend
    from repro.core.backend.numpy_backend import NumpyBackend
    probes = np.arange(-3, 40, dtype=np.int64)
    empty = np.zeros(0, np.int64)
    dim = DimTable(empty, {"v": empty, "w": np.zeros(0, np.float32)},
                   name="d")
    returns = {f"out_{c}": c for c in cols}
    lk = Lookup("lk", dim, "fk", returns, default=7, matched_flag=flag)
    runner = JaxBackend().compile_segment(FusedSegment.from_components([lk]))
    cache = SharedCache({"fk": probes.copy()})
    runner(cache)
    names = sorted(returns) + ([flag] if flag else [])
    runs = {"fused": {n: np.asarray(cache.col(n)) for n in names}}
    for bk in (JaxBackend(), NumpyBackend()):
        out = bk.lookup(dim, probes, returns, 7, flag)
        runs[type(bk).__name__] = {n: np.asarray(a) for n, a in out.items()}
    _, matched = dim.probe(probes)
    assert not matched.any()
    for name, got in runs.items():
        assert sorted(got) == sorted(names), name
        if flag:
            np.testing.assert_array_equal(got[flag], matched, err_msg=name)
        for out, col in returns.items():
            assert got[out].shape == probes.shape, (name, out)
            assert (got[out] == 7).all(), (name, out)


def _ssb_segment(query, sparse_part=False):
    """The fused segment of ``query``'s row-synchronized members over SF1
    dimension tables (small fact table), and its runner."""
    from repro.core.backend.jax_backend import JaxBackend
    data = generate(lineorder_rows=1024, customers=30_000, suppliers=2_000,
                    parts=200_000, seed=3)
    if sparse_part:
        # part keys 1009 apart: the part table falls back to fmix32
        data.part["p_partkey"] = data.part["p_partkey"] * 1009
        data.lineorder["lo_partkey"] = data.lineorder["lo_partkey"] * 1009
    members = {"Q4.1": ["lookup_customer", "lookup_supplier", "lookup_part",
                        "lookup_date", "filter_unmatched", "project",
                        "profit_expr"],
               "Q1.1": ["lookup_date", "filter", "revenue_expr"]}[query]
    qf = BUILDERS[query](data)
    seg = FusedSegment.from_components(
        [qf.flow.component(m) for m in members])
    return data, JaxBackend().compile_segment(seg)


@pytest.mark.parametrize("query,sparse_part,gathers", [
    ("Q4.1", False, 4), ("Q1.1", False, 1), ("Q4.1", True, None)])
def test_fused_segment_gathers_once_per_returned_column(query, sparse_part,
                                                        gathers):
    """Lowered for the CPU, the fused Q4.1 segment over SSB's direct SF1
    tables holds one gather per Lookup (four payloads) and Q1.1's one (its
    ``d_ok`` flag rides on ``d_year``): no ``slot_idx`` or ``qualifies``
    gather.  The tables' sizes all differ, so no two gathers share one
    outlined function.  A hashed part table keeps its probe loop (a
    ``slot_idx`` and a slot-key gather a pass), and after it gathers its
    one returned column, as a direct one does: the compiled program holds
    six gathers."""
    import jax.numpy as jnp
    data, runner = _ssb_segment(query, sparse_part)
    bucket = 2048
    entries, total = runner.pack_layout(
        bucket, [(c, data.lineorder[c].dtype) for c in sorted(runner.inputs)])
    dims = runner.device_dims()
    lowered = runner._jit.lower((bucket, tuple(entries)),
                                jnp.zeros((total,), jnp.uint8), {}, dims)
    text = lowered.as_text()
    n = text.count('"stablehlo.gather"(')
    assert [look.gathers for look in dims] == [1] * len(dims)
    if gathers is not None:
        assert n == gathers == len(dims)
        assert "stablehlo.while" not in text
    else:
        assert "stablehlo.while" in text
        # gathers of one table shape share an outlined function: count
        # them in the compiled program, where each is its own op
        compiled = lowered.compile().as_text()
        assert len(re.findall(r"\bgather\(", compiled)) == 2 + len(dims)
        assert [look.form.base is None for look in dims] == [False, False,
                                                             True, False]


# ---------------------------------------------------------------------------
#  CacheArena
# ---------------------------------------------------------------------------
def test_arena_reuse_hit_miss_counters():
    arena = CacheArena(enabled=True, max_bytes=1 << 20)
    before = GLOBAL_CACHE_STATS.snapshot()
    a1, r1 = arena.acquire(np.int64, (100,))
    assert a1.shape == (100,) and a1.dtype == np.int64
    arena.release(r1)
    a2, r2 = arena.acquire(np.int64, (100,))
    assert r2 is r1                       # same root buffer recycled
    after = GLOBAL_CACHE_STATS.snapshot()
    assert after["arena_hits"] - before["arena_hits"] == 1
    assert after["arena_misses"] - before["arena_misses"] == 1
    assert after["arena_bytes_reused"] - before["arena_bytes_reused"] == 800


def test_arena_bucket_cap_and_foreign_buffers():
    arena = CacheArena(enabled=True, max_bytes=1024)
    _, r1 = arena.acquire(np.uint8, (4096,))
    arena.release(r1)                     # 4096 > cap: dropped
    assert arena.pooled_buffers() == 0
    arena.release(np.empty(100, np.uint8))   # not a pow2 arena bucket
    arena.release(np.empty(512, np.int64))   # wrong dtype
    assert arena.pooled_buffers() == 0


def test_arena_disabled_is_plain_allocation():
    arena = CacheArena(enabled=False)
    arr, root = arena.acquire(np.float64, (10,))
    assert root is None and arr.flags["OWNDATA"]
    arena.release(root)                   # no-op


def test_arena_poisoning_and_double_release_guard(monkeypatch):
    monkeypatch.setenv("REPRO_CACHE_GUARD", "1")
    arena = CacheArena(enabled=True, max_bytes=1 << 20)
    arr, root = arena.acquire(np.uint8, (300,))
    arr[:] = 7
    arena.release(root)
    assert (root == 0xAB).all()           # poisoned: use-after-recycle is loud
    with pytest.raises(RuntimeError, match="double release"):
        arena.release(root)


def test_recycle_returns_buffers_and_is_idempotent():
    arena_before = GLOBAL_ARENA.pooled_buffers()
    c = SharedCache({"a": np.arange(64, dtype=np.int64)}, 64)
    cp = c.copy()
    assert cp._owned is not None
    cp.recycle()
    assert cp._owned is None
    cp.recycle()                          # idempotent
    assert GLOBAL_ARENA.pooled_buffers() >= arena_before


def test_engine_equality_under_guard(monkeypatch):
    """With poisoning on, a premature recycle anywhere in the executor would
    corrupt sink rows — byte equality against the unfused/no-guard run is
    the use-after-recycle detector."""
    data = _data()
    qf = BUILDERS["Q4.1"](data)
    StreamingEngine(qf.flow, OptimizeOptions(num_splits=4)).run()
    baseline = qf.sink.result()

    monkeypatch.setenv("REPRO_CACHE_GUARD", "1")
    qf2 = BUILDERS["Q4.1"](data)
    StreamingEngine(qf2.flow, OptimizeOptions(
        num_splits=4, fuse_segments=True)).run()
    guarded = qf2.sink.result()
    for k in baseline:
        np.testing.assert_array_equal(guarded[k], baseline[k], err_msg=k)


def test_fault_retry_under_guard_no_poisoned_reuse(monkeypatch):
    """Mid-segment transient faults abort chunks that already wrote into
    arena-pooled buffers; the retry must not see those poisoned bytes.
    With REPRO_CACHE_GUARD=1 recycled buffers are 0xAB-filled and double
    releases raise, so byte equality against the fault-free baseline is
    the use-after-recycle / double-release detector for the replay path."""
    monkeypatch.delenv("REPRO_FAULTS", raising=False)   # exact counts below
    data = _data()
    qf = BUILDERS["Q4.1"](data)
    StreamingEngine(qf.flow, OptimizeOptions(
        num_splits=4, fuse_segments=True)).run()
    baseline = qf.sink.result()

    monkeypatch.setenv("REPRO_CACHE_GUARD", "1")
    monkeypatch.setenv("REPRO_RETRY_BACKOFF", "0.001")
    plan = faults.FaultPlan.parse(
        "seed=3; kernel:kind=transient,count=1,after=1; "
        "chunk:kind=transient,count=1")
    qf2 = BUILDERS["Q4.1"](data)
    with faults.fault_scope(plan):
        run = StreamingEngine(qf2.flow, OptimizeOptions(
            num_splits=4, fuse_segments=True)).run()
    faulty = qf2.sink.result()

    assert run.faults_injected == plan.injected >= 1
    assert run.retries >= 1
    for k in baseline:
        np.testing.assert_array_equal(faulty[k], baseline[k], err_msg=k)


def test_permanent_fault_aborts_and_releases_buffers(monkeypatch):
    """A permanent mid-segment fault must abort promptly (no retries), hand
    every in-flight buffer back to the arena exactly once (guard raises on
    double release), and leave the flow rerunnable byte-identically."""
    monkeypatch.delenv("REPRO_FAULTS", raising=False)   # exact counts below
    monkeypatch.setenv("REPRO_CACHE_GUARD", "1")
    data = _data()
    qf = BUILDERS["Q4.1"](data)
    plan = faults.FaultPlan.parse("kernel:kind=permanent,after=1")
    with faults.fault_scope(plan):
        with pytest.raises(faults.PermanentFault):
            StreamingEngine(qf.flow, OptimizeOptions(
                num_splits=4, fuse_segments=True)).run()
    assert plan.injected == 1

    # same flow objects, no plan: the rerun must match a fresh baseline —
    # stranded or double-released buffers from the abort would corrupt it
    run = StreamingEngine(qf.flow, OptimizeOptions(
        num_splits=4, fuse_segments=True)).run()
    rerun = qf.sink.result()
    assert run.retries == 0 and run.faults_injected == 0

    qf_ref = BUILDERS["Q4.1"](data)
    StreamingEngine(qf_ref.flow, OptimizeOptions(
        num_splits=4, fuse_segments=True)).run()
    ref = qf_ref.sink.result()
    for k in ref:
        np.testing.assert_array_equal(rerun[k], ref[k], err_msg=k)


# ---------------------------------------------------------------------------
#  split aliasing guard
# ---------------------------------------------------------------------------
def test_split_views_alias_parent_but_are_disjoint():
    c = SharedCache({"a": np.arange(100, dtype=np.int64)}, 100)
    parts = c.split(4)
    assert all(p.columns["a"].base is not None for p in parts)  # views
    assert_views_disjoint(parts)          # contract: pairwise disjoint


def test_overlap_guard_raises_on_aliased_splits():
    base = np.arange(100, dtype=np.int64)
    a = SharedCache({"a": base[0:60]}, 60)
    b = SharedCache({"a": base[40:100]}, 60)   # overlaps rows 40..59
    with pytest.raises(RuntimeError, match="overlap"):
        assert_views_disjoint([a, b])


def test_split_guard_active_under_env(monkeypatch):
    monkeypatch.setenv("REPRO_CACHE_GUARD", "1")
    c = SharedCache({"a": np.arange(50, dtype=np.int64)}, 50)
    assert len(c.split(3)) == 3           # clean splits pass the check


# ---------------------------------------------------------------------------
#  scoped per-run statistics
# ---------------------------------------------------------------------------
def test_cache_stats_scope_attributes_per_run():
    from repro.core.shared_cache import record_copy
    c = SharedCache({"a": np.arange(256, dtype=np.int64)}, 256)
    record_copy(c)                        # outside any scope
    with cache_stats_scope() as s1:
        record_copy(c)
        record_copy(c)
        with cache_stats_scope() as s2:   # nested scopes both observe
            record_copy(c)
    assert s1.snapshot()["copies"] == 3
    assert s2.snapshot()["copies"] == 1


def test_engine_runs_report_scoped_counters():
    """Two sequential engine runs attribute copies/arena traffic to their
    own EngineRun — equal workloads report equal counters."""
    data = _data()
    runs = []
    for _ in range(2):
        qf = BUILDERS["Q4.1"](data)
        runs.append(StreamingEngine(
            qf.flow, OptimizeOptions(num_splits=4)).run())
    assert runs[0].copies == runs[1].copies
    assert runs[0].dispatch_calls == runs[1].dispatch_calls
    assert runs[0].h2d_transfers == runs[1].h2d_transfers


def test_worker_pool_propagates_scope():
    from repro.core import SharedWorkerPool
    from repro.core.shared_cache import record_transfer
    pool = SharedWorkerPool(2)
    try:
        with cache_stats_scope() as s:
            futs = [pool.submit(record_transfer, "h2d", 10)
                    for _ in range(4)]
            for f in futs:
                f.result()
        assert s.snapshot()["h2d_transfers"] == 4
        assert s.snapshot()["h2d_bytes"] == 40
    finally:
        pool.shutdown()


# ---------------------------------------------------------------------------
#  bench JSON writer
# ---------------------------------------------------------------------------
def test_bench_json_schema(tmp_path, monkeypatch):
    import json as _json
    import sys
    sys.path.insert(0, os.path.dirname(os.path.dirname(__file__)))
    from benchmarks.run import write_bench_json
    monkeypatch.setenv("BENCH_TAG", "unittest")
    path = tmp_path / "BENCH_unittest.json"
    stats = GLOBAL_CACHE_STATS.snapshot()
    write_bench_json({"sec": {"wall_s": 1.0, "status": "ok",
                              "cache_stats": stats}},
                     mode="full", path=str(path))
    payload = _json.loads(path.read_text())
    assert payload["tag"] == "unittest"
    assert payload["mode"] == "full"
    assert payload["backend"] in ("numpy", "jax")
    sec = payload["sections"]["sec"]
    assert sec["status"] == "ok"
    for key in ("copies", "h2d_transfers", "arena_hits", "arena_misses",
                "arena_bytes_reused"):
        assert key in sec["cache_stats"]
