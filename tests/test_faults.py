"""Fault-injection subsystem units: taxonomy, FaultPlan grammar and
plan-lifetime accounting, retry backoff, degradation ladders (sticky
routes, REPRO_DEGRADE gate), serving dead letters + long-lived faulty
session survival, pool shutdown leak accounting, and chunk
snapshot/restore.

Engine-level byte-equality under fault plans lives in
``test_fusion.py`` / ``test_optimizer_equivalence.py``; this file pins
the primitives those properties are built from.
"""
import threading
import time

import numpy as np
import pytest

import repro
from repro.core import config, faults
from repro.core.executor import SharedWorkerPool
from repro.core.faults import (FaultPlan, FaultRule, PermanentFault,
                               PoisonFault, TransientFault, backoff_schedule,
                               classify, fault_recorder, fault_scope,
                               restore_cache, retry_call, snapshot_cache,
                               with_retries)
from repro.core.shared_cache import SharedCache
from repro.session import replay_deltas


@pytest.fixture(autouse=True)
def _no_ambient_faults(monkeypatch):
    """These units assert EXACT fire/retry counts, so an ambient process-wide
    plan (the CI chaos leg exports REPRO_FAULTS) must not add injections."""
    monkeypatch.delenv("REPRO_FAULTS", raising=False)


# ---------------------------------------------------------------------------
#  taxonomy
# ---------------------------------------------------------------------------
def test_classify_injected_faults_carry_their_kind():
    assert classify(TransientFault("x")) == "transient"
    assert classify(PermanentFault("x")) == "permanent"
    assert classify(PoisonFault("x")) == "poison"


def test_classify_real_exceptions():
    for exc in (ConnectionError("net"), TimeoutError("slow"),
                InterruptedError("sig"), OSError("io")):
        assert classify(exc) == "transient"
    for exc in (ValueError("logic"), KeyError("k"), RuntimeError("r"),
                ZeroDivisionError()):
        assert classify(exc) == "permanent"


# ---------------------------------------------------------------------------
#  FaultPlan grammar + accounting
# ---------------------------------------------------------------------------
def test_plan_parse_full_grammar():
    plan = FaultPlan.parse(
        "seed=7; chunk@filt:kind=transient,count=2,after=1,split=3;"
        " kernel:kind=poison,p=0.5; arena:delay=0.01")
    assert plan.seed == 7 and len(plan.rules) == 3
    r0, r1, r2 = plan.rules
    assert (r0.site, r0.component, r0.kind) == ("chunk", "filt", "transient")
    assert (r0.count, r0.after, r0.split) == (2, 1, 3)
    assert (r1.site, r1.component, r1.kind, r1.p) == ("kernel", None,
                                                      "poison", 0.5)
    assert (r2.site, r2.kind, r2.delay_s) == ("arena", "transient", 0.01)


def test_plan_parse_rejects_unknowns():
    with pytest.raises(ValueError, match="unknown fault site"):
        FaultPlan.parse("disk:kind=transient")
    with pytest.raises(ValueError, match="unknown fault kind"):
        FaultPlan.parse("chunk:kind=flaky")
    with pytest.raises(ValueError, match="unknown fault-rule option"):
        FaultPlan.parse("chunk:bogus=1")


def test_rule_matching_component_split_after_count():
    plan = FaultPlan([FaultRule("chunk", component="filt", kind="transient",
                                count=2, after=1, split=0)])
    with fault_scope(plan):
        faults.inject("chunk", component="other", split=0)   # wrong component
        faults.inject("chunk", component="filt", split=1)    # wrong split
        faults.inject("kernel", component="filt", split=0)   # wrong site
        faults.inject("chunk", component="filt", split=0)    # seen=1 <= after
        with pytest.raises(TransientFault):
            faults.inject("chunk", component="filt", split=0)
        with pytest.raises(TransientFault):
            faults.inject("chunk", component="filt", split=0)
        faults.inject("chunk", component="filt", split=0)    # count exhausted
    assert plan.injected == 2
    assert plan.rules[0].fired == 2 and plan.rules[0].seen == 4


def test_plan_reset_restores_fresh_lifetime():
    plan = FaultPlan.parse("seed=5; chunk:kind=transient,count=1")
    with fault_scope(plan):
        with pytest.raises(TransientFault):
            faults.inject("chunk")
        faults.inject("chunk")                               # spent
    assert plan.injected == 1
    plan.reset()
    assert plan.injected == 0 and plan.rules[0].fired == 0
    with fault_scope(plan), pytest.raises(TransientFault):
        faults.inject("chunk")


def test_probabilistic_rule_is_seed_deterministic():
    def fires(seed):
        plan = FaultPlan([FaultRule("chunk", kind="transient", count=100,
                                    p=0.5)], seed=seed)
        out = []
        with fault_scope(plan):
            for _ in range(32):
                try:
                    faults.inject("chunk")
                    out.append(0)
                except TransientFault:
                    out.append(1)
        return out
    a, b = fires(11), fires(11)
    assert a == b                      # same seed => same firing pattern
    assert 0 < sum(a) < 32             # and p=0.5 actually skips some
    assert fires(12) != a


def test_delay_rule_sleeps_instead_of_raising():
    plan = FaultPlan([FaultRule("chunk", kind="transient", delay_s=0.02)])
    with fault_scope(plan):
        t0 = time.perf_counter()
        faults.inject("chunk")         # must NOT raise
        assert time.perf_counter() - t0 >= 0.015
    assert plan.injected == 1


def test_env_plan_installed_via_repro_faults(monkeypatch):
    monkeypatch.setenv("REPRO_FAULTS", "chunk:kind=permanent,count=1")
    assert faults.active()
    with pytest.raises(PermanentFault):
        faults.inject("chunk")
    faults.inject("chunk")             # plan-lifetime: spent for the process
    monkeypatch.delenv("REPRO_FAULTS")
    assert not faults.active()


# ---------------------------------------------------------------------------
#  retry helpers
# ---------------------------------------------------------------------------
def test_backoff_schedule_doubles_and_caps():
    assert backoff_schedule(5, 0.1) == [0.1, 0.2, 0.4, 0.8, 1.6]
    assert backoff_schedule(7, 0.1)[-2:] == [2.0, 2.0]   # capped
    assert backoff_schedule(0, 0.1) == []


def test_retry_call_retries_transient_until_success():
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise TransientFault("flaky")
        return "ok"

    with fault_recorder() as rec:
        assert retry_call(flaky, max_retries=3, backoff=0.0) == "ok"
    assert len(calls) == 3
    assert [r["attempt"] for r in rec.retries] == [0, 1]


def test_retry_call_permanent_raises_immediately():
    calls = []

    def broken():
        calls.append(1)
        raise ValueError("logic bug")

    with pytest.raises(ValueError):
        retry_call(broken, max_retries=5, backoff=0.0)
    assert len(calls) == 1


def test_retry_call_exhaustion_reraises_last():
    def always():
        raise TransientFault("never up")

    with pytest.raises(TransientFault):
        retry_call(always, max_retries=2, backoff=0.0)


def test_with_retries_filter_and_shim():
    from repro.train.fault import with_retries as train_with_retries
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 2:
            raise OSError("io")
        return 7

    assert with_retries(flaky, backoff=0.0)() == 7
    # the train-module shim delegates to the core implementation with its
    # historical (RuntimeError, OSError) filter
    calls.clear()
    assert train_with_retries(flaky, backoff=0.0)() == 7
    with pytest.raises(KeyError):      # outside retry_on: no retry
        with_retries(lambda: (_ for _ in ()).throw(KeyError("k")),
                     backoff=0.0)()


# ---------------------------------------------------------------------------
#  snapshot / restore
# ---------------------------------------------------------------------------
def test_snapshot_restore_rewinds_and_bumps_version():
    c = SharedCache({"a": np.arange(8, dtype=np.int64)}, 8)
    v0 = c.version
    snap = snapshot_cache(c)
    c.columns["a"][:] = -1
    c.columns["b"] = np.zeros(8, dtype=np.int64)
    c.n = 4
    restore_cache(c, snap)
    assert c.n == 8 and set(c.columns) == {"a"}
    np.testing.assert_array_equal(c.columns["a"], np.arange(8))
    assert c.version > v0              # device views must be invalidated
    # restored buffers are fresh — mutating the snapshot can't corrupt them
    snap["cols"]["a"][:] = 99
    np.testing.assert_array_equal(c.columns["a"], np.arange(8))


# ---------------------------------------------------------------------------
#  degradation ladders (jax kernel routes)
# ---------------------------------------------------------------------------
def _jax_backend():
    try:
        from repro.core.backend.jax_backend import JaxBackend
        return JaxBackend()
    except Exception:                  # pragma: no cover — no jax in env
        pytest.skip("jax backend unavailable")


def test_degraded_impl_walks_ladder_and_sticks():
    bk = _jax_backend()
    with fault_recorder() as rec:
        # interpret mode is never a production rung: a failing pallas
        # group-by goes straight to the XLA reference
        assert bk._degraded_impl("pallas", ValueError("x")) == "reference"
        assert bk._groupby_route == "reference"
        # off the TPU, auto group-by is the reference: it degrades to sort
        assert bk._degraded_impl("auto", ValueError("x")) == "sort"
        assert bk._groupby_route == "sort"
        # ladder floor: nothing below sort
        assert bk._degraded_impl("sort", ValueError("x")) is None
        assert bk._groupby_route == "sort"
    assert [(d.src, d.dst) for d in rec.degradations] == [
        ("groupby[pallas]", "reference"), ("groupby[reference]", "sort")]
    assert all(d.kind == "kernel" for d in rec.degradations)
    assert "interpret" not in bk._GROUPBY_LADDER


def test_degraded_impl_propagates_transient_and_injected():
    bk = _jax_backend()
    # transient => replay retries the SAME route instead of degrading
    assert bk._degraded_impl("auto", TransientFault("t")) is None
    assert bk._degraded_impl("auto", ConnectionError("t")) is None
    # injected permanent/poison faults must abort, not silently degrade
    assert bk._degraded_impl("pallas", PermanentFault("p")) is None
    assert bk._degraded_impl("pallas", PoisonFault("p")) is None
    assert bk._groupby_route is None


def test_degrade_disabled_by_env(monkeypatch):
    monkeypatch.setenv("REPRO_DEGRADE", "0")
    bk = _jax_backend()
    assert bk._degraded_impl("pallas", ValueError("x")) is None
    assert bk._groupby_route is None


# ---------------------------------------------------------------------------
#  serving: tick retries, dead letters, long-lived faulty session
# ---------------------------------------------------------------------------
def _serve_flow(rows=0, seed=0):
    r = np.random.RandomState(seed)
    data = {"k": r.randint(0, 5, rows).astype(np.int64),
            "v": r.randint(0, 100, rows).astype(np.int64)}
    schema = {c: a[:0] for c, a in data.items()}
    f = (repro.flow("faulty-serve").source(schema)
         .derive("e", repro.col("v") + 1)
         .aggregate(["k"], {"out": ("e", "sum"), "cnt": ("e", "count")})
         .sink())
    return f, data


def _tick_cols(seed, rows=40):
    r = np.random.RandomState(seed)
    return {"k": r.randint(0, 5, rows).astype(np.int64),
            "v": r.randint(0, 100, rows).astype(np.int64)}


def test_serving_transient_tick_retried_not_double_counted(monkeypatch):
    monkeypatch.setenv("REPRO_RETRY_BACKOFF", "0.001")
    f, _ = _serve_flow()
    session = repro.Session(metadata=None)
    plan = FaultPlan.parse("tick:kind=transient,count=2")
    with session.serve(f) as srv, fault_scope(plan):
        deltas = [srv.tick(_tick_cols(s)) for s in range(3)]
    assert plan.injected == 2
    assert sum(t.retries for t in deltas) == 2
    assert not any(t.dead_lettered for t in deltas)
    # the retried ticks' aggregates were rolled back before replay: the
    # replayed deltas equal a clean one-shot run of the same rows
    ref_f, _ = _serve_flow()
    with session.serve(ref_f) as ref_srv:
        ref = [ref_srv.tick(_tick_cols(s)) for s in range(3)]
    got, want = replay_deltas(deltas), replay_deltas(ref)
    for k in want:
        np.testing.assert_array_equal(got[k], want[k], err_msg=k)


def test_serving_poison_tick_dead_lettered_session_survives():
    f, _ = _serve_flow()
    session = repro.Session(metadata=None)
    plan = FaultPlan.parse("tick:kind=poison,count=1")
    with session.serve(f) as srv:
        with fault_scope(plan):
            bad = srv.tick(_tick_cols(0))
        good = srv.tick(_tick_cols(1))
    assert bad.dead_lettered and bad.delta == {}
    assert len(srv.dead_letters) == 1
    dl = srv.dead_letters[0]
    assert dl["attempts"] == 1         # poison: no pointless retries
    np.testing.assert_array_equal(dl["columns"]["k"], _tick_cols(0)["k"])
    assert not good.dead_lettered      # the stream moved on
    assert srv.dead_letters.maxlen == config.DEAD_LETTER_MAX


def test_serving_dead_letter_buffer_is_bounded():
    f, _ = _serve_flow()
    session = repro.Session(metadata=None)
    n = config.DEAD_LETTER_MAX + 20
    plan = FaultPlan([FaultRule("tick", kind="poison", count=n)])
    with session.serve(f) as srv, fault_scope(plan):
        for s in range(n):
            assert srv.tick(_tick_cols(s, rows=4)).dead_lettered
    assert len(srv.dead_letters) == config.DEAD_LETTER_MAX
    # oldest entries were evicted, newest kept (identified by their columns)
    np.testing.assert_array_equal(srv.dead_letters[0]["columns"]["v"],
                                  _tick_cols(20, rows=4)["v"])
    np.testing.assert_array_equal(srv.dead_letters[-1]["columns"]["v"],
                                  _tick_cols(n - 1, rows=4)["v"])


def test_serving_survives_long_mixed_fault_run(monkeypatch):
    """~60 ticks with interleaved transient and poison faults: the session
    must stay alive throughout, and the surviving deltas must replay to
    exactly the clean-run aggregate over the surviving rows."""
    monkeypatch.setenv("REPRO_RETRY_BACKOFF", "0.0001")
    f, _ = _serve_flow()
    session = repro.Session(metadata=None)
    plan = FaultPlan([
        FaultRule("tick", kind="transient", count=100, p=0.3),
        FaultRule("tick", kind="poison", count=100, p=0.1),
    ], seed=42)
    deltas, survived = [], []
    with session.serve(f) as srv, fault_scope(plan):
        for s in range(60):
            t = srv.tick(_tick_cols(s, rows=20))
            deltas.append(t)
            if not t.dead_lettered:
                survived.append(s)
    assert plan.injected > 0                         # the run was actually hit
    assert len(survived) < 60 or plan.injected >= 1
    ref_f, _ = _serve_flow()
    with session.serve(ref_f) as ref_srv:
        ref = [ref_srv.tick(_tick_cols(s, rows=20)) for s in survived]
    got, want = replay_deltas(deltas), replay_deltas(ref)
    assert set(got) == set(want)
    for k in want:
        np.testing.assert_array_equal(got[k], want[k], err_msg=k)


# ---------------------------------------------------------------------------
#  pool shutdown accounting (no silent thread leaks)
# ---------------------------------------------------------------------------
def test_pool_shutdown_joins_cleanly_by_default():
    pool = SharedWorkerPool(2, name="t-clean")
    futs = [pool.submit(lambda: time.sleep(0.01)) for _ in range(4)]
    for fut in futs:
        fut.result()
    pool.shutdown()
    assert pool.leaked_threads == 0
    assert pool.stats()["leaked_threads"] == 0


def test_pool_shutdown_counts_and_warns_on_stragglers():
    release = threading.Event()
    pool = SharedWorkerPool(1, name="t-straggler", join_timeout=0.05)
    pool.submit(release.wait)
    time.sleep(0.05)                   # let the worker pick the task up
    try:
        with pytest.warns(RuntimeWarning, match="did not join"):
            pool.shutdown(wait=True)
        assert pool.leaked_threads == 1
        assert pool.stats()["leaked_threads"] == 1
    finally:
        release.set()                  # unblock the straggler for real
