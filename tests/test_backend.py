"""Operator-backend subsystem tests.

1. Registry/selection semantics (names, env var, per-engine override).
2. Backend equivalence: every operator kernel's jax result equals the numpy
   reference on randomized inputs (hypothesis where available, fallback shim
   otherwise).
3. SharedCache edge cases: empty compact mask, zero-row split, `take` with
   reordering / out-of-window indices / duplicate-gather growth, and
   `concat_caches` column-set mismatch reporting.
4. Device-resident columns: a full query under the jax backend with
   host<->device transfer accounting.
"""
import numpy as np
import pytest

try:
    import hypothesis.strategies as st
    from hypothesis import given, settings
except ImportError:        # pragma: no cover — env without the `test` extra
    from _hypothesis_compat import given, settings, st

from repro.core import OptimizeOptions, StreamingEngine
from repro.core.backend import (available_backends, get_backend,
                                get_default_backend, resolve_backend,
                                set_default_backend)
from repro.core.shared_cache import (GLOBAL_CACHE_STATS, SharedCache,
                                     concat_caches)
from repro.etl import BUILDERS
from repro.etl.components import DimTable


def _np():
    return get_backend("numpy")


def _jax():
    return get_backend("jax")


def _host(bk, x):
    return np.asarray(bk.to_host(x))


# ---------------------------------------------------------------- registry
def test_registry_and_selection(monkeypatch):
    assert {"numpy", "jax"} <= set(available_backends())
    assert get_backend("numpy").name == "numpy"
    with pytest.raises(ValueError, match="unknown backend"):
        get_backend("tensorflow")
    # explicit name wins over everything
    monkeypatch.setenv("REPRO_BACKEND", "jax")
    assert resolve_backend("numpy").name == "numpy"
    # env var picks the default
    assert resolve_backend(None).name == "jax"
    monkeypatch.delenv("REPRO_BACKEND")
    # set_default_backend overrides the builtin default
    set_default_backend("jax")
    try:
        assert get_default_backend().name == "jax"
    finally:
        set_default_backend(None)


def test_backend_instances_are_singletons():
    assert get_backend("numpy") is get_backend("numpy")
    assert get_backend("jax") is get_backend("jax")


def test_dtype_width_canonicalization():
    # numpy reports native widths; jax canonicalizes 64-bit to 32-bit (x64 off)
    assert _np().dtype_width(np.int64) == 8
    assert _jax().dtype_width(np.int64) == 4
    assert _jax().dtype_width(np.float64) == 4
    cols = {"a": np.zeros(10, dtype=np.int64)}
    assert _np().est_nbytes(cols) == 80
    assert _jax().est_nbytes(cols) == 40


def test_etl_config_engine_options_carry_backend():
    from repro.configs.ssb_etl import ETLConfig
    cfg = ETLConfig(backend="jax")
    opts = cfg.engine_options()
    assert opts.backend == "jax"
    assert opts.num_splits == cfg.num_splits
    assert cfg.engine_options(backend="numpy").backend == "numpy"


def test_chunk_sensitive_source_ignores_backend_alignment(ssb_tiny):
    from repro.data import InputPipeline, PipelineConfig
    # the synthetic LM source is chunk-sensitive: identical batches under
    # both backends even though jax plans aligned chunk sizes
    pc = PipelineConfig(seq_len=32, global_batch=2, vocab_size=100,
                        docs_per_window=64, num_splits=4, pipeline_degree=2,
                        max_doc_len=48, min_doc_len=4, seed=9)
    batches = {}
    for bname in ("numpy", "jax"):
        set_default_backend(bname)
        try:
            it = iter(InputPipeline(pc))
            batches[bname] = [next(it) for _ in range(2)]
        finally:
            set_default_backend(None)
    for a, b in zip(batches["numpy"], batches["jax"]):
        np.testing.assert_array_equal(a, b)


def test_batch_align_feeds_planner_chunk_rows(ssb_tiny):
    from repro.core import backend_chunk_rows
    qf = BUILDERS["Q4.1"](ssb_tiny)
    assert backend_chunk_rows(qf.flow, 4, _np()) is None
    chunk = backend_chunk_rows(qf.flow, 4, _jax())
    align = _jax().batch_align
    assert chunk % align == 0
    assert chunk >= ssb_tiny.lineorder["lo_orderkey"].size // 4


# ------------------------------------------------- kernel equivalence (jax)
def _rand_cache(r):
    n = r.randint(1, 400)
    rng = np.random.default_rng(r.randint(0, 2**31))
    return SharedCache({
        "a": rng.integers(-50, 50, n).astype(np.int64),
        "b": rng.integers(0, 1000, n).astype(np.int64),
        "f": rng.uniform(-1e3, 1e3, n).astype(np.float64),
    }, n)


@given(st.integers(0, 2**31 - 1))
@settings(max_examples=15, deadline=None)
def test_filter_mask_equivalence(seed):
    import random
    c = _rand_cache(random.Random(seed))
    pred = lambda ca, r: (ca.col("a")[r] % 3 == 0) & (ca.col("b")[r] > 100)
    rows = slice(0, c.n)
    m_np = _np().filter_mask(pred, c, rows)
    m_jax = _host(_jax(), _jax().filter_mask(pred, c, rows))
    np.testing.assert_array_equal(m_np, m_jax)


@given(st.integers(0, 2**31 - 1))
@settings(max_examples=15, deadline=None)
def test_eval_expression_equivalence(seed):
    import random
    c = _rand_cache(random.Random(seed))
    fn = lambda ca, r: ca.col("a")[r] * 2 + ca.col("b")[r]
    rows = slice(0, c.n)
    np.testing.assert_array_equal(
        _np().eval_expression(fn, c, rows),
        _host(_jax(), _jax().eval_expression(fn, c, rows)))


@given(st.integers(0, 2**31 - 1))
@settings(max_examples=15, deadline=None)
def test_lookup_equivalence(seed):
    rng = np.random.default_rng(seed)
    n_dim = int(rng.integers(1, 100))
    keys = np.unique(rng.integers(0, 500, n_dim)).astype(np.int64)
    payload = {"v": rng.integers(0, 10_000, len(keys)).astype(np.int64)}
    qual = rng.random(len(keys)) < 0.7
    dim = DimTable(keys, payload, row_filter=qual)
    vals = rng.integers(0, 500, int(rng.integers(1, 300))).astype(np.int64)

    want = _np().lookup(dim, vals, {"g": "v"}, -1, "m")
    got = _jax().lookup(dim, vals, {"g": "v"}, -1, "m")
    assert list(got) == list(want) == ["g", "m"]
    np.testing.assert_array_equal(want["m"], _host(_jax(), got["m"]))
    np.testing.assert_array_equal(want["g"], _host(_jax(), got["g"]))


@given(st.integers(0, 2**31 - 1))
@settings(max_examples=10, deadline=None)
def test_groupby_reduce_equivalence(seed):
    rng = np.random.default_rng(seed)
    n = int(rng.integers(1, 500))
    keys = [rng.integers(0, 6, n).astype(np.int64),
            rng.integers(0, 4, n).astype(np.int64)]
    vals = rng.integers(-1000, 1000, n).astype(np.int64)
    aggs = {"s": (vals, "sum"), "mn": (vals, "min"), "mx": (vals, "max"),
            "av": (vals, "avg"), "ct": (vals, "count")}
    gk_np, ag_np = _np().groupby_reduce(keys, aggs, n)
    gk_j, ag_j = _jax().groupby_reduce(keys, aggs, n)
    for a, b in zip(gk_np, gk_j):
        np.testing.assert_array_equal(a, _host(_jax(), b))
    np.testing.assert_array_equal(ag_np["ct"], _host(_jax(), ag_j["ct"]))
    np.testing.assert_array_equal(ag_np["mn"], _host(_jax(), ag_j["mn"]))
    np.testing.assert_array_equal(ag_np["mx"], _host(_jax(), ag_j["mx"]))
    # float32 accumulation on device vs float64 reference
    rtol = _jax().oracle_rtol
    np.testing.assert_allclose(ag_np["s"], _host(_jax(), ag_j["s"]), rtol=rtol)
    np.testing.assert_allclose(ag_np["av"], _host(_jax(), ag_j["av"]), rtol=rtol)


def test_groupby_reduce_global_group():
    vals = np.array([1.0, 2.0, 3.0, 4.0])
    for bk in (_np(), _jax()):
        gk, ag = bk.groupby_reduce([], {"s": (vals, "sum"),
                                        "ct": (vals, "count")}, len(vals))
        assert gk == []
        assert float(_host(bk, ag["s"])[0]) == 10.0
        assert int(_host(bk, ag["ct"])[0]) == 4


def test_aggregate_global_empty_aggs_one_row():
    from repro.etl.components import Aggregate
    out = Aggregate("a", [], {}).finish(
        [SharedCache({"v": np.array([1.0, 2.0])}, 2)])
    assert out.n == 1 and out.names == []


def test_est_nbytes_counts_multidim_columns():
    cols = {"tokens": np.zeros((10, 32), dtype=np.int32)}
    assert _np().est_nbytes(cols) == 10 * 32 * 4


def test_device_view_shared_across_ranges_and_invalidated():
    bk = _jax()
    c = SharedCache({"x": np.arange(64, dtype=np.int64)}, 64)
    pred = lambda ca, r: ca.col("x")[r] % 2 == 0
    before = GLOBAL_CACHE_STATS.snapshot()
    bk.filter_mask(pred, c, slice(0, 32))
    mid = GLOBAL_CACHE_STATS.snapshot()
    bk.filter_mask(pred, c, slice(32, 64))     # same cache version: no upload
    after = GLOBAL_CACHE_STATS.snapshot()
    assert mid["h2d_bytes"] > before["h2d_bytes"]
    assert after["h2d_bytes"] == mid["h2d_bytes"]
    # mutation bumps version -> stale view dropped, column re-uploaded
    c.compact(np.ones(64, dtype=bool))
    m = bk.filter_mask(pred, c, slice(0, c.n))
    assert GLOBAL_CACHE_STATS.snapshot()["h2d_bytes"] > after["h2d_bytes"]
    np.testing.assert_array_equal(_host(bk, m), np.arange(64) % 2 == 0)


def test_groupby_reduce_rejects_unknown_op():
    for bk in (_np(), _jax()):
        with pytest.raises(ValueError, match="unknown agg op"):
            bk.groupby_reduce([np.zeros(3, np.int64)],
                              {"x": (np.zeros(3), "median")}, 3)


@given(st.integers(0, 2**31 - 1))
@settings(max_examples=15, deadline=None)
def test_sort_rows_equivalence(seed):
    rng = np.random.default_rng(seed)
    n = int(rng.integers(1, 400))
    keys = [rng.integers(0, 5, n).astype(np.int64),
            rng.integers(0, 7, n).astype(np.int64)]
    for ascending in (True, False):
        o_np = _np().sort_rows(keys, ascending=ascending)
        o_j = _host(_jax(), _jax().sort_rows(keys, ascending=ascending))
        # both lexsorts are stable => identical permutations
        np.testing.assert_array_equal(o_np, o_j)


# ------------------------------------------------------- SharedCache edges
def test_compact_empty_mask():
    c = SharedCache({"x": np.arange(10)}, 10)
    c.compact(np.zeros(10, dtype=bool))
    assert c.n == 0
    assert len(c.col("x")) == 0


def test_split_zero_rows():
    c = SharedCache({"x": np.array([], dtype=np.int64)}, 0)
    splits = c.split(4)
    assert len(splits) == 1
    assert splits[0].n == 0


def test_take_reorders_in_place():
    c = SharedCache({"x": np.arange(5, dtype=np.int64)}, 5)
    buf = c.columns["x"]
    c.take(np.array([4, 3, 2, 1, 0]))
    np.testing.assert_array_equal(c.col("x"), [4, 3, 2, 1, 0])
    assert c.columns["x"] is buf          # same buffer: shared caching


def test_take_rejects_out_of_window_indices():
    # buffer longer than the valid window: index into the stale tail must
    # raise, not silently read stale rows
    c = SharedCache({"x": np.arange(10, dtype=np.int64)}, 10)
    c.compact(np.arange(10) < 4)          # n=4; rows 4..9 are stale
    with pytest.raises(IndexError, match="valid row window"):
        c.take(np.array([0, 5]))
    with pytest.raises(IndexError, match="valid row window"):
        c.take(np.array([-5]))


def test_take_duplicate_gather_grows_buffer_explicitly():
    c = SharedCache({"x": np.arange(4, dtype=np.int64)}, 4)
    c.take(np.array([0, 1, 2, 3, 0, 1, 2, 3]))     # k > n: explicit grow
    assert c.n == 8
    np.testing.assert_array_equal(c.col("x"), [0, 1, 2, 3, 0, 1, 2, 3])
    assert len(c.columns["x"]) == 8


def test_take_rejects_boolean_mask():
    c = SharedCache({"x": np.arange(4)}, 4)
    with pytest.raises(TypeError, match="integer indices"):
        c.take(np.array([True, False, True, False]))


def test_concat_caches_reports_column_mismatch():
    a = SharedCache({"x": np.array([1]), "y": np.array([2])}, split_index=0)
    b = SharedCache({"x": np.array([3]), "z": np.array([4])}, split_index=1)
    with pytest.raises(ValueError) as ei:
        concat_caches([a, b])
    msg = str(ei.value)
    assert "cache #1" in msg and "'y'" in msg and "'z'" in msg


# ----------------------------------------------------- device columns (jax)
def test_device_columns_in_cache_roundtrip():
    bk = _jax()
    c = SharedCache({"h": np.arange(8, dtype=np.int64),
                     "d": bk.asarray(np.arange(8, dtype=np.int64) * 10)}, 8)
    c.compact(np.asarray(np.arange(8) % 2 == 0))
    assert c.n == 4
    np.testing.assert_array_equal(c.col("h"), [0, 2, 4, 6])
    np.testing.assert_array_equal(_host(bk, c.col("d")), [0, 20, 40, 60])
    c.take(np.array([3, 2, 1, 0]))
    out = c.to_dict()
    np.testing.assert_array_equal(out["h"], [6, 4, 2, 0])
    np.testing.assert_array_equal(out["d"], [60, 40, 20, 0])
    assert all(isinstance(v, np.ndarray) for v in out.values())


def test_jax_engine_run_records_transfers(ssb_tiny):
    before = GLOBAL_CACHE_STATS.snapshot()
    qf = BUILDERS["Q4.1"](ssb_tiny)
    expect = qf.oracle(ssb_tiny)
    r = StreamingEngine(qf.flow, OptimizeOptions(num_splits=2,
                                                 backend="jax")).run()
    got = qf.sink.result()
    assert r.backend == "jax"
    rtol = _jax().oracle_rtol
    for k in expect:
        np.testing.assert_allclose(got[k], expect[k], rtol=rtol,
                                   err_msg=f"Q4.1 jax column {k}")
    after = GLOBAL_CACHE_STATS.snapshot()
    # device kernels must have moved bytes host->device (and the engine run
    # must surface them — the §3 copy-cost analogue for the device tier)
    assert r.h2d_bytes > 0
    assert after["h2d_bytes"] - before["h2d_bytes"] >= r.h2d_bytes
    # backend-aligned source chunking came from the runtime plan
    assert r.runtime_plan.chunk_rows is not None
    assert r.runtime_plan.chunk_rows % _jax().batch_align == 0


def test_numpy_engine_reference_unchanged(ssb_tiny):
    qf = BUILDERS["Q4.1"](ssb_tiny)
    expect = qf.oracle(ssb_tiny)
    r = StreamingEngine(qf.flow, OptimizeOptions(num_splits=2,
                                                 backend="numpy")).run()
    got = qf.sink.result()
    assert r.backend == "numpy"
    for k in expect:
        np.testing.assert_allclose(got[k], expect[k], rtol=1e-9)
    assert r.h2d_bytes == 0 and r.d2h_bytes == 0


# ------------------------------------------------------- compile cache home
class _StubConfig:
    def __init__(self, cache_dir):
        self.jax_compilation_cache_dir = cache_dir
        self.updates = []

    def update(self, name, value):
        self.updates.append((name, value))


class _StubJax:
    def __init__(self, cache_dir):
        self.config = _StubConfig(cache_dir)


@pytest.mark.parametrize("env_dir,config_dir,checkout,placed", [
    (None, None, True, True),                # nothing set: the fixed repo path
    ("/elsewhere/cache", None, True, False),  # the variable is JAX's to read
    (None, "/set/in/code", True, False),     # already configured: left alone
    (None, None, False, False),              # installed package: no default
])
def test_place_compile_cache(monkeypatch, env_dir, config_dir, checkout,
                             placed):
    from pathlib import Path

    from repro.core.backend import jax_backend
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    repo = Path(__file__).resolve().parents[1]
    assert jax_backend.COMPILE_CACHE_DIR == repo / ".jax_cache"
    if not checkout:
        monkeypatch.setattr(jax_backend, "COMPILE_CACHE_DIR", None)
    stub = _StubJax(config_dir)
    jax_backend.place_compile_cache(stub)
    expect = ([("jax_compilation_cache_dir", str(repo / ".jax_cache"))]
              if placed else [])
    assert stub.config.updates == expect


def test_compile_cache_default_needs_a_source_checkout(tmp_path,
                                                       monkeypatch):
    """Only a ``src/`` tree beside ``pyproject.toml`` gets the in-checkout
    default; an installed package (no ``pyproject.toml`` four levels up)
    gets none."""
    from repro.core.backend import jax_backend
    module = tmp_path / "a" / "b" / "c" / "d" / "jax_backend.py"
    monkeypatch.setattr(jax_backend, "__file__", str(module))
    assert jax_backend._checkout_cache_dir() is None
    (tmp_path / "pyproject.toml").write_text("")
    assert jax_backend._checkout_cache_dir() == tmp_path / ".jax_cache"
