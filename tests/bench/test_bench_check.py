"""The check that decides ``correct``: the reference agrees with a jax run
of each traffic flow, and the comparison fails on a perturbed table, on
the control, and on a run whose timed path is broken underneath."""
from __future__ import annotations

import copy
import time

import numpy as np
import pytest

from bench_helpers import ALL_CELLS, tiny_cell
from bench import check, control, loops, registry


def _loop(name: str, seed: int = 11):
    cell = tiny_cell(name)
    cfg = cell.config
    data = registry.generator(cfg["generator"])(cfg, seed)
    return cell, loops.make(cfg, cell.traffic, cell.flow, data)


@pytest.mark.parametrize("name", ALL_CELLS)
def test_reference_agrees_with_a_jax_run(name):
    cell, loop = _loop(name)
    loop.warm_up()
    loop.step()
    loop.close()
    numbers = check.worst(loop.check(), 0)
    checks = check.verdict(numbers, cell.config["limits"])
    assert check.passed(checks), checks
    assert len(loop.outputs) == loop.n >= 2


def _perturb(table, kind, cols):
    t = {k: np.array(v, copy=True) for k, v in table.items()}
    if kind == "sum":
        c = cols["float"][0]
        t[c] = t[c] * (1 + 1e-2)
    elif kind == "drop_row":
        t = {k: v[1:] for k, v in t.items()}
    elif kind == "key":
        t[cols["keys"][0]][0] += 1
    elif kind == "order":
        t = {k: v[::-1] for k, v in t.items()}
    elif kind == "count":
        t[cols["exact"][0]][0] += 1
    return t


@pytest.mark.parametrize("name,kind", [
    ("ssb_sf1.q4.1", "sum"), ("ssb_sf1.q4.1", "drop_row"),
    ("ssb_sf1.q4.1", "key"), ("ssb_sf1.q4.1", "order"),
    ("ssb_sf1_serve.t256k", "count"), ("ssb_sf1_serve.t256k", "sum")])
def test_comparison_fails_on_a_perturbed_table(name, kind):
    from bench import reference
    cell = tiny_cell(name)
    cfg = cell.config
    data = registry.generator(cfg["generator"])(cfg, 5)
    feed = loops.feed(cfg, cell.traffic, cell.flow, data)
    expected = feed.reference(3)
    cols = reference.aggregate_columns(cell.flow)
    good = check.worst(feed.compare(sorted(expected.items()), expected), 0)
    assert check.passed(check.verdict(good, cfg["limits"]))
    bad = [(i, _perturb(t, kind, cols) if i == 1 else t)
           for i, t in sorted(expected.items())]
    numbers = check.worst(feed.compare(bad, expected), 0)
    assert not check.passed(check.verdict(numbers, cfg["limits"])), numbers


@pytest.mark.parametrize("name", ALL_CELLS)
def test_control_fails(name):
    cell = tiny_cell(name)
    window = 40 if "tick_rows" in cell.traffic else 3
    _, numbers = control.readings(cell, 3, window=window)
    assert not check.passed(check.verdict(numbers, cell.config["limits"]))


# --------------------------------------------------------------------------
#  the rest of a run, with the timed path broken underneath
# --------------------------------------------------------------------------
def _half(columns):
    n = len(next(iter(columns.values())))
    return {c: a[:n // 2] for c, a in columns.items()}


def _break_batch(monkeypatch, fault):
    import repro
    from bench import flows
    run = repro.Session.run
    last = {}

    def broken(self, f, **kw):
        if fault == "half_batch":
            src = flows.source_of(f)
            src.set_data(_half(src.columns))
        res = run(self, f, **kw)
        if fault == "altered_answer":
            res.table = {k: (np.asarray(v) * 1.01 if np.asarray(v).dtype.kind
                             == "f" else v) for k, v in res.table.items()}
        if fault == "state_unchanged":
            stale = last.get("table")
            last["table"] = res.table
            if stale is not None:
                res.table = stale
        return res
    monkeypatch.setattr(repro.Session, "run", broken)


def _break_serve(monkeypatch, fault):
    import repro
    tick = repro.ServeSession.tick
    last = {}

    def broken(self, columns, **kw):
        if fault == "half_batch":
            columns = _half(columns)
        if fault == "state_unchanged" and last.get("n", 0) % 2 == 1:
            last["n"] += 1
            return copy.copy(last["result"])
        r = tick(self, columns, **kw)
        if fault == "altered_answer":
            r.delta = {k: (np.asarray(v) * 1.01 if np.asarray(v).dtype.kind
                           == "f" else v) for k, v in r.delta.items()}
        last["n"] = last.get("n", 0) + 1
        last["result"] = r
        return r
    monkeypatch.setattr(repro.ServeSession, "tick", broken)


@pytest.mark.parametrize("name,fault", [
    (c, f) for c in ALL_CELLS
    for f in ("half_batch", "altered_answer", "state_unchanged")])
def test_a_broken_timed_path_is_not_correct(monkeypatch, name, fault):
    import jax
    import bench.run as bench_run
    cell = tiny_cell(name)
    if cell.config["path"] == "serve":
        _break_serve(monkeypatch, fault)
    else:
        _break_batch(monkeypatch, fault)
    result, _ = bench_run.measure(cell, 2**31 + 7, 0.2, False,
                                  jax.devices()[:1],
                                  {"hbm_bytes_per_s": 819e9},
                                  time.perf_counter())
    assert result["correct"] is False, result["checks"]
    assert list(result)[-1] == "checks"


@pytest.mark.parametrize("name", ALL_CELLS)
def test_a_sound_run_is_correct(name):
    import jax
    import bench.run as bench_run
    cell = tiny_cell(name)
    result, info = bench_run.measure(cell, 2**31 + 7, 0.2, False,
                                     jax.devices()[:1],
                                     {"hbm_bytes_per_s": 819e9},
                                     time.perf_counter())
    assert result["correct"] is True, result["checks"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    names = {m["name"] for m in cell.end_to_end}
    assert set(result["metrics"]) == names
    assert all(m["value"] > 0 for m in result["metrics"].values())
