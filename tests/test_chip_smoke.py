"""The chip smoke's phases on the CPU at a tiny size, so they cannot rot.

``chip_smoke.py`` drives every SSB flow (fused and unfused), the resident
serving loop and the 4-shard mesh route through ``Session``, and checks
each sink against its numpy oracle.  Here the same phase functions run at
~20k lineorder rows; ``main()`` itself must refuse a device that is not a
TPU, and the process shard route must refuse when the default device is an
accelerator.
"""
from __future__ import annotations

import importlib.util
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
ROWS = 20_000


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def data(smoke):
    return smoke.make_data(ROWS, seed=5)


@pytest.mark.parametrize("fuse", [False, True])
def test_flows_match_oracles(smoke, data, fuse, capsys):
    assert smoke.run_flows(data, fuse=fuse) == []
    lines = [ln for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("smoke flow=")]
    assert len(lines) == len(smoke.BUILDERS)
    assert all(" oracle=ok " in ln for ln in lines)


@pytest.mark.parametrize("fuse", [False, True])
def test_serving_replays_batch_and_oracle(smoke, data, fuse):
    assert smoke.run_serving(data, ticks=8, tick_rows=2048, fuse=fuse) == []


def test_kernel_sums_match_float64(smoke, capsys):
    assert smoke.run_kernel_sums(ROWS) == []
    lines = [ln for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("smoke kernel=")]
    assert len(lines) == 2 and all(" oracle=ok " in ln for ln in lines)


def test_serving_needs_enough_rows(smoke, data):
    failures = smoke.run_serving(data, ticks=8, tick_rows=ROWS)
    assert failures and "needs" in failures[0]


def test_sharded_q41_matches_serial(smoke, data, capsys):
    assert smoke.run_sharded(data, shards=4) == []
    out = capsys.readouterr().out
    assert "mode=mesh-shards=4" in out
    # placement is read from the run: Q4.1's Lookups leave device columns
    placement = next(ln for ln in out.splitlines()
                     if ln.startswith("smoke placement:"))
    assert "shard passes' device columns on [['cpu:0'], ['cpu:0']," \
        in placement and "merge mesh over ['cpu:0']" in placement


def test_compare_catches_wrong_values(smoke):
    import numpy as np
    expect = {"k": np.array([1, 2]), "v": np.array([1.0, 2.0])}
    assert smoke.compare(dict(expect), expect, 1e-3) is None
    assert "integer" in smoke.compare({"k": np.array([1, 3]),
                                       "v": expect["v"]}, expect, 1e-3)
    assert "relative error" in smoke.compare(
        {"k": expect["k"], "v": np.array([1.0, 2.1])}, expect, 1e-3)
    assert "missing" in smoke.compare({"k": expect["k"]}, expect, 1e-3)


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]])
def test_main_refuses_a_device_that_is_not_a_tpu(smoke, argv, capsys):
    assert smoke.main(argv) != 0
    captured = capsys.readouterr()
    assert '"ok"' not in captured.out
    assert "needs a TPU" in captured.err


def test_process_shard_route_refuses_on_an_accelerator(monkeypatch, data):
    import jax

    import repro
    from repro.core.shard import proc
    from repro.etl import BUILDERS

    def no_spawn(*_a, **_k):
        raise AssertionError("the process route spawned workers")

    monkeypatch.setattr(proc, "_get_pool", no_spawn)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    qf = BUILDERS["Q4.1"](data)
    with pytest.raises(RuntimeError, match="one process at a time"):
        repro.Session(backend="jax", metadata=None).run(
            qf, engine="streaming", shards=2, shard_impl="process")
