"""Checkpoint/restore (atomic, async, keep-k, resharding restore), elastic
restart and straggler detection."""
import os
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.train.checkpoint import (CheckpointManager, latest_step,
                                    restore_checkpoint, save_checkpoint)
from repro.train.fault import (ElasticRunner, StragglerWatchdog,
                               with_retries)


def _state(seed=0):
    k = jax.random.PRNGKey(seed)
    return {"params": {"w": jax.random.normal(k, (8, 4)),
                       "b": jnp.zeros((4,))},
            "step": jnp.asarray(7, jnp.int32)}


def test_save_restore_roundtrip(tmp_path):
    st = _state()
    save_checkpoint(str(tmp_path), 7, st)
    got, meta = restore_checkpoint(str(tmp_path), st)
    assert meta["step"] == 7
    np.testing.assert_allclose(np.array(got["params"]["w"]),
                               np.array(st["params"]["w"]))


def test_restore_with_sharding_placement(tmp_path):
    st = _state()
    save_checkpoint(str(tmp_path), 1, st)
    # "reshard" onto the current (single-device) mesh — the elastic-restart
    # path: restore takes target shardings and device_puts accordingly
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = jax.make_mesh((1,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    sh = jax.tree.map(lambda _: NamedSharding(mesh, P()), st)
    got, _ = restore_checkpoint(str(tmp_path), st, shardings=sh)
    assert got["params"]["w"].sharding == NamedSharding(mesh, P())


def test_latest_step_and_keep_k(tmp_path):
    mgr = CheckpointManager(str(tmp_path), every_steps=1, keep=2,
                            async_save=False)
    for s in range(1, 6):
        mgr.maybe_save(s, _state())
    assert latest_step(str(tmp_path)) == 5
    dirs = sorted(os.listdir(tmp_path))
    assert dirs == ["step_00000004", "step_00000005"]


def test_async_save_snapshots_before_donation(tmp_path):
    """The manager must host-snapshot before returning: mutating the live
    state after maybe_save must not corrupt the checkpoint."""
    mgr = CheckpointManager(str(tmp_path), every_steps=1, keep=3,
                            async_save=True)
    st = {"w": jnp.ones((1000,))}
    mgr.maybe_save(1, st)
    st["w"] = st["w"] * 0          # simulate donated-buffer reuse
    mgr.wait()
    got, _ = restore_checkpoint(str(tmp_path), {"w": jnp.zeros((1000,))})
    np.testing.assert_allclose(np.array(got["w"]), np.ones(1000))


def test_atomic_save_no_tmp_left(tmp_path):
    save_checkpoint(str(tmp_path), 3, _state())
    assert not any(d.endswith(".tmp") for d in os.listdir(tmp_path))


def test_elastic_runner_restores_and_continues():
    calls = {"n": 0}

    def restore():
        return ({"restored": True}, 5)

    def loop(state, start):
        calls["n"] += 1
        if calls["n"] < 3:
            raise RuntimeError("node lost")
        return (state, start)

    runner = ElasticRunner(restore, max_restarts=5)
    state, step = runner.run(loop, {"restored": False}, 0)
    assert state["restored"] and step == 5
    assert runner.restarts == 2


def test_elastic_runner_gives_up():
    runner = ElasticRunner(lambda: ({}, 0), max_restarts=1)
    with pytest.raises(RuntimeError):
        runner.run(lambda s, t: (_ for _ in ()).throw(RuntimeError("x")),
                   {}, 0)


def test_with_retries_backoff():
    attempts = {"n": 0}

    def flaky():
        attempts["n"] += 1
        if attempts["n"] < 3:
            raise OSError("transient")
        return "ok"

    assert with_retries(flaky, max_retries=4, backoff=0.001)() == "ok"
    assert attempts["n"] == 3


def test_straggler_watchdog_detects_persistent_slowdown():
    events = []
    wd = StragglerWatchdog(window=16, threshold=2.0, patience=3,
                           on_straggler=events.append)
    for s in range(10):
        wd.observe(s, 0.1)
    for s in range(10, 14):
        wd.observe(s, 0.5)          # 5x median, persistent
    assert len(events) >= 1
    assert events[0].ratio > 2.0


def test_straggler_watchdog_ignores_one_off_spike():
    wd = StragglerWatchdog(window=16, threshold=2.0, patience=3)
    for s in range(10):
        wd.observe(s, 0.1)
    wd.observe(10, 1.0)             # single spike
    for s in range(11, 20):
        wd.observe(s, 0.1)
    assert wd.events == []


def test_train_resume_from_checkpoint(tmp_path):
    from repro.configs import get_config
    from repro.launch.train import train_loop
    cfg = get_config("stablelm-3b", smoke=True).replace(grad_accum=1)
    r1 = train_loop(cfg, steps=6, batch=4, seq_len=32,
                    ckpt_dir=str(tmp_path), ckpt_every=3, log_every=100)
    assert latest_step(str(tmp_path)) == 6
    r2 = train_loop(cfg, steps=10, batch=4, seq_len=32,
                    ckpt_dir=str(tmp_path), resume=True, log_every=100)
    assert r2["steps_done"] == 4          # resumed from step 6
