"""GPipe-over-shard_map (Algorithm 2 on the device mesh): correctness vs the
sequential stage composition, run in a subprocess with 8 host devices (the
main test process keeps the default single device)."""
import subprocess
import sys
import textwrap

import pytest

from repro.train.pipeline_parallel import plan_microbatches


def test_plan_microbatches_theorem1():
    # total net 10s over 4 stages; t0 = 0.01 -> m* = sqrt((10-2.5)/0.01)~27
    m = plan_microbatches(10.0, 4, 0.01, m_max=64)
    assert 20 <= m <= 32
    # huge overhead -> degenerate to 1
    assert plan_microbatches(1.0, 4, 10.0) == 1
    # clamped by m_max
    assert plan_microbatches(1000.0, 2, 1e-6, m_max=16) == 16


GPIPE_PROG = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.train.pipeline_parallel import gpipe_spmd, stack_stage_params

    n_stages, m, mb, d = 4, 6, 2, 16
    mesh = jax.make_mesh((n_stages,), ("stage",),
                         axis_types=(jax.sharding.AxisType.Auto,),
                         devices=jax.devices()[:n_stages])

    def stage_fn(w, h):
        return jnp.tanh(h @ w)

    key = jax.random.PRNGKey(0)
    ws = [jax.random.normal(jax.random.fold_in(key, i), (d, d)) * 0.5
          for i in range(n_stages)]
    stacked = stack_stage_params(ws)
    xs = jax.random.normal(jax.random.fold_in(key, 99), (m, mb, d))

    pipelined = gpipe_spmd(stage_fn, mesh, n_stages, m, axis="stage")
    with jax.set_mesh(mesh):
        got = jax.jit(pipelined)(stacked, xs)

    # reference: sequential stage composition per microbatch
    ref = xs
    for w in ws:
        ref = jax.vmap(lambda h: stage_fn(w, h))(ref)
    err = float(jnp.max(jnp.abs(got - ref)))
    assert err < 1e-5, err
    print("GPIPE_OK", err)
""")


def test_gpipe_matches_sequential_subprocess():
    r = subprocess.run([sys.executable, "-c", GPIPE_PROG],
                       capture_output=True, text=True, timeout=300,
                       env={**__import__("os").environ,
                            "PYTHONPATH": "src"})
    assert "GPIPE_OK" in r.stdout, r.stdout + r.stderr
