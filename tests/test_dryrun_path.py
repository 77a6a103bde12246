"""The launch path end-to-end on a small host mesh in a subprocess (the
main test process keeps its single default device): cell_specs -> jit with
shardings -> lower -> compile -> roofline walk."""
import os
import subprocess
import sys
import textwrap

PROG = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.configs.base import ShapeConfig
    from repro.launch.specs import cell_specs
    from repro.launch.hlo_analysis import analyze_compiled
    from repro.train.optimizer import OptConfig
    from repro.train.train_step import make_train_step

    mesh = jax.make_mesh((2, 2), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2,
                         devices=jax.devices()[:4])
    cfg = get_config("mixtral-8x7b", smoke=True).replace(grad_accum=2)
    shape = ShapeConfig("tiny_train", seq_len=32, global_batch=8,
                        kind="train", grad_accum=2)
    specs = cell_specs(cfg, shape, mesh)
    cfg = specs["cfg"]
    step = make_train_step(cfg, OptConfig(), specs["rules"])
    with jax.set_mesh(mesh):
        fn = jax.jit(step,
                     in_shardings=(specs["param_shardings"],
                                   specs["opt_shardings"],
                                   specs["batch_shardings"]),
                     out_shardings=(specs["param_shardings"],
                                    specs["opt_shardings"], None),
                     donate_argnums=(0, 1))
        lowered = fn.lower(specs["param_shapes"], specs["opt_shapes"],
                           specs["batch_shapes"])
    compiled = lowered.compile()
    roof = analyze_compiled(compiled, 4, model_flops=1.0)
    assert roof.flops_per_device > 0
    assert roof.bytes_per_device > 0
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes > 0
    # the walker found the scan trip counts (layers x microbatches)
    print("DRYRUN_PATH_OK", roof.flops_per_device,
          roof.collective_bytes_per_device)
""")


def test_dryrun_lower_compile_analyze_subprocess():
    r = subprocess.run([sys.executable, "-c", PROG],
                       capture_output=True, text=True, timeout=600,
                       env={**os.environ, "PYTHONPATH": "src"})
    assert "DRYRUN_PATH_OK" in r.stdout, r.stdout + r.stderr
