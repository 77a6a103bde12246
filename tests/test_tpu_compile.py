"""Compile the device path for a described TPU v5e chip, without a chip.

Each test compiles ahead of time, for one chip of a ``v5e:2x2`` topology,
a kernel that ``auto`` picks on a TPU, at the largest shapes the SF1 chip
smoke (``chip_smoke.py``) feeds it:

* one Lookup (``DimLookup``: the slot function, then one gather of a
  slot-addressed column) over the 200k-row part table at 2^21 probe rows:
  direct (T = 2^18 slots, one pass) and fmix32 (T = 2^19 slots, the table
  sparse part keys would give);
* ``radix_groupby_pallas`` at the serving batch's 2^21 rows and at Q2.1's
  7000 dense cells;
* ``segment_sum_pallas`` at Q1.1's 2^17 rows;
* the exact integer sums (``exact_sums_pallas``) at TPC-H Q1's 6M rows of
  14 limb columns over 6 cells, and at Q1.1's keyless sum;
* the fused TPC-H Q1 segment at a 2^21-row chunk bucket, with ``charge``
  computed in two words under ``wide.charge``;
* one fused Q4.1 segment kernel over SF1 dimension tables at a 2^21-row
  chunk bucket, each Lookup a gather from slot-addressed payloads under
  its scope.

A compile that passes is not a chip run: nothing here executes.  The
topology is described inside a fixture (only the worker that runs these
tests loads the TPU library), and JAX's persistent compilation cache is
off around the compiles, since a described chip cannot read entries back.
"""
from __future__ import annotations

import os

import pytest

import jax
import jax.numpy as jnp

#: usable HBM of one v5e chip (16 GiB), less headroom for the runtime
V5E_HBM_BYTES = 15 * 2 ** 30


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _fits(compiled) -> None:
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes)
    assert total < V5E_HBM_BYTES, f"{total} bytes do not fit one v5e chip"


@pytest.mark.parametrize("T,max_probes,base", [
    (1 << 18, 1, 1),           # direct: part keys 1..200,000
    (1 << 19, 32, None),       # fmix32
])
def test_probe_route_compiles_at_sf1_part_table(one_chip, T, max_probes,
                                                base):
    from repro.core.backend.jax_dims import DimLookup, Plan
    N = 1 << 21
    arrays = {"cols": {"p_brand1": _spec(one_chip, (T,), jnp.int32)}}
    if base is None:
        arrays.update(slot_keys=(_spec(one_chip, (T,), jnp.int32),),
                      slot_idx=_spec(one_chip, (T,), jnp.int32))
    look = DimLookup.tree_unflatten(
        Plan(base, T, max_probes, None, (("brand", "p_brand1"),), -1, None),
        (arrays,))
    compiled = jax.jit(lambda look, vals: look(vals)).lower(
        look, _spec(one_chip, (N,), jnp.int32)).compile()
    _fits(compiled)


@pytest.mark.parametrize("n_rows,n_cols,n_groups", [
    (1 << 21, 2, 25),        # serving batch: 8 ticks x 262144 rows
    (1 << 19, 1, 7000),      # Q2.1: 7 years x 1000 brands of dense cells
])
def test_radix_groupby_pallas_compiles(one_chip, n_rows, n_cols, n_groups):
    from repro.kernels.radix_groupby.kernel import radix_groupby_pallas
    compiled = jax.jit(
        lambda ids, v: radix_groupby_pallas(ids, v, n_groups)).lower(
        _spec(one_chip, (n_rows,), jnp.int32),
        _spec(one_chip, (n_rows, n_cols), jnp.float32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits(compiled)


def test_segment_sum_pallas_compiles(one_chip):
    from repro.kernels.segment_sum.kernel import segment_sum_pallas
    n_rows = 1 << 17                 # Q1.1's filtered rows at SF1 (~112k)
    compiled = jax.jit(lambda s, v: segment_sum_pallas(s, v, 1)).lower(
        _spec(one_chip, (n_rows,), jnp.int32),
        _spec(one_chip, (n_rows, 1), jnp.float32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits(compiled)


@pytest.mark.parametrize("n_rows,n_limbs,n_groups", [
    (6_000_000, 14, 6),      # TPC-H Q1 at SF1: 5 inputs, 4 of 6 cells
    (1 << 17, 3, 1),         # SSB Q1.1's keyless revenue
])
def test_exact_sums_pallas_compiles(one_chip, n_rows, n_limbs, n_groups):
    from repro.kernels.radix_groupby.exact import exact_sums_pallas
    compiled = jax.jit(
        lambda ids, l: exact_sums_pallas(ids, l, n_groups)).lower(
        _spec(one_chip, (n_rows,), jnp.int32),
        _spec(one_chip, (n_limbs + 1, n_rows), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits(compiled)


def test_fused_tpch_q1_segment_compiles_with_a_wide_charge(one_chip):
    import numpy as np
    from repro import col
    from repro.core.backend.jax_backend import JaxBackend
    from repro.etl.components import Expression, Filter, FusedSegment
    from repro.obs.trace import entry_scopes
    disc = col("l_extendedprice") * (100 - col("l_discount"))
    seg = FusedSegment.from_components([
        Filter("ship", col("l_shipdate") <= 19980902),
        Expression("disc", "disc_price", disc),
        Expression("charge", "charge", disc * (100 + col("l_tax")))])
    runner = JaxBackend().compile_segment(seg)
    bucket = 1 << 21
    entries, total = runner.pack_layout(bucket, [
        (c, np.dtype(np.int64)) for c in sorted(runner.inputs)])
    ranges = {"l_extendedprice": (90_000, 10_495_000), "l_discount": (0, 10),
              "l_tax": (0, 8), "l_shipdate": (19920102, 19981201)}
    wide_ops, bounds, _ = runner._wide_plan(ranges, {})
    assert bounds == {"charge": (90_000 * 90 * 100, 10_495_000 * 100 * 108)}
    compiled = runner._jit.lower(
        (bucket, tuple(entries), (wide_ops, ())),
        _spec(one_chip, (total,), jnp.uint8), {}, []).compile()
    _fits(compiled)
    program, ops = entry_scopes(compiled.as_text())
    assert "expr.charge/wide.charge" in ops.values()


@pytest.fixture(scope="module")
def fused_q41(one_chip):
    """The fused Q4.1 segment kernel over SF1 dimension tables, compiled at
    a 2^21-row chunk bucket."""
    from repro.core.backend.jax_backend import JaxBackend
    from repro.etl import BUILDERS
    from repro.etl.components import FusedSegment
    from repro.etl.ssb import generate
    data = generate(lineorder_rows=1024, customers=30_000, suppliers=2_000,
                    parts=200_000, seed=3)
    qf = BUILDERS["Q4.1"](data)
    members = ["lookup_customer", "lookup_supplier", "lookup_part",
               "lookup_date", "filter_unmatched", "project", "profit_expr"]
    seg = FusedSegment.from_components(
        [qf.flow.component(m) for m in members])
    runner = JaxBackend().compile_segment(seg)
    bucket = 1 << 21
    entries, total = runner.pack_layout(
        bucket, [(c, data.lineorder[c].dtype) for c in sorted(runner.inputs)])
    dims = jax.tree.map(lambda a: _spec(one_chip, a.shape, a.dtype),
                        runner.device_dims())
    # every SSB table is direct: the kernel reads slot-addressed payloads
    assert max(int(a.shape[0]) for d in dims
               for a in d.arrays["cols"].values()) == 1 << 18
    assert all(d.plan.base is not None for d in dims)
    return runner._jit.lower(
        (bucket, tuple(entries)), _spec(one_chip, (total,), jnp.uint8), {},
        dims).compile()


def test_fused_q41_segment_compiles_at_sf1(fused_q41):
    _fits(fused_q41)


def test_fused_q41_segment_names_one_probe_loop_per_lookup(fused_q41):
    """On the chip's compiler, each Lookup over its direct SF1 table is a
    range check and one gather from its slot-addressed payload, with no
    loop, and has top-level ops under ``lookup.<dim>/``: the ops the
    profiler's device events name."""
    from repro.obs.trace import entry_scopes
    program, ops = entry_scopes(fused_q41.as_text())
    assert program == "jit__kernel"
    assert not [op for op in ops if op.startswith("%while")]
    assert {s.split("/")[0] for s in ops.values()
            if s.startswith("lookup.")} == {
        f"lookup.{d}" for d in ("customer", "date", "part", "supplier")}
