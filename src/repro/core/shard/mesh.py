"""Mesh route: Aggregate second-stage merges through jax ``shard_map``.

``make_combiner`` builds the device-mesh reducer the merge pass hands to
``Aggregate.shard_merge``: concatenated per-shard partial rows are
aligned to dense ``[groups]`` vectors (group ids from ``np.unique`` over
the key tuple — lexicographic, matching the backend's group order),
scattered over the ``(data,)`` axis of a mesh of every device
(``jax.make_mesh``), locally segment-reduced on each device, and combined
with ``psum``/``pmin``/``pmax``.

Exactness contract: outputs are cast back to the stage-1 partial dtypes,
and when jax runs without x64 the combiner refuses (returns ``None`` —
the caller falls back to the host ``reduce_partials``, and the refusal is
recorded as a ``shard_impl`` Degradation) any input whose values would not
round-trip through the 32-bit canonical dtypes.  Rows
padded to a multiple of the device count carry the op identity and land
in group 0, so they never perturb a real group.
"""
from __future__ import annotations

from typing import Callable, Dict, Optional, Sequence, Tuple

import numpy as np

from .. import faults


def _canon_dtype(dtype: np.dtype, x64: bool) -> np.dtype:
    """The dtype jax will actually compute in."""
    if x64 or dtype.itemsize <= 4 or dtype.kind not in "iuf":
        return dtype
    return np.dtype({"i": np.int32, "u": np.uint32, "f": np.float32}[dtype.kind])


def _round_trips(v: np.ndarray, cd: np.dtype) -> bool:
    if cd == v.dtype or v.size == 0:
        return True
    return bool(np.array_equal(v.astype(cd).astype(v.dtype), v))


def _identity(op: str, dtype: np.dtype):
    if op == "sum":
        return dtype.type(0)
    if dtype.kind in "iu":
        info = np.iinfo(dtype)
        return info.max if op == "min" else info.min
    return dtype.type(np.inf if op == "min" else -np.inf)


def make_combiner() -> Callable:
    """A ``combine(cat, group_names, ops)`` closure with the same contract
    as ``merge.reduce_partials`` — except it may return ``None`` per call
    (no rows, or dtypes that would not round-trip), in which case the caller
    uses the host reduce.  The mesh spans every device of the default
    backend and is kept as ``combine.mesh``; a failure to build it
    propagates."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    devices = jax.devices()
    D = len(devices)
    mesh = jax.make_mesh((D,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,),
                         devices=devices)
    x64 = bool(jax.config.jax_enable_x64)

    def _mesh_reduce(v: np.ndarray, inv: np.ndarray, n_groups: int,
                     op: str) -> np.ndarray:
        ident = _identity(op, v.dtype)

        def local(vv, ii):
            if op == "sum":
                acc = jnp.zeros((n_groups,), dtype=vv.dtype).at[ii].add(vv)
                return jax.lax.psum(acc, "data")
            full = jnp.full((n_groups,), ident, dtype=vv.dtype)
            if op == "min":
                return jax.lax.pmin(full.at[ii].min(vv), "data")
            return jax.lax.pmax(full.at[ii].max(vv), "data")

        f = jax.shard_map(local, mesh=mesh, in_specs=(P("data"), P("data")),
                          out_specs=P(), check_vma=False)
        return np.asarray(f(jnp.asarray(v), jnp.asarray(inv)))

    def combine(cat: Dict[str, np.ndarray], group_names: Sequence[str],
                ops: Dict[str, str]
                ) -> Optional[Tuple[list, Dict[str, np.ndarray]]]:
        keys = [np.asarray(cat[g]) for g in group_names]
        vals = {p: np.asarray(cat[p]) for p in ops}
        n = len(next(iter(vals.values()))) if vals else 0
        if n == 0:
            return None
        for name, arr in (*zip(group_names, keys), *vals.items()):
            if (arr.dtype.kind not in "iufb"
                    or not _round_trips(arr, _canon_dtype(arr.dtype, x64))):
                faults.record_degradation(
                    "shard_impl", src="mesh", dst="host-merge",
                    component=name,
                    error=f"{arr.dtype} partial does not round-trip through "
                          f"the device dtype")
                return None
        if keys:
            uniq, inv = np.unique(np.stack(keys, axis=1), axis=0,
                                  return_inverse=True)
            n_groups = len(uniq)
            group_cols = [uniq[:, j].astype(k.dtype, copy=False)
                          for j, k in enumerate(keys)]
        else:
            inv, n_groups, group_cols = np.zeros(n, np.int64), 1, []
        pad = (-n) % D
        inv_p = np.concatenate(
            [inv.reshape(-1), np.zeros(pad, inv.dtype)]).astype(np.int32)
        part_cols: Dict[str, np.ndarray] = {}
        for p, op in ops.items():
            v = vals[p]
            cd = _canon_dtype(v.dtype, x64)
            v_p = np.concatenate(
                [v.astype(cd, copy=False),
                 np.full(pad, _identity(op, cd), dtype=cd)])
            out = _mesh_reduce(v_p, inv_p, n_groups, op)
            part_cols[p] = out.astype(v.dtype, copy=False)
        return group_cols, part_cols

    combine.mesh = mesh
    return combine
