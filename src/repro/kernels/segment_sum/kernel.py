"""Pallas TPU kernel: grouped aggregation (segment sum).

The paper's BLOCK component (Fig-11 component 9, `groupby_sum`) is a
scatter-add on GPUs/CPUs.  TPUs have no atomic scatter, so we ADAPT the
operation to the MXU (DESIGN §4): each row tile builds a one-hot membership
matrix [rows_tile, n_groups] and the per-tile aggregation is the matmul

    acc[g, c] += onehot[r, g]^T @ vals[r, c]

which is systolic-friendly and runs at matmul throughput.  The grid iterates
row tiles SEQUENTIALLY (TPU grid axes are sequential by default) carrying the
[n_groups, n_cols] accumulator in a VMEM scratch buffer; only the final tile
writes the accumulator back to HBM.

VMEM working set per step:
    rows_tile * n_cols * 4   (values tile)
  + rows_tile * 4            (segment ids)
  + rows_tile * n_groups * 4 (one-hot, materialized by the MXU feed)
  + n_groups * n_cols * 4    (accumulator scratch)
With rows_tile=512, n_groups<=1024, n_cols<=8: ~2.3 MB << 16 MB VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _segment_sum_kernel(seg_ref, val_ref, out_ref, acc_ref, *,
                        n_groups: int, n_tiles: int, precision):
    """One grid step: accumulate one row tile into the VMEM accumulator.

    seg_ref: [rows_tile, 1]     int32 group ids (-1 = padding row)
    val_ref: [rows_tile, C]     float32 values
    out_ref: [n_groups, C]      output (written on the last tile only)
    acc_ref: [n_groups, C]      VMEM scratch accumulator
    """
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    seg = seg_ref[...]                                    # [R, 1]
    vals = val_ref[...]                                   # [R, C]
    # one-hot membership: [R, G]; padding rows (seg<0) match no group
    groups = jax.lax.broadcasted_iota(jnp.int32, (seg.shape[0], n_groups), 1)
    onehot = (seg == groups).astype(vals.dtype)
    # MXU: [R, G]^T @ [R, C] -> [G, C] (contract over the row dim)
    acc_ref[...] += jax.lax.dot_general(
        onehot, vals, (((0,), (0,)), ((), ())), precision=precision,
        preferred_element_type=jnp.float32)

    @pl.when(t == n_tiles - 1)
    def _flush():
        out_ref[...] = acc_ref[...]


def segment_sum_pallas(seg_ids: jax.Array, values: jax.Array, n_groups: int,
                       rows_tile: int = 512, interpret: bool = False,
                       precision=jax.lax.Precision.HIGHEST) -> jax.Array:
    """seg_ids: [N] int32 in [0, n_groups) (or -1 for padding rows);
    values: [N, C] float32.  Returns [n_groups, C] float32 sums.
    ``precision`` as in ``radix_groupby_pallas``: ``HIGHEST`` keeps the
    values float32 on the MXU."""
    N, C = values.shape
    n_tiles = max(1, -(-N // rows_tile))
    pad = n_tiles * rows_tile - N
    if pad:
        seg_ids = jnp.pad(seg_ids, ((0, pad),), constant_values=-1)
        values = jnp.pad(values, ((0, pad), (0, 0)))
    seg2d = seg_ids[:, None].astype(jnp.int32)            # TPU wants >=2D

    kernel = functools.partial(_segment_sum_kernel, n_groups=n_groups,
                               n_tiles=n_tiles, precision=precision)
    return pl.pallas_call(
        kernel,
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((rows_tile, 1), lambda t: (t, 0)),
            pl.BlockSpec((rows_tile, C), lambda t: (t, 0)),
        ],
        out_specs=pl.BlockSpec((n_groups, C), lambda t: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_groups, C), jnp.float32),
        scratch_shapes=[pltpu.VMEM((n_groups, C), jnp.float32)],
        interpret=interpret,
    )(seg2d, values)
