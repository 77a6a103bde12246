"""Pallas TPU kernel: radix-partitioned grouped aggregation.

Replaces the sort + segment-sum route for the paper's BLOCK component: keys
are first densified to contiguous group ids (backend-side, lexicographic
order preserved), then the id space is cut into ``n_parts`` radix
partitions of ``part_groups`` groups each (the id's high bits select the
partition).  Each partition reduces independently with the MXU one-hot
matmul (DESIGN §4 — no atomic scatter on TPU), carrying a
[part_groups, C+1] VMEM accumulator across a sequential row-tile sweep; the
trailing accumulator column tallies row counts, so sums AND counts come out
of one matmul.

Why partition at all, when ``segment_sum`` already reduces any n_groups?
The full-width accumulator and one-hot are [*, n_groups]: past a few
thousand groups they blow the VMEM budget.  The radix cut bounds both at
``part_groups`` regardless of total group count (2^20 dense cells works in
~1 MB of VMEM), trading one extra row sweep per partition — each sweep
reads the SAME row tiles, so the grid is (n_parts, n_tiles) with the tile
axis innermost and rows outside partition p one-hot to zero.

VMEM working set per step:
    rows_tile * (C+2) * 4             (values tile + ids)
  + rows_tile * part_groups * 4       (one-hot, MXU feed)
  + part_groups * (C+1) * 4           (accumulator scratch)
With rows_tile=512, part_groups=256, C<=8: ~0.8 MB << 16 MB VMEM.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _radix_groupby_kernel(ids_ref, val_ref, out_ref, acc_ref, *,
                          part_groups: int, n_tiles: int, precision):
    """One grid step: accumulate one row tile into partition p's VMEM
    accumulator.

    ids_ref: [rows_tile, 1]             int32 dense group ids (-1 = padding)
    val_ref: [rows_tile, C+1]           float32 values + ones column
    out_ref: [part_groups, C+1]         partition block (last tile only)
    acc_ref: [part_groups, C+1]         VMEM scratch accumulator
    """
    p = pl.program_id(0)
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    ids = ids_ref[...]                                    # [R, 1]
    vals = val_ref[...]                                   # [R, C+1]
    local = ids - p * part_groups                         # id within part p
    # one-hot membership [R, G_p]: rows outside partition p (and padding
    # rows, local < 0) match no local group
    groups = jax.lax.broadcasted_iota(jnp.int32,
                                      (ids.shape[0], part_groups), 1)
    onehot = ((local == groups) & (local >= 0)
              & (local < part_groups)).astype(vals.dtype)
    acc_ref[...] += jax.lax.dot_general(
        onehot, vals, (((0,), (0,)), ((), ())), precision=precision,
        preferred_element_type=jnp.float32)

    @pl.when(t == n_tiles - 1)
    def _flush():
        out_ref[...] = acc_ref[...]


def radix_groupby_pallas(ids: jax.Array, values: jax.Array, n_groups: int,
                         part_groups: int = 256, rows_tile: int = 512,
                         interpret: bool = False,
                         precision=jax.lax.Precision.HIGHEST
                         ) -> Tuple[jax.Array, jax.Array]:
    """ids: [N] int32 dense group ids in [0, n_groups) (-1 = padding);
    values: [N, C] float32 (C may be 0).  Returns
    ``(sums [n_groups, C], counts [n_groups])`` float32.

    ``precision`` of the one-hot matmul: at ``DEFAULT`` the TPU rounds the
    float32 values to bfloat16 (2^-9 relative), which a group of one row
    cannot average away, so sums may miss ``oracle_rtol``; ``HIGHEST``
    keeps float32 (``chip_smoke.py`` measures both)."""
    N, C = values.shape
    n_parts = max(1, -(-n_groups // part_groups))
    g_pad = n_parts * part_groups
    n_tiles = max(1, -(-N // rows_tile))
    pad = n_tiles * rows_tile - N
    ones = (ids >= 0).astype(jnp.float32)[:, None]
    ext = jnp.concatenate([values.astype(jnp.float32), ones], axis=1)
    if pad:
        ids = jnp.pad(ids, ((0, pad),), constant_values=-1)
        ext = jnp.pad(ext, ((0, pad), (0, 0)))
    ids2d = ids[:, None].astype(jnp.int32)

    kernel = functools.partial(_radix_groupby_kernel,
                               part_groups=part_groups, n_tiles=n_tiles,
                               precision=precision)
    out = pl.pallas_call(
        kernel,
        grid=(n_parts, n_tiles),              # tile axis innermost: each
        in_specs=[                            # partition sweeps all rows
            pl.BlockSpec((rows_tile, 1), lambda p, t: (t, 0)),
            pl.BlockSpec((rows_tile, C + 1), lambda p, t: (t, 0)),
        ],
        out_specs=pl.BlockSpec((part_groups, C + 1), lambda p, t: (p, 0)),
        out_shape=jax.ShapeDtypeStruct((g_pad, C + 1), jnp.float32),
        scratch_shapes=[pltpu.VMEM((part_groups, C + 1), jnp.float32)],
        interpret=interpret,
    )(ids2d, ext)
    return out[:n_groups, :C], out[:n_groups, C]
