"""Exact integer group sums: integer columns split into 8-bit limbs, each
limb summed per group by a one-hot matmul whose every partial sum is an
exact integer, the tiles added in int32.

A limb (0..255) and a one-hot entry are exact bfloat16 operands, and a
tile of ``rows_tile`` (512) rows sums to at most 255 x 512 < 2**24, exact
in the matmul's float32 result; so one MXU pass at ``DEFAULT`` precision
gives each tile's limb sums exactly, where float32 sums at ``HIGHEST`` take
several passes and are exact only below 2**24.  The tiles add into an
int32 accumulator, which holds 255 x 2**23 rows: rows come in blocks of at
most ``BLOCK_ROWS``, each block with its own sums, and the host adds the
blocks and recombines the limbs in int64 (``core.wideint.recombine``).

The layout is limb-major (``[limbs, rows]``, rows on the lanes), so a few
limb columns cost no lane padding.  Both group-by wrappers
(``radix_groupby``, ``segment_sum``) call ``exact_sums`` inside their jitted
programs, under ``jax.named_scope("groupby.exact")``.
"""
from __future__ import annotations

import functools
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...core import wideint

#: rows whose limb sums an int32 accumulator holds: 255 * 2**23 < 2**31
BLOCK_ROWS = 1 << 23


def _exact_kernel(ids_ref, limb_ref, out_ref, acc_ref, *, part_groups: int,
                  tiles: int):
    """One grid step (block b, partition p, tile t): add one row tile's
    limb sums into partition p's int32 accumulator.

    ids_ref:  [1, rows_tile]          int32 group ids (-1 = padding)
    limb_ref: [C, rows_tile]          bfloat16 limbs (last row: ones)
    out_ref:  [part_groups, C]        the block's partition sums
    acc_ref:  [part_groups, C]        int32 VMEM accumulator
    """
    p = pl.program_id(1)
    t = pl.program_id(2)

    @pl.when(t == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    local = ids_ref[...] - p * part_groups                 # [1, R]
    groups = jax.lax.broadcasted_iota(
        jnp.int32, (part_groups, local.shape[1]), 0)
    onehot = (local == groups).astype(jnp.bfloat16)        # [G, R]
    acc_ref[...] += jax.lax.dot_general(
        onehot, limb_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32).astype(jnp.int32)

    @pl.when(t == tiles - 1)
    def _flush():
        out_ref[...] = acc_ref[...]


def exact_sums_pallas(ids: jax.Array, limbs: jax.Array, n_groups: int,
                      part_groups: int = 256, rows_tile: int = 512,
                      interpret: bool = False) -> jax.Array:
    """ids: [N] int32 group ids in [0, n_groups) (-1 = padding); limbs:
    [C, N] integers in [0, 255].  Returns ``[blocks, n_groups, C]`` int32
    sums, one block per ``BLOCK_ROWS`` rows."""
    C, N = limbs.shape
    part_groups = min(part_groups, -(-max(n_groups, 1) // 16) * 16)
    n_parts = -(-n_groups // part_groups)
    n_tiles = max(1, -(-N // rows_tile))
    tiles = min(n_tiles, BLOCK_ROWS // rows_tile)
    n_blocks = -(-n_tiles // tiles)
    pad = n_blocks * tiles * rows_tile - N
    ids = jnp.pad(ids.astype(jnp.int32), (0, pad), constant_values=-1)
    limbs = jnp.pad(limbs.astype(jnp.bfloat16), ((0, 0), (0, pad)))

    kernel = functools.partial(_exact_kernel, part_groups=part_groups,
                               tiles=tiles)
    out = pl.pallas_call(
        kernel,
        grid=(n_blocks, n_parts, tiles),       # tiles innermost: each
        in_specs=[                             # partition sweeps its block
            pl.BlockSpec((1, rows_tile), lambda b, p, t: (0, b * tiles + t)),
            pl.BlockSpec((C, rows_tile), lambda b, p, t: (0, b * tiles + t)),
        ],
        out_specs=pl.BlockSpec((None, part_groups, C),
                               lambda b, p, t: (b, p, 0)),
        out_shape=jax.ShapeDtypeStruct(
            (n_blocks, n_parts * part_groups, C), jnp.int32),
        scratch_shapes=[pltpu.VMEM((part_groups, C), jnp.int32)],
        interpret=interpret,
    )(ids[None, :], limbs)
    return out[:, :n_groups]


def exact_sums_ref(ids: jax.Array, limbs: jax.Array, n_groups: int
                   ) -> jax.Array:
    """The same sums in plain int32 ``segment_sum``, block by block."""
    C, N = limbs.shape
    n_blocks = max(1, -(-N // BLOCK_ROWS))
    block = jnp.arange(N, dtype=jnp.int32) // BLOCK_ROWS
    seg = jnp.where(ids >= 0, ids + block * n_groups, n_blocks * n_groups)
    out = jax.ops.segment_sum(limbs.T.astype(jnp.int32), seg,
                              num_segments=n_blocks * n_groups)
    return out.reshape(n_blocks, n_groups, C)


def exact_sums(ids: jax.Array, ints: Sequence[Tuple[object, wideint.Wide]],
               limbs: Sequence[int], n_groups: int, impl: str,
               part_groups: int = 256, rows_tile: int = 512
               ) -> Tuple[jax.Array, jax.Array]:
    """Per group and block, the limb sums of each ``(column, offset)`` of
    ``ints`` (``limbs[i]`` limbs of ``column - offset``; a column is an
    integer array or a ``wideint.Wide``) and the row counts:
    ``([blocks, n_groups, sum(limbs)], [blocks, n_groups])`` int32."""
    with jax.named_scope("groupby.exact"):
        rows = [l for (col, off), n in zip(ints, limbs)
                for l in wideint.limbs(col, off, n)]
        rows.append((ids >= 0).astype(jnp.int32))
        mat = jnp.stack(rows)
        if impl == "reference":
            out = exact_sums_ref(ids, mat, n_groups)
        else:
            out = exact_sums_pallas(ids, mat, n_groups,
                                    part_groups=part_groups,
                                    rows_tile=rows_tile,
                                    interpret=impl == "interpret")
    return out[..., :-1], out[..., -1]
