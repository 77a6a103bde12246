"""Public radix-groupby op: jit'd wrapper choosing the Pallas kernel (TPU)
or interpret=True (CPU validation) with the pure-jnp oracle as fallback;
integer inputs go to the exact limb sums of ``exact.py`` in the same
program."""
from __future__ import annotations

import functools
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp

from .exact import exact_sums
from .kernel import radix_groupby_pallas
from .ref import radix_groupby_ref


@functools.partial(jax.jit, static_argnames=("n_groups", "impl",
                                             "part_groups", "rows_tile",
                                             "limbs"))
def radix_groupby(ids: jax.Array, values: jax.Array, n_groups: int,
                  impl: str = "auto", part_groups: int = 256,
                  rows_tile: int = 512, ints: Sequence = (),
                  limbs: Tuple[int, ...] = ()) -> Tuple[jax.Array, ...]:
    """Grouped float32 sums + counts over dense group ids: out rows are the
    dense id cells (ascending), ``counts[g]`` tallies rows with
    ``ids == g`` (-1 = padding, matches no group).

    With integer inputs (``ints``: ``(column, offset)`` pairs, ``limbs``
    the 8-bit limbs of each ``column - offset``; see ``exact.exact_sums``)
    returns ``(float sums, counts, limb sums)``, the counts and limb sums
    int32 per block of rows (``[blocks, n_groups, ...]``), exact.

    impl: 'pallas' (TPU), 'interpret' (Pallas body on CPU), 'reference'
    (pure jnp), 'auto' (pallas on TPU else reference).
    """
    if impl == "auto":
        impl = ("pallas" if jax.default_backend() == "tpu" else "reference")
    with jax.named_scope("groupby.radix"):
        if not limbs:
            return _float_route(ids, values, n_groups, impl, part_groups,
                                rows_tile)
        sums = (_float_route(ids, values, n_groups, impl, part_groups,
                             rows_tile)[0] if values.shape[1]
                else jnp.zeros((n_groups, 0), jnp.float32))
        exact, counts = exact_sums(ids, ints, limbs, n_groups, impl,
                                   part_groups, rows_tile)
        return sums, counts, exact


def _float_route(ids, values, n_groups, impl, part_groups, rows_tile):
    if impl == "pallas":
        return radix_groupby_pallas(ids, values, n_groups,
                                    part_groups=part_groups,
                                    rows_tile=rows_tile)
    if impl == "interpret":
        return radix_groupby_pallas(ids, values, n_groups,
                                    part_groups=part_groups,
                                    rows_tile=rows_tile, interpret=True)
    return radix_groupby_ref(ids, values, n_groups)
