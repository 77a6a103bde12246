"""Public radix-groupby op: jit'd wrapper choosing the Pallas kernel (TPU)
or interpret=True (CPU validation) with the pure-jnp oracle as fallback."""
from __future__ import annotations

import functools
from typing import Tuple

import jax

from .kernel import radix_groupby_pallas
from .ref import radix_groupby_ref


@functools.partial(jax.jit, static_argnames=("n_groups", "impl",
                                             "part_groups", "rows_tile"))
def radix_groupby(ids: jax.Array, values: jax.Array, n_groups: int,
                  impl: str = "auto", part_groups: int = 256,
                  rows_tile: int = 512) -> Tuple[jax.Array, jax.Array]:
    """Grouped float32 sums + counts over dense group ids: out rows are the
    dense id cells (ascending), ``counts[g]`` tallies rows with
    ``ids == g`` (-1 = padding, matches no group).

    impl: 'pallas' (TPU), 'interpret' (Pallas body on CPU), 'reference'
    (pure jnp), 'auto' (pallas on TPU else reference).
    """
    if impl == "auto":
        impl = ("pallas" if jax.default_backend() == "tpu" else "reference")
    with jax.named_scope("groupby.radix"):
        if impl == "pallas":
            return radix_groupby_pallas(ids, values, n_groups,
                                        part_groups=part_groups,
                                        rows_tile=rows_tile)
        if impl == "interpret":
            return radix_groupby_pallas(ids, values, n_groups,
                                        part_groups=part_groups,
                                        rows_tile=rows_tile, interpret=True)
        return radix_groupby_ref(ids, values, n_groups)
