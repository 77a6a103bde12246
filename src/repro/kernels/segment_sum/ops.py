"""Public segment_sum op: jit'd wrapper choosing the Pallas kernel (TPU) or
interpret=True (CPU validation) with the pure-jnp oracle as fallback;
integer inputs go to the exact limb sums of ``radix_groupby/exact.py`` in
the same program."""
from __future__ import annotations

import functools
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp

from ..radix_groupby.exact import exact_sums
from .kernel import segment_sum_pallas
from .ref import segment_sum_ref


@functools.partial(jax.jit,
                   static_argnames=("n_groups", "impl", "rows_tile",
                                    "limbs"))
def segment_sum(seg_ids: jax.Array, values: jax.Array, n_groups: int,
                impl: str = "auto", rows_tile: int = 512,
                ints: Sequence = (), limbs: Tuple[int, ...] = ()):
    """Grouped sum: out[g] = sum of values rows whose seg_id == g.

    With integer inputs (``ints``, ``limbs`` as in ``radix_groupby``)
    returns ``(float sums, counts, limb sums)``, the counts and limb sums
    int32 per block of rows, exact.

    impl: 'pallas' (TPU), 'interpret' (Pallas body on CPU), 'reference'
    (pure jnp), 'auto' (pallas on TPU else reference).
    """
    if impl == "auto":
        impl = ("pallas" if jax.default_backend() == "tpu" else "reference")
    with jax.named_scope("groupby.segment_sum"):
        if not limbs:
            return _float_route(seg_ids, values, n_groups, impl, rows_tile)
        sums = (_float_route(seg_ids, values, n_groups, impl, rows_tile)
                if values.shape[1]
                else jnp.zeros((n_groups, 0), jnp.float32))
        exact, counts = exact_sums(seg_ids, ints, limbs, n_groups, impl,
                                   rows_tile=rows_tile)
        return sums, counts, exact


def _float_route(seg_ids, values, n_groups, impl, rows_tile):
    if impl == "pallas":
        return segment_sum_pallas(seg_ids, values, n_groups,
                                  rows_tile=rows_tile)
    if impl == "interpret":
        return segment_sum_pallas(seg_ids, values, n_groups,
                                  rows_tile=rows_tile, interpret=True)
    return segment_sum_ref(seg_ids, values, n_groups)
