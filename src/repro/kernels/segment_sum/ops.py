"""Public segment_sum op: jit'd wrapper choosing the Pallas kernel (TPU) or
interpret=True (CPU validation) with the pure-jnp oracle as fallback."""
from __future__ import annotations

import functools

import jax

from .kernel import segment_sum_pallas
from .ref import segment_sum_ref


@functools.partial(jax.jit,
                   static_argnames=("n_groups", "impl", "rows_tile"))
def segment_sum(seg_ids: jax.Array, values: jax.Array, n_groups: int,
                impl: str = "auto", rows_tile: int = 512) -> jax.Array:
    """Grouped sum: out[g] = sum of values rows whose seg_id == g.

    impl: 'pallas' (TPU), 'interpret' (Pallas body on CPU), 'reference'
    (pure jnp), 'auto' (pallas on TPU else reference).
    """
    if impl == "auto":
        impl = ("pallas" if jax.default_backend() == "tpu" else "reference")
    with jax.named_scope("groupby.segment_sum"):
        if impl == "pallas":
            return segment_sum_pallas(seg_ids, values, n_groups,
                                      rows_tile=rows_tile)
        if impl == "interpret":
            return segment_sum_pallas(seg_ids, values, n_groups,
                                      rows_tile=rows_tile, interpret=True)
        return segment_sum_ref(seg_ids, values, n_groups)
