"""Public hash-probe op: the jit'd XLA probe (``hash_probe_ref``) over a
table from the host-side ``hash_build`` (build once per dimension table,
probe per chunk).  It is the same probe the fused segment kernel inlines.

The table's slot function is the build's choice, from the keys: direct
(slot ``key - base``, one pass) for one integer key column whose span fits
in 31 bits and whose table is at most ``DIRECT_MAX_RATIO`` times the
hashed one, else fmix32 linear probing (``base`` None, ``max_probes``
passes).  Pass the build's ``max_probes`` and ``base`` as they are.

There is no Pallas form: the TPU compiler refuses in-kernel gathers from a
VMEM table (``NotImplementedError: Only 2D gather is supported``, also for
a lane-dense ``(T/128, 128)`` table and for a 2D ``take_along_axis``), and a
``(T, 1)`` table of 2^19 slots would pad to 256 MiB of VMEM.  XLA lowers
the probe to its own gathers from HBM."""
from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax

from .ref import hash_probe_ref


@functools.partial(jax.jit, static_argnames=("max_probes", "base"))
def hash_probe(slot_keys: Sequence[jax.Array], slot_idx: jax.Array,
               val_cols: Sequence[jax.Array], max_probes: int,
               base: Optional[int]) -> Tuple[jax.Array, jax.Array]:
    """Probe a ``hash_build`` table: returns ``(idx, found)`` where
    ``idx[i]`` is the build's first-occurrence row index of ``val_cols[i]``
    (0 when not found) and ``found[i]`` marks presence.  ``base`` is the
    build's: the least key of a direct table, None for fmix32."""
    return hash_probe_ref(tuple(slot_keys), slot_idx, tuple(val_cols),
                          max_probes, base)
