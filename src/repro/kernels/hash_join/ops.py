"""Public hash-probe op: the jit'd XLA probe (``hash_probe_ref``) over a
table from the host-side ``hash_build`` (build once per dimension table,
probe per chunk).  It is the same probe the fused segment kernel inlines.

There is no Pallas form: the TPU compiler refuses in-kernel gathers from a
VMEM table (``NotImplementedError: Only 2D gather is supported``, also for
a lane-dense ``(T/128, 128)`` table and for a 2D ``take_along_axis``), and a
``(T, 1)`` table of 2^19 slots would pad to 256 MiB of VMEM.  XLA lowers
the probe loop to its own gathers from HBM."""
from __future__ import annotations

import functools
from typing import Sequence, Tuple

import jax

from .ref import hash_probe_ref


@functools.partial(jax.jit, static_argnames=("max_probes",))
def hash_probe(slot_keys: Sequence[jax.Array], slot_idx: jax.Array,
               val_cols: Sequence[jax.Array], max_probes: int
               ) -> Tuple[jax.Array, jax.Array]:
    """Probe an open-addressing hash table: returns ``(idx, found)`` where
    ``idx[i]`` is the build's first-occurrence row index of ``val_cols[i]``
    (0 when not found) and ``found[i]`` marks presence."""
    return hash_probe_ref(tuple(slot_keys), slot_idx, tuple(val_cols),
                          max_probes)
