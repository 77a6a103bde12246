"""Host-side build + the jnp slot functions for the hash join (every jax
Lookup runs them, ``core/backend/jax_dims.py``).

The build runs ONCE per dimension table on the host (numpy) and the probe
runs per chunk on the device, so the two halves must agree bit-for-bit on
the home slot of a key.  The build picks one of two slot functions from
the keys it is given:

* **direct** (``base`` = the least key): one integer key column whose span
  ``max - min + 1`` fits in 31 bits and whose table, ``next_pow2(span)``
  slots, is at most ``DIRECT_MAX_RATIO`` times the hashed one.  Key ``k``
  sits in slot ``(uint32(k) - uint32(base)) & (size - 1)``, its own, so one
  pass settles every probe row, hit or miss (``max_probes`` 1).
* **fmix32** (``base`` None): every other key set — sparse, multi-column
  or empty.  Open addressing with linear probing from a murmur3-style
  fmix32 finalizer over the keys' low 32 bits (uint32 wraparound
  arithmetic, identical in numpy and in jnp with x64 disabled, where
  64-bit keys canonicalize to 32-bit on device anyway).

Either way keys compare on their low 32 bits.  Duplicate keys keep the
FIRST occurrence (lowest row index).  Built over a ``DimTable``'s sorted
key column this makes the row a key's slot holds (``slot_idx``) equal to
``searchsorted``'s leftmost-duplicate index, the one ``DimTable.probe``
(the host reference) returns; over an arbitrary (shuffled) key order it is
simply first-occurrence-wins.

There is no Pallas form: the TPU compiler refuses in-kernel gathers from a
VMEM table (``NotImplementedError: Only 2D gather is supported``, also for
a lane-dense ``(T/128, 128)`` table and for a 2D ``take_along_axis``), and a
``(T, 1)`` table of 2^19 slots would pad to 256 MiB of VMEM.  XLA lowers
the slot functions and the Lookup's gathers to its own gathers from HBM.
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

#: murmur3 fmix32 constants — shared by the host build and the device probe
_FMIX_C1 = 0x85EB_CA6B
_FMIX_C2 = 0xC2B2_AE35
#: per-key-column mixing multiplier (odd => bijective mod 2^32)
_COL_MIX = 0x9E37_79B9
#: a key column is direct-addressed where its table is at most this many
#: times the size of the fmix32 one (SSB's dates, 2,557 days over 61,131
#: yyyymmdd values, sit exactly at the edge)
DIRECT_MAX_RATIO = 8


def _fmix32_np(h: np.ndarray) -> np.ndarray:
    h = h.astype(np.uint32)
    h ^= h >> np.uint32(16)
    h *= np.uint32(_FMIX_C1)
    h ^= h >> np.uint32(13)
    h *= np.uint32(_FMIX_C2)
    h ^= h >> np.uint32(16)
    return h


def hash_keys_np(key_cols: Sequence[np.ndarray]) -> np.ndarray:
    """uint32 combined hash of one or more integer key columns (host)."""
    h = np.zeros(len(key_cols[0]), dtype=np.uint32)
    for k in key_cols:
        h = _fmix32_np(h ^ (np.asarray(k).astype(np.uint32)
                            * np.uint32(_COL_MIX)))
    return h


def hash_keys(key_cols: Sequence[jax.Array]) -> jax.Array:
    """uint32 combined hash of one or more integer key columns (device) —
    bit-identical to :func:`hash_keys_np`."""
    h = jnp.zeros(key_cols[0].shape[0], dtype=jnp.uint32)
    for k in key_cols:
        h = h ^ (k.astype(jnp.uint32) * jnp.uint32(_COL_MIX))
        h = h ^ (h >> 16)
        h = h * jnp.uint32(_FMIX_C1)
        h = h ^ (h >> 13)
        h = h * jnp.uint32(_FMIX_C2)
        h = h ^ (h >> 16)
    return h


def _next_pow2(x: int) -> int:
    return 1 << max(4, (x - 1).bit_length())


def _direct_base(key_cols: Sequence[np.ndarray],
                 hashed_size: int) -> Optional[int]:
    """The least key where ``key_cols`` is one integer column that direct
    addressing serves (its span fits in 31 bits and its table is at most
    ``DIRECT_MAX_RATIO`` times ``hashed_size``), else None."""
    if len(key_cols) != 1:
        return None
    (k,) = key_cols
    if not len(k) or not np.issubdtype(k.dtype, np.integer):
        return None
    lo, hi = int(k.min()), int(k.max())
    span = hi - lo + 1
    if span >= 1 << 31 or _next_pow2(span) > DIRECT_MAX_RATIO * hashed_size:
        return None
    return lo


def _base_u32(base: int) -> np.uint32:
    return np.uint32(base % (1 << 32))


def hash_build(key_cols: Sequence[np.ndarray]) -> Dict[str, object]:
    """Slot table over ``d`` rows of one or more integer key columns, built
    on the host.

    Returns ``{"slot_keys": tuple_of_[T]_arrays, "slot_idx": int32 [T],
    "table_size": T, "max_probes": int, "mean_probes": float, "base": int |
    None}`` — ``slot_idx[t] < 0`` marks an empty slot, ``max_probes`` is a
    static probe-length bound, so a device probe loop with that trip count
    always settles each row at a hit or a miss, and ``mean_probes`` is the
    mean probe length a lookup of each distinct key needs (the loop runs
    ``max_probes`` passes for every row all the same).

    Direct (``base`` the least key, the rule in the module docstring): slot
    ``k - base`` holds key ``k``'s first row index, ``T = next_pow2(span)``
    and ``max_probes`` is 1.  Otherwise fmix32 linear probing (``base``
    None): insertion processes rows in index order, one probe distance per
    round, so equal keys keep the FIRST row index and colliding distinct
    keys are placed deterministically (lowest index wins a free slot);
    ``T`` is the next power of two >= 2*d (load factor <= 0.5) and
    ``max_probes`` the longest occupied run + 1."""
    key_cols = [np.asarray(k) for k in key_cols]
    d = len(key_cols[0])
    if any(len(k) != d for k in key_cols):
        raise ValueError("hash_build: key columns must share a length")
    size = _next_pow2(max(2 * max(d, 1), 16))
    base = _direct_base(key_cols, size)
    if base is not None:
        (k,) = key_cols
        off = (k.astype(np.uint32) - _base_u32(base)).astype(np.int64)
        size = _next_pow2(int(off.max()) + 1)
        _, first = np.unique(off, return_index=True)     # keep-first
        slot_idx = np.full(size, -1, dtype=np.int32)
        slot_idx[off[first]] = first
        slot_keys = np.zeros(size, dtype=k.dtype)
        slot_keys[off[first]] = k[first]
        return {"slot_keys": (slot_keys,), "slot_idx": slot_idx,
                "table_size": size, "max_probes": 1, "mean_probes": 1.0,
                "base": base}
    mask = np.uint32(size - 1)

    slot_idx = np.full(size, -1, dtype=np.int32)
    slot_keys = [np.zeros(size, dtype=k.dtype) for k in key_cols]
    # a key placed in round s is found by a probe of length s + 1
    probe_sum = n_keys = 0
    if d:
        h0 = hash_keys_np(key_cols)
        live = np.arange(d, dtype=np.int64)     # unplaced rows, index order
        step = np.uint32(0)
        while live.size:
            cand = ((h0[live] + step) & mask).astype(np.int64)
            placeable = slot_idx[cand] < 0
            placed = np.zeros(len(live), dtype=bool)
            if placeable.any():
                # lowest row index wins each contested free slot this round
                slots = cand[placeable]
                rows = live[placeable]
                _, first = np.unique(slots, return_index=True)
                slot_idx[slots[first]] = rows[first]
                for sk, k in zip(slot_keys, key_cols):
                    sk[slots[first]] = k[rows[first]]
                placed[np.flatnonzero(placeable)[first]] = True
                n_keys += len(first)
                probe_sum += (int(step) + 1) * len(first)
            # drop duplicates of an identical key in the slot, placed in an
            # earlier round or this one (keep-first)
            dup = ~placed & (slot_idx[cand] >= 0)
            for sk, k in zip(slot_keys, key_cols):
                dup &= sk[cand] == k[live]
            live = live[~(placed | dup)]
            step += np.uint32(1)

    # static probe bound: longest run of occupied slots (+1 for the empty
    # terminator), computed on the doubled table to cover wraparound
    occ2 = np.concatenate([slot_idx >= 0, slot_idx >= 0])
    max_run = 0
    run = 0
    for o in occ2:
        run = run + 1 if o else 0
        if run > max_run:
            max_run = run
    max_probes = int(min(max_run, size) + 1)
    return {"slot_keys": tuple(slot_keys), "slot_idx": slot_idx,
            "table_size": size, "max_probes": max_probes,
            "mean_probes": probe_sum / n_keys if n_keys else 0.0,
            "base": None}


def probe_lengths_np(built: Dict[str, object],
                     val_cols: Sequence[np.ndarray]) -> np.ndarray:
    """Per probe row, the passes of the device probe's loop that
    settle it: from the row's home slot (the table's own slot function) up
    to the slot holding its key (a hit) or the first empty slot (a miss),
    walked on the host over ``hash_build``'s table, and at most
    ``max_probes``, as the device loop runs.  Keys compare on their low 32
    bits, as on the device."""
    slot_idx = built["slot_idx"]
    mask = np.uint32(built["table_size"] - 1)
    slot_keys = [np.asarray(k).astype(np.uint32) for k in built["slot_keys"]]
    vals = [np.asarray(v).astype(np.uint32) for v in val_cols]
    if built["base"] is None:
        h = hash_keys_np(vals)
    else:
        h = vals[0] - _base_u32(built["base"])
    out = np.full(len(h), built["max_probes"], dtype=np.int32)
    live = np.arange(len(h))
    for step in range(built["max_probes"]):
        if not live.size:
            break
        cand = ((h[live] + np.uint32(step)) & mask).astype(np.int64)
        done = slot_idx[cand] < 0
        hit = ~done
        for sk, v in zip(slot_keys, vals):
            hit &= sk[cand] == v[live]
        done |= hit
        out[live[done]] = step + 1
        live = live[~done]
    return out


def direct_slots(vals: jax.Array, base: int,
                 size: int) -> Tuple[jax.Array, jax.Array]:
    """A direct table's slot function, traced: ``(slot int32, in_range
    bool)`` per probe row, slot ``(uint32(key) - uint32(base)) & (size -
    1)`` and ``in_range`` where ``uint32(key) - uint32(base) < size``.  A
    key in range owns its slot (the slots past the span are empty), so the
    slot's contents settle the row; a key out of range misses."""
    off = vals.astype(jnp.uint32) - jnp.uint32(base % (1 << 32))
    in_range = off < jnp.uint32(size)
    slot = (off & jnp.uint32(size - 1)).astype(jnp.int32)
    return slot, in_range


def hashed_slots(slot_keys: Sequence[jax.Array], slot_idx: jax.Array,
                 val_cols: Sequence[jax.Array],
                 max_probes: int) -> Tuple[jax.Array, jax.Array]:
    """An fmix32 table's slot function, traced: ``max_probes`` passes of
    linear probing from each row's home slot.  Returns ``(slot int32,
    found bool)``: the slot holding the row's key where found, else 0."""
    size = slot_idx.shape[0]
    n = val_cols[0].shape[0]
    h = hash_keys(list(val_cols))

    def body(step, carry):
        slot, found, done = carry
        cand = ((h + jnp.uint32(step)) & jnp.uint32(size - 1)).astype(jnp.int32)
        occ = jnp.take(slot_idx, cand, mode="clip")
        eq = jnp.ones(n, dtype=bool)
        for sk, v in zip(slot_keys, val_cols):
            eq = eq & (jnp.take(sk, cand, mode="clip") == v)
        hit = (~done) & (occ >= 0) & eq
        miss = (~done) & (occ < 0)
        slot = jnp.where(hit, cand, slot)
        return slot, found | hit, done | hit | miss

    slot = jnp.zeros(n, dtype=jnp.int32)
    found = jnp.zeros(n, dtype=bool)
    done = jnp.zeros(n, dtype=bool)
    slot, found, _ = jax.lax.fori_loop(0, max_probes, body,
                                       (slot, found, done))
    return slot, found

