"""A dimension table's form on the device, and the one way a jax Lookup
reads it.

``device_form(dim, asarray)`` builds a ``DimTable``'s form once, under one
lock, and caches it on the table (``DimTable.DEVICE_FORM``).  The build is
``kernels/hash_join.hash_build``'s over the table's sorted keys, and it
picks the slot function from the keys: ``key - base`` on a direct table,
the fmix32 probe on a hashed one.

A Lookup reads **slot-addressed** columns, on either form: slot ``s`` of a
returned column holds the payload of the table's first row with the
slot's key where that row qualifies, and the Lookup's ``default``
elsewhere.  Once a row's slot is found, one gather per returned column
answers it.  A table with no rows has only empty slots: every row it is
asked for is unmatched.  Where the Lookup asks for its match bit, the first integer
column whose span ``hi - lo + 1`` fits its device dtype carries it, as
``payload - lo + 1`` where matched and 0 elsewhere (the ``carrier``); with
none, one int32 ``matched`` array holds it.

``DimLookup`` is one Lookup's read of a form: a pytree whose leaves are its
arrays and whose static half (``plan``) is the slot function and the
carrier.  The fused segment kernel takes it as an argument and inlines its
call; the unfused Lookup jits the same call.
"""
from __future__ import annotations

import threading
from typing import Dict, NamedTuple, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from .. import wideint
from ..shared_cache import record_dim_upload
from ...kernels.hash_join import (direct_slots, hash_build, hashed_slots,
                                  probe_lengths_np)

#: guards every build and upload of a table's form: concurrent §4.3 row
#: ranges of one table must not upload twice (or count its bytes twice)
_LOCK = threading.Lock()


def device_form(dim, asarray) -> "DimForm":
    """``dim``'s device form, built on first use with ``asarray`` (the
    backend's upload) and cached on the table."""
    form = dim.__dict__.get(dim.DEVICE_FORM)
    if form is None:
        with _LOCK:
            form = dim.__dict__.get(dim.DEVICE_FORM)
            if form is None:
                form = dim.__dict__[dim.DEVICE_FORM] = DimForm(dim, asarray)
    return form


def _device_dtype(values: np.ndarray) -> np.dtype:
    return np.dtype(jax.dtypes.canonicalize_dtype(values.dtype))


class DimForm:
    """One ``DimTable`` on the device: its slot function (``base``, the
    least key of a direct table or None, ``size`` slots, ``max_probes``
    passes), the slot keys and occupancy a hashed table's probe loop reads,
    and the slot-addressed columns its Lookups have asked for."""

    def __init__(self, dim, asarray) -> None:
        self._dim = dim
        self._asarray = asarray
        keys = np.asarray(dim.keys)
        built = hash_build((keys,))
        self._built = built
        self.base: Optional[int] = built["base"]
        self.size = int(built["table_size"])
        self.max_probes = int(built["max_probes"])
        self.mean_probes = float(built["mean_probes"])
        self._key_range = ((int(keys[0]), int(keys[-1])) if len(keys)
                           else None)
        #: (kind, column, default) -> a slot-addressed device array
        self._columns: Dict[tuple, object] = {}
        #: (columns, default, flagged) -> ``read``'s answer
        self._reads: Dict[tuple, tuple] = {}
        #: (least key, passes per key over the key range, or None)
        self._lengths: Optional[tuple] = None
        self._probe = None if self.base is not None else {
            "slot_keys": tuple(self._upload(k, None)
                               for k in built["slot_keys"]),
            "slot_idx": self._upload(built["slot_idx"], None)}

    def _upload(self, host: np.ndarray, name: Optional[str]):
        record_dim_upload(host.nbytes)
        return self._asarray(host, name=name)

    def read(self, cols: Sequence[str], default, flagged: bool
             ) -> Tuple[Dict[str, object], Optional[Tuple[str, int]]]:
        """The arrays a Lookup returning ``cols`` reads, and its carrier
        (``(column, lo)``, or None): ``{"cols": {column: [size] array}}``,
        with ``"matched"`` where ``flagged`` and no column carries the
        match bit, and a hashed table's ``"slot_keys"`` and
        ``"slot_idx"``."""
        key = (tuple(cols), default, flagged)
        got = self._reads.get(key)
        if got is not None:
            return got
        carrier = None
        if flagged:
            for col in cols:
                cd = _device_dtype(self._dim.payload[col])
                r = wideint.column_range(self._dim.payload[col])
                if (r is not None and cd != np.bool_
                        and r[1] - r[0] + 1 <= np.iinfo(cd).max):
                    carrier = (col, r[0])
                    break
        arrays: Dict[str, object] = {"cols": {col: self._column(
            "offset" if carrier and col == carrier[0] else "value", col,
            default) for col in cols}}
        if flagged and carrier is None:
            arrays["matched"] = self._column("matched", None, None)
        if self._probe is not None:
            arrays.update(self._probe)
        got = self._reads[key] = (arrays, carrier)
        return got

    def _column(self, kind: str, col: Optional[str], default):
        """One slot-addressed array, built on the host and uploaded once:
        ``kind`` "value" (the payload where matched, ``default`` in the
        payload's device dtype elsewhere), "offset" (``payload - lo + 1``
        where matched, 0 elsewhere) or "matched" (int32 1 where
        matched)."""
        key = (kind, col, default if kind == "value" else None)
        got = self._columns.get(key)
        if got is None:
            with _LOCK:
                got = self._columns.get(key)
                if got is None:
                    dim = self._dim
                    slot_idx = self._built["slot_idx"]
                    row = np.maximum(slot_idx, 0)

                    def at(a):     # a's value at each slot's row
                        return (a[row] if len(a)
                                else np.zeros(len(row), a.dtype))

                    matched = (slot_idx >= 0) & at(dim.qualifies)
                    if kind == "matched":
                        host = matched.astype(np.int32)
                    else:
                        payload = dim.payload[col]
                        cd = _device_dtype(payload)
                        if kind == "offset":
                            lo = wideint.column_range(payload)[0]
                            host = np.where(
                                matched,
                                at(payload).astype(np.int64) - lo + 1,
                                0).astype(cd)
                        else:
                            fill = np.asarray(jnp.asarray(default, cd))
                            host = np.where(matched, at(payload), fill)
                    got = self._columns[key] = self._upload(host, col)
        return got

    def need(self, vals: np.ndarray) -> int:
        """The passes the probe loop needs over ``vals``, summed: each key's
        probe length looked up in a table over the dimension's key range
        (walked once, on first use, where the range is at most four times
        the slots); keys outside it are walked row by row."""
        if self._lengths is None:
            lo, hi = self._key_range or (0, -1)
            lengths = None
            if 0 < hi - lo + 1 <= 4 * self.size:
                lengths = probe_lengths_np(self._built,
                                           (np.arange(lo, hi + 1),))
            self._lengths = (lo, lengths)
        lo, lengths = self._lengths
        if lengths is None:
            return int(probe_lengths_np(self._built, (vals,)).sum())
        inside = (vals >= lo) & (vals < lo + len(lengths))
        if inside.all():
            return int(lengths[vals - lo].sum(dtype=np.int64))
        return int(lengths[vals[inside] - lo].sum(dtype=np.int64)
                   + probe_lengths_np(self._built, (vals[~inside],)).sum())


class Plan(NamedTuple):
    """A ``DimLookup``'s static half: never traced."""
    base: Optional[int]
    size: int
    max_probes: int
    carrier: Optional[Tuple[str, int]]
    returns: Tuple[Tuple[str, str], ...]
    default: object
    matched_flag: Optional[str]


@jax.tree_util.register_pytree_node_class
class DimLookup:
    """One Lookup's read of a table's form: ``arrays`` (the pytree's
    leaves) and ``plan``.  ``form`` is the table's form where the object
    was built from one, None where jax rebuilt it from its leaves."""

    def __init__(self, form: DimForm, return_cols: Dict[str, str], default,
                 matched_flag: Optional[str]) -> None:
        self.form = form
        self.arrays, carrier = form.read(
            list(dict.fromkeys(return_cols.values())), default,
            bool(matched_flag))
        self.plan = Plan(form.base, form.size, form.max_probes, carrier,
                         tuple(return_cols.items()), default, matched_flag)

    def tree_flatten(self):
        return (self.arrays,), self.plan

    @classmethod
    def tree_unflatten(cls, plan, children):
        self = object.__new__(cls)
        self.form, self.plan, (self.arrays,) = None, plan, children
        return self

    @property
    def gathers(self) -> int:
        """Gathers a row makes outside the probe loop: one per returned
        column, and one of ``matched`` where no column carries the match
        bit."""
        return len(self.plan.returns) + ("matched" in self.arrays)

    def counts(self, vals: Optional[np.ndarray] = None) -> Dict[str, object]:
        """The probe counter's fields; ``need`` where ``vals`` (the probed
        keys, on the host) are given."""
        form = self.form
        out = {"passes": form.max_probes, "direct": int(form.base is not None),
               "mean_probes": form.mean_probes, "slots": form.size,
               "gathers": self.gathers}
        if vals is not None:
            out["need"] = form.need(vals)
        return out

    def __call__(self, vals) -> Dict[str, object]:
        """The Lookup over the probe keys ``vals``, traced: each row's slot
        under ``probe``, then one gather per returned column (or of
        ``matched``) under ``gather``.  Returns ``{output column: array}``
        and, where asked for, the match flag."""
        base, size, max_probes, carrier, returns, default, flag = self.plan
        a = self.arrays
        with jax.named_scope("probe"):
            if base is not None:
                slot, found = direct_slots(vals, base, size)
            else:
                slot, found = hashed_slots(a["slot_keys"], a["slot_idx"],
                                           (vals,), max_probes)
        out: Dict[str, object] = {}
        with jax.named_scope("gather"):
            matched = None
            if "matched" in a:
                matched = found & (jnp.take(a["matched"], slot,
                                            mode="clip") != 0)
            carrier, lo = carrier or (None, 0)
            for name, dim_col in returns:
                w = jnp.take(a["cols"][dim_col], slot, mode="clip")
                fill = jnp.asarray(default, w.dtype)
                if dim_col == carrier:
                    matched = found & (w != 0)
                    out[name] = jnp.where(
                        matched, (w - 1) + jnp.asarray(lo, w.dtype), fill)
                else:
                    out[name] = jnp.where(found, w, fill)
        if flag:
            out[flag] = matched
        return out
