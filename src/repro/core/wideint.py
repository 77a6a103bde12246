"""Exact integers past int32 on a device that runs without x64.

The jax backend keeps every integer as int32 on the device.  An integer
expression whose value can leave int32 (money: price x (100 - discount) x
(100 + tax) in cents) would wrap there.  This module decides, from the
observed ranges of an expression's inputs, which of its nodes can leave
int32, and computes those nodes in a *wide* form: a 64-bit two's-complement
integer held as two 32-bit words, ``lo`` (uint32) and ``hi`` (int32).

- ``bound``/``plan``: interval arithmetic over an ``Expr`` tree (``+ - *
  // %``, negation, comparisons, casts); ``plan`` marks each node whose
  interval leaves int32, in preorder, or returns None where none does, so
  such an expression compiles exactly as before;
- ``evaluate``: the expression with the marked nodes in the wide form
  (``+ - *``, negation and comparisons; any other operation on a wide value
  raises rather than wrap);
- ``WideColumn``: a wide column in the shared cache, with its interval;
  ``np.asarray`` gives its int64 values;
- ``limbs``: a wide or int32 column less an offset, as 8-bit limbs (the
  exact group-by sums them with one-hot matmuls).

Host int64 columns that do not fit int32 raise ``IntRangeError`` where
they would be narrowed (``check_fits``).
"""
from __future__ import annotations

from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

from .expr import BinOp, Cast, Col, Expr, Lit, UnOp, Where
from .faults import PoisonFault

Bound = Tuple[int, int]
INT32: Bound = (-(1 << 31), (1 << 31) - 1)
INT64: Bound = (-(1 << 63), (1 << 63) - 1)
#: bits of one limb of the exact group-by: a limb (0..255) is exact as a
#: bfloat16 matmul operand
LIMB_BITS = 8
_COMPARE = ("eq", "ne", "lt", "le", "gt", "ge")


class IntRangeError(PoisonFault):
    """An integer column's values do not fit the device's 32-bit integers:
    the data itself cannot run on this path, so it is never retried."""


def fits(b: Optional[Bound], within: Bound = INT32) -> bool:
    return b is None or (within[0] <= b[0] and b[1] <= within[1])


def bits(b: Bound) -> int:
    """Two's-complement width of the interval, sign included."""
    return max(int(b[0]).bit_length(), int(b[1]).bit_length()) + 1


def check_fits(name: str, dtype, lo: int, hi: int) -> None:
    """Raise ``IntRangeError`` where a column's observed range [lo, hi]
    does not fit the device integer ``dtype``."""
    info = np.iinfo(dtype)
    if lo < info.min or hi > info.max:
        raise IntRangeError(
            f"column {name!r} holds integers in [{lo}, {hi}], outside the "
            f"device's {np.dtype(dtype).name} [{info.min}, {info.max}]")


def column_range(values: np.ndarray) -> Optional[Bound]:
    """``(min, max)`` of an integer or boolean host column, None for any
    other dtype or an empty column."""
    v = np.asarray(values)
    if v.size == 0 or not (np.issubdtype(v.dtype, np.integer)
                           or v.dtype == np.bool_):
        return None
    return int(v.min()), int(v.max())


# ---------------------------------------------------------------------------
#  Interval arithmetic
# ---------------------------------------------------------------------------
def _children(node: Expr) -> List[Expr]:
    if isinstance(node, BinOp):
        return [node.left, node.right]
    if isinstance(node, (UnOp, Cast)):
        return [node.operand]
    if isinstance(node, Where):
        return [node.cond, node.if_true, node.if_false]
    return []


def _corners(a: Bound, b: Bound, fn) -> Bound:
    vals = [fn(x, y) for x in a for y in b]
    return min(vals), max(vals)


def _node_bound(node: Expr, kids: List[Optional[Bound]],
                ranges: Dict[str, Bound]) -> Optional[Bound]:
    """The interval of ``node`` from its children's, or None where the node
    is not an integer (a float column or literal, ``/``, a float cast)."""
    if isinstance(node, Col):
        return ranges.get(node.name)
    if isinstance(node, Lit):
        v = node.value
        return (int(v), int(v)) if isinstance(v, (int, np.integer,
                                                  bool, np.bool_)) else None
    if any(k is None for k in kids):
        return None
    if isinstance(node, BinOp):
        a, b = kids
        op = node.op
        if op == "add":
            return a[0] + b[0], a[1] + b[1]
        if op == "sub":
            return a[0] - b[1], a[1] - b[0]
        if op == "mul":
            return _corners(a, b, lambda x, y: x * y)
        if op == "floordiv":
            if b[0] > 0 or b[1] < 0:
                return _corners(a, b, lambda x, y: x // y)
            m = max(abs(a[0]), abs(a[1]))        # |a // b| <= |a| for b != 0
            return -m, m
        if op == "mod":
            m = max(abs(b[0]), abs(b[1]))
            return -m, m
        if op in _COMPARE:
            return 0, 1
        if op in ("and", "or", "xor"):
            if fits(a, (0, 1)) and fits(b, (0, 1)):
                return 0, 1
            return INT32 if fits(a) and fits(b) else None
        return None                               # truediv: a float
    if isinstance(node, UnOp):
        (a,) = kids
        if node.op == "neg":
            return -a[1], -a[0]
        if node.op == "invert":
            return -a[1] - 1, -a[0] - 1
        lo = 0 if a[0] <= 0 <= a[1] else min(abs(a[0]), abs(a[1]))
        return lo, max(abs(a[0]), abs(a[1]))      # abs
    if isinstance(node, Cast):
        dt = node.dtype
        if dt == np.bool_:
            return 0, 1
        if not np.issubdtype(dt, np.integer):
            return None
        info = np.iinfo(dt)
        return kids[0] if fits(kids[0], (int(info.min), int(info.max))) \
            else (int(info.min), int(info.max))   # a narrowing cast wraps
    if isinstance(node, Where):
        _, t, f = kids
        return min(t[0], f[0]), max(t[1], f[1])
    return None


def _preorder(expr: Expr) -> List[Tuple[Expr, int]]:
    """Every node of ``expr`` with the size of its subtree, in preorder."""
    out: List[Tuple[Expr, int]] = []

    def visit(node: Expr) -> int:
        i = len(out)
        out.append((node, 0))
        size = 1 + sum(visit(c) for c in _children(node))
        out[i] = (node, size)
        return size
    visit(expr)
    return out


class Plan(NamedTuple):
    """``bound``: the expression's interval (None where it is not an
    integer); ``flags``: in preorder, each node computed in the wide form
    (its interval leaves int32, or it reads a wide column), None where no
    node is and the expression compiles as before; ``bits``: the widest
    wide node's two's-complement width (0 where none)."""
    bound: Optional[Bound]
    flags: Optional[Tuple[bool, ...]]
    bits: int


def plan(expr: Expr, ranges: Dict[str, Bound],
         wide_inputs: Sequence[str] = ()) -> Plan:
    """The wide form's plan for ``expr`` over its inputs' ``ranges``
    (``Plan``); raises ``IntRangeError`` where a node can leave int64."""
    nodes = _preorder(expr)
    flags = [False] * len(nodes)
    bounds: List[Optional[Bound]] = [None] * len(nodes)
    for i in range(len(nodes) - 1, -1, -1):      # children before parents
        node, size = nodes[i]
        kids, j = [], i + 1
        while j < i + size:
            kids.append(bounds[j])
            j += nodes[j][1]
        bounds[i] = b = _node_bound(node, kids, ranges)
        flags[i] = ((isinstance(node, Col) and node.name in wide_inputs)
                    or not fits(b))
        if not fits(b, INT64):
            raise IntRangeError(
                f"{expr!r}: {node!r} spans [{b[0]}, {b[1]}], past int64")
    wide = [bits(b) for b, f in zip(bounds, flags) if f and b is not None]
    return Plan(bounds[0], tuple(flags) if any(flags) else None,
                max(wide, default=0))


# ---------------------------------------------------------------------------
#  The wide form: 64-bit two's complement in two 32-bit words
# ---------------------------------------------------------------------------
class Wide(NamedTuple):
    """A traced wide integer: ``hi * 2**32 + lo`` (lo uint32, hi int32)."""
    lo: object
    hi: object


def _jnp():
    import jax.numpy as jnp
    return jnp


def const(v: int) -> Wide:
    """A Python integer as a wide constant (scalars, broadcast on use)."""
    jnp = _jnp()
    v = int(v)
    return Wide(jnp.uint32(v & 0xFFFFFFFF), jnp.int32(v >> 32))


def widen(x) -> Wide:
    """An int32 (or narrower, or boolean) array, or a Python integer, in
    the wide form."""
    if isinstance(x, Wide):
        return x
    if isinstance(x, (int, np.integer, bool, np.bool_)):
        return const(int(x))
    jnp = _jnp()
    x = x.astype(jnp.int32)
    return Wide(x.astype(jnp.uint32), x >> 31)


def narrow(x: Wide):
    """The low word as int32: exact where the value fits int32."""
    return x.lo.astype(_jnp().int32)


def add(a: Wide, b: Wide) -> Wide:
    jnp = _jnp()
    lo = a.lo + b.lo
    return Wide(lo, a.hi + b.hi + (lo < a.lo).astype(jnp.int32))


def neg(a: Wide) -> Wide:
    jnp = _jnp()
    lo = ~a.lo + jnp.uint32(1)
    return Wide(lo, ~a.hi + (lo == 0).astype(jnp.int32))


def sub(a: Wide, b: Wide) -> Wide:
    return add(a, neg(b))


def _mul32(a, b):
    """Full 64-bit product of two uint32 words: ``(lo, hi)`` words."""
    jnp = _jnp()
    m16 = jnp.uint32(0xFFFF)
    a0, a1, b0, b1 = a & m16, a >> 16, b & m16, b >> 16
    p00, p01, p10, p11 = a0 * b0, a0 * b1, a1 * b0, a1 * b1
    mid = (p00 >> 16) + (p01 & m16) + (p10 & m16)
    return ((p00 & m16) | (mid << 16),
            p11 + (p01 >> 16) + (p10 >> 16) + (mid >> 16))


def mul(a: Wide, b: Wide) -> Wide:
    """``a * b`` modulo 2**64, exact where the product fits int64."""
    jnp = _jnp()
    u32 = jnp.uint32
    lo, hi = _mul32(a.lo, b.lo)
    hi = hi + a.lo * b.hi.astype(u32) + a.hi.astype(u32) * b.lo
    return Wide(lo, hi.astype(jnp.int32))


def compare(op: str, a: Wide, b: Wide):
    eq = (a.hi == b.hi) & (a.lo == b.lo)
    lt = (a.hi < b.hi) | ((a.hi == b.hi) & (a.lo < b.lo))
    return {"eq": lambda: eq, "ne": lambda: ~eq, "lt": lambda: lt,
            "le": lambda: lt | eq, "gt": lambda: ~(lt | eq),
            "ge": lambda: ~lt}[op]()


_WIDE_OPS = {"add": add, "sub": sub, "mul": mul}


def evaluate(expr: Expr, view, rows, flags: Tuple[bool, ...]):
    """``expr`` over ``view`` with the nodes ``flags`` marks (``plan``) in
    the wide form; a subtree with no marked node evaluates as before.  The
    result is a ``Wide`` where the root is marked, else an array.  A wide
    column of ``view`` is a ``Wide`` over all rows (``rows`` is the full
    range there)."""
    nodes = _preorder(expr)

    def ev(i: int):
        node, size = nodes[i]
        if not any(flags[i:i + size]):
            return node.evaluate(view, rows)
        if isinstance(node, Col):
            return view.col(node.name)
        if isinstance(node, Lit):
            return const(node.value)
        kids, j = [], i + 1
        while j < i + size:
            kids.append(ev(j))
            j += nodes[j][1]
        out = _wide_node(node, kids)
        return out if flags[i] or not isinstance(out, Wide) else narrow(out)
    return ev(0)


def _wide_node(node: Expr, kids: list):
    if isinstance(node, BinOp) and node.op in _WIDE_OPS:
        return _WIDE_OPS[node.op](widen(kids[0]), widen(kids[1]))
    if isinstance(node, BinOp) and node.op in _COMPARE:
        return compare(node.op, widen(kids[0]), widen(kids[1]))
    if isinstance(node, UnOp) and node.op == "neg":
        return neg(widen(kids[0]))
    if (isinstance(node, Cast) and np.issubdtype(node.dtype, np.integer)
            and node.dtype.itemsize == 8):
        return widen(kids[0])
    raise NotImplementedError(
        f"{node!r} needs integers past int32; the device's wide integers "
        f"support + - *, negation, comparisons and int64 casts")


# ---------------------------------------------------------------------------
#  Wide columns in the shared cache
# ---------------------------------------------------------------------------
class WideColumn:
    """A device column of 64-bit integers in two words (``lo`` uint32,
    ``hi`` int32), with the interval its values lie in.  It slices, masks
    and gathers like a device array; ``np.asarray`` gives int64 on the
    host.  A 32-bit device operation refuses it (``__jax_array__``)."""

    __slots__ = ("lo", "hi", "bound")
    dtype = np.dtype(np.int64)
    ndim = 1

    def __init__(self, lo, hi, bound: Bound):
        self.lo, self.hi, self.bound = lo, hi, (int(bound[0]), int(bound[1]))

    @property
    def wide(self) -> Wide:
        return Wide(self.lo, self.hi)

    def __len__(self) -> int:
        return int(self.lo.shape[0])

    @property
    def shape(self) -> Tuple[int]:
        return (len(self),)

    @property
    def size(self) -> int:
        return len(self)

    @property
    def nbytes(self) -> int:
        return 8 * len(self)

    def __getitem__(self, idx) -> "WideColumn":
        return WideColumn(self.lo[idx], self.hi[idx], self.bound)

    def __array__(self, dtype=None, copy=None):
        out = (np.asarray(self.hi).astype(np.int64) << 32) | \
            np.asarray(self.lo).astype(np.int64)
        return out if dtype is None else out.astype(dtype)

    def __jax_array__(self):
        raise IntRangeError(
            f"a wide integer column in [{self.bound[0]}, {self.bound[1]}] "
            f"cannot enter a 32-bit device operation")

    @staticmethod
    def concat(parts: Sequence) -> "WideColumn":
        jnp = _jnp()
        parts = [p if isinstance(p, WideColumn) else from_host(p)
                 for p in parts]
        return WideColumn(jnp.concatenate([p.lo for p in parts]),
                          jnp.concatenate([p.hi for p in parts]),
                          (min(p.bound[0] for p in parts),
                           max(p.bound[1] for p in parts)))

    def __repr__(self) -> str:
        return f"WideColumn(n={len(self)}, bound={self.bound})"


def from_host(values) -> WideColumn:
    """An integer host or device column as a ``WideColumn``."""
    jnp = _jnp()
    v = np.asarray(values, dtype=np.int64)
    b = column_range(v) or (0, 0)
    return WideColumn(jnp.asarray((v & 0xFFFFFFFF).astype(np.uint32)),
                      jnp.asarray((v >> 32).astype(np.int32)), b)


# ---------------------------------------------------------------------------
#  Limbs for the exact group-by
# ---------------------------------------------------------------------------
def limb_count(b: Bound) -> int:
    """8-bit limbs that hold every value of ``b`` less its minimum (at
    least one)."""
    return max(1, -(-(int(b[1]) - int(b[0])).bit_length() // LIMB_BITS))


def limbs(x, offset: Wide, n: int) -> list:
    """``x - offset`` (``x`` an integer array or a ``Wide``; the difference
    lies in [0, 2**(8 * n))) as ``n`` int32 arrays of 8-bit limbs, least
    significant first."""
    jnp = _jnp()
    d = sub(widen(x), offset)
    words = (d.lo, d.hi.astype(jnp.uint32))
    return [((words[k // 4] >> (LIMB_BITS * (k % 4))) & jnp.uint32(0xFF))
            .astype(jnp.int32) for k in range(n)]


#: float64 holds every integer of smaller magnitude exactly
FLOAT64_EXACT = 1 << 53


def exact_result(total: np.ndarray) -> np.ndarray:
    """An exact integer sum as the backends return it: float64 (the dtype
    sums have always had) where every value lies below 2**53, which float64
    holds exactly, else int64; never rounded."""
    total = np.asarray(total, dtype=np.int64)
    if total.size and np.abs(total).max() >= FLOAT64_EXACT:
        return total
    return total.astype(np.float64)


def recombine(sums: np.ndarray, counts: np.ndarray,
              offsets: Sequence[int], limb_counts: Sequence[int]
              ) -> List[np.ndarray]:
    """Per input, the exact int64 sums from per-group limb sums (``sums``
    ``[..., groups, sum(limb_counts)]``, summed over leading axes) and
    per-group counts: ``sum_k S_k * 2**(8k) + count * offset``."""
    s = np.asarray(sums, dtype=np.int64)
    s = s.reshape(-1, *s.shape[-2:]).sum(axis=0)
    c = np.asarray(counts, dtype=np.int64)
    c = c.reshape(-1, c.shape[-1]).sum(axis=0)
    out, col = [], 0
    for off, n in zip(offsets, limb_counts):
        total = c * np.int64(off)
        for k in range(n):
            total = total + (s[:, col + k] << (LIMB_BITS * k))
        out.append(total)
        col += n
    return out
