"""Column expressions of the flow files, parsed once into nested tuples.

The grammar is a small subset of Python's: column names, integer
literals, ``+ - * //``, ``& |`` on masks, one comparison (``== != < <= >
>=``) per pair of parentheses, and ``between(x, lo, hi)``.  Two walkers
read the tuples: ``bench.flows`` builds the program's expression objects,
``bench.reference`` computes numpy arrays.
"""
from __future__ import annotations

import ast
from typing import Callable, FrozenSet

OPS = {ast.Add: "+", ast.Sub: "-", ast.Mult: "*", ast.FloorDiv: "//",
       ast.BitAnd: "&", ast.BitOr: "|", ast.Eq: "==", ast.NotEq: "!=",
       ast.Lt: "<", ast.LtE: "<=", ast.Gt: ">", ast.GtE: ">="}


def parse(text: str) -> tuple:
    """``text`` as a tuple tree: ``("col", name)``, ``("lit", value)``,
    ``(op, left, right)`` or ``("between", x, lo, hi)``."""
    return _node(ast.parse(text, mode="eval").body, text)


def _node(n: ast.AST, text: str) -> tuple:
    if isinstance(n, ast.Name):
        return ("col", n.id)
    if isinstance(n, ast.Constant) and type(n.value) is int:
        return ("lit", n.value)
    if isinstance(n, ast.BinOp) and type(n.op) in OPS:
        return (OPS[type(n.op)], _node(n.left, text), _node(n.right, text))
    if (isinstance(n, ast.Compare) and len(n.ops) == 1
            and type(n.ops[0]) in OPS):
        return (OPS[type(n.ops[0])], _node(n.left, text),
                _node(n.comparators[0], text))
    if (isinstance(n, ast.Call) and isinstance(n.func, ast.Name)
            and n.func.id == "between" and len(n.args) == 3):
        return ("between",) + tuple(_node(a, text) for a in n.args)
    raise ValueError(f"unsupported expression {ast.dump(n)} in {text!r}")


def columns(tree: tuple) -> FrozenSet[str]:
    """Every column name the expression reads."""
    if tree[0] == "col":
        return frozenset([tree[1]])
    if tree[0] == "lit":
        return frozenset()
    return frozenset().union(*(columns(t) for t in tree[1:]))


def walk(tree: tuple, column: Callable, literal: Callable):
    """Evaluate ``tree`` with ``column(name)`` and ``literal(value)`` as its
    leaves and Python's operators between them (numpy arrays and the
    program's expression objects both overload them)."""
    op = tree[0]
    if op == "col":
        return column(tree[1])
    if op == "lit":
        return literal(tree[1])
    if op == "between":
        x, lo, hi = (walk(t, column, literal) for t in tree[1:])
        return (x >= lo) & (x <= hi)
    a, b = walk(tree[1], column, literal), walk(tree[2], column, literal)
    return {"+": lambda: a + b, "-": lambda: a - b, "*": lambda: a * b,
            "//": lambda: a // b, "&": lambda: a & b, "|": lambda: a | b,
            "==": lambda: a == b, "!=": lambda: a != b, "<": lambda: a < b,
            "<=": lambda: a <= b, ">": lambda: a > b,
            ">=": lambda: a >= b}[op]()
