"""Token trees of the plan's value and predicate expressions: what they
read, remapped, and as text. A leaf that imports nothing: `plan/ir.py`
binds expressions to these trees and `ops/expr.py` evaluates them, and
neither may import the other (`analysis/layering.py`).

A value is ``("col", position)``, ``("lit", int)``, ``("case",
predicate)`` or ``(op, value, value)`` with op one of add / sub / mul. A
predicate is ``("cmp", position, op, literal)``, ``("colcmp", position,
op, position)``, ``("miss", position, op)`` (the executor's: a literal
that is no word of the column's vocabulary), ``("not", predicate)`` or
``("and" | "or", predicate, predicate)``.
"""
from __future__ import annotations

VALUE_SYMBOL = {"add": "+", "sub": "-", "mul": "*"}
CMP_SYMBOL = {"eq": "==", "ne": "!=", "lt": "<", "gt": ">", "le": "<=",
              "ge": ">="}


def pred_columns(tokens) -> set:
    """The column positions a predicate reads."""
    kind = tokens[0]
    if kind in ("cmp", "miss"):
        return {tokens[1]}
    if kind == "colcmp":
        return {tokens[1], tokens[3]}
    return set().union(*(pred_columns(t) for t in tokens[1:]))


def pred_remap(tokens, mapping) -> tuple:
    kind = tokens[0]
    if kind in ("cmp", "miss"):
        return (kind, mapping[tokens[1]]) + tokens[2:]
    if kind == "colcmp":
        return ("colcmp", mapping[tokens[1]], tokens[2], mapping[tokens[3]])
    return (kind,) + tuple(pred_remap(t, mapping) for t in tokens[1:])


def pred_repr(tokens, names=None) -> str:
    def col(i):
        return names[i] if names else f"c{i}"

    kind = tokens[0]
    if kind == "cmp":
        return f"{col(tokens[1])} {CMP_SYMBOL[tokens[2]]} {tokens[3]!r}"
    if kind == "miss":
        return f"{col(tokens[1])} {CMP_SYMBOL[tokens[2]]} <not in it>"
    if kind == "colcmp":
        return f"{col(tokens[1])} {CMP_SYMBOL[tokens[2]]} {col(tokens[3])}"
    if kind == "not":
        return f"~{pred_repr(tokens[1], names)}"
    return (f"({pred_repr(tokens[1], names)} {kind} "
            f"{pred_repr(tokens[2], names)})")


def value_columns(tokens, values_only: bool = False) -> set:
    """The columns a value reads; ``values_only``: without those that
    only a ``case`` predicate reads."""
    if tokens[0] == "col":
        return {tokens[1]}
    if tokens[0] == "lit":
        return set()
    if tokens[0] == "case":
        return set() if values_only else pred_columns(tokens[1])
    return value_columns(tokens[1], values_only) \
        | value_columns(tokens[2], values_only)


def value_remap(tokens, mapping) -> tuple:
    if tokens[0] == "col":
        return ("col", mapping[tokens[1]])
    if tokens[0] == "lit":
        return tokens
    if tokens[0] == "case":
        return ("case", pred_remap(tokens[1], mapping))
    return (tokens[0], value_remap(tokens[1], mapping),
            value_remap(tokens[2], mapping))


def value_repr(tokens, names=None) -> str:
    if tokens[0] == "col":
        return names[tokens[1]] if names else f"c{tokens[1]}"
    if tokens[0] == "lit":
        return str(tokens[1])
    if tokens[0] == "case":
        return f"case_when({pred_repr(tokens[1], names)})"
    return (f"({value_repr(tokens[1], names)} {VALUE_SYMBOL[tokens[0]]} "
            f"{value_repr(tokens[2], names)})")
