"""Integer value expressions over columns: ``add`` / ``sub`` / ``mul`` of
columns, other expressions and integer literals, evaluated exactly or not
at all; and ``case``, a predicate as the integer 1 or 0.

An expression arrives as a token tree (``plan/ir.py`` builds it, and the
plan's fingerprint reads the same tokens): ``("col", i)``, ``("lit", v)``,
``(op, a, b)`` with op one of ``add``, ``sub``, ``mul``, and ``("case",
predicate)``. Its column has a declared dtype, int32 or int64, by the
operands' (`result_dtype`).

A PREDICATE is a token tree too (`predicate`): ``("cmp", i, op,
literal)``, ``("colcmp", i, op, j)``, ``("and" | "or", x, y)``, ``("not",
x)``; a compare is false where a column it reads is null, as the plan's
filters have it (`data/table.Table._compare`). ``("case", p)`` is 1 where
``p`` is true and 0 where it is false or null: its range is {0, 1} without
a look at the table, it is an int32 lane in every form, and it is never
null, so the columns only a predicate reads are neither probed nor part of
the computed column's validity (`columns_of(..., values_only=True)`). The
caller hands a predicate over with its string literals resolved to the
column's dictionary codes (`data/table._resolve_predicate`: ``("miss", i,
op)`` for a literal the vocabulary lacks).

EXACT OR NOT AT ALL. Before a computed column is handed on the host
walks the tree with the OBSERVED range of every column it reads
(`plan_forms`, plain interval arithmetic on Python integers): a step
whose range leaves the int64, or a result whose range leaves its declared
dtype, raises and names the column and the step. No value ever wraps.

The same ranges choose each step's FORM where x64 is off (as PR 35's sort
packing chooses its operands): a step whose range fits an int32 is ONE
int32 lane whatever its declared width (of a plane-held input only the
low plane is read), and only a step that needs more is a pair of words
(`wideint`). The forms are the compiled program's static part, so two
tables whose ranges give the same forms share one program; the caller
(`data/table._with_columns`) dispatches that program with the forms its
last table of the shape proved while this table's ranges are on their
way, and again where they prove others. With x64 on every step is a
native int64.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..exprtokens import pred_columns as predicate_columns
from ..exprtokens import value_columns as columns_of  # noqa: F401
from ..exprtokens import value_repr as render
from ..status import Code, CylonError
from . import wideint as W

# integer column dtypes an expression may read, and those that make its
# column an int64
_NARROW = ("int8", "int16", "int32", "uint8", "uint16")
_WIDE = ("int64", "uint32")


def result_dtype(tokens, types) -> str:
    """"int32" or "int64": int64 as soon as an operand column is an int64
    (or a uint32) or a literal lies outside the int32."""
    kind = tokens[0]
    if kind == "col":
        t = str(types[tokens[1]])
        if t in _NARROW:
            return "int32"
        if t in _WIDE:
            return "int64"
        raise CylonError(
            Code.TypeError,
            f"compute: column {tokens[1]} is {t}: expressions take integer "
            f"columns (decimals are scaled integers; no floats, no strings)")
    if kind == "lit":
        return "int32" if W.fits(tokens[1], tokens[1], W.INT32_RANGE) \
            else "int64"
    if kind == "case":
        return "int32"
    a, b = (result_dtype(t, types) for t in tokens[1:])
    return "int64" if "int64" in (a, b) else "int32"


COMPARE = {
    "eq": lambda a, b: a == b, "ne": lambda a, b: a != b,
    "lt": lambda a, b: a < b, "gt": lambda a, b: a > b,
    "le": lambda a, b: a <= b, "ge": lambda a, b: a >= b,
}


def predicate(tokens, leaves, valids):
    """The bool lane of a predicate over ``leaves`` (column position ->
    array) and ``valids`` (position -> validity mask, nullable columns
    only): what the plan's filter masks give for the same expression."""
    kind = tokens[0]
    if kind in ("cmp", "miss", "colcmp"):
        x = leaves[tokens[1]]
        if kind == "cmp":
            res = COMPARE[tokens[2]](x, tokens[3])
        elif kind == "miss":    # the literal is no word of the vocabulary
            res = jnp.full(x.shape, tokens[2] == "ne")
        else:
            res = COMPARE[tokens[2]](x, leaves[tokens[3]])
        for p in sorted(predicate_columns(tokens)):
            if p in valids:
                res = res & valids[p]
        return res
    if kind == "not":
        return ~predicate(tokens[1], leaves, valids)
    a, b = (predicate(t, leaves, valids) for t in tokens[1:])
    return a & b if kind == "and" else a | b


def _interval(op, a, b):
    if op == "add":
        return a[0] + b[0], a[1] + b[1]
    if op == "sub":
        return a[0] - b[1], a[1] - b[0]
    products = [x * y for x in a for y in b]
    return min(products), max(products)


def plan_forms(tokens, ranges, dtype: str, name: str, names=None):
    """(forms, (lo, hi)): the range of every step of ``tokens`` from the
    observed ``ranges`` (column position -> (lo, hi)), checked, and with
    it each step's form, "i32" or "i64", as a tree of the tokens' shape:
    ``(form, children...)``. Raises `CylonError` where a step cannot be
    shown to fit the int64, or the result its declared ``dtype``."""
    def refuse(step, rng, width):
        raise CylonError(
            Code.Invalid,
            f"compute: column {name!r}: the step {render(step, names)} may "
            f"reach [{rng[0]}, {rng[1]}] on this table (from the observed "
            f"ranges of its columns), which does not fit its {width} "
            f"column; no column was made")

    def walk(t):
        if t[0] == "col":
            rng, kids = ranges[t[1]], ()
        elif t[0] == "lit":
            rng, kids = (t[1], t[1]), ()
        elif t[0] == "case":
            rng, kids = (0, 1), ()
        else:
            (fa, ra), (fb, rb) = walk(t[1]), walk(t[2])
            rng, kids = _interval(t[0], ra, rb), (fa, fb)
        if not W.fits(*rng, W.INT64_RANGE):
            refuse(t, rng, "int64")
        form = "i32" if W.fits(*rng, W.INT32_RANGE) else "i64"
        return (form,) + kids, rng

    forms, rng = walk(tokens)
    if dtype == "int32" and forms[0] != "i32":
        refuse(tokens, rng, "int32 (make an operand an int64)")
    return forms, rng


def evaluate_native(tokens, leaves, dtype: str, valids=None):
    """The expression over native arrays (x64 on): every step an int64,
    the result cast to its declared dtype. `plan_forms` has shown that
    nothing wraps."""
    def walk(t):
        if t[0] == "col":
            return leaves[t[1]].astype(jnp.int64)
        if t[0] == "lit":
            return jnp.int64(t[1])
        if t[0] == "case":
            return predicate(t[1], leaves, valids or {}).astype(jnp.int64)
        a, b = walk(t[1]), walk(t[2])
        return a + b if t[0] == "add" else a - b if t[0] == "sub" else a * b

    return walk(tokens).astype(dtype)


def _leaf(x, form):
    """A column's array in ``form``: an int32 lane, or a pair of words."""
    planes = x.ndim == 2
    if form == "i32":
        if planes:
            return W.low_int32((x[0], x[1]))
        return jax.lax.bitcast_convert_type(x, jnp.int32) \
            if x.dtype == jnp.uint32 else x.astype(jnp.int32)
    if planes:
        return x[0], x[1]
    return W.from_uint32(x) if x.dtype == jnp.uint32 \
        else W.from_int32(x.astype(jnp.int32))


def _widen(v, form):
    return v if form == "i64" else W.from_int32(v)


def evaluate_words(tokens, forms, leaves, dtype: str, valids=None):
    """The expression where x64 is off: ``leaves`` maps a column position
    to its array (an integer lane at most 32 bits wide, or the
    ``uint32[2, n]`` planes of an int64). The result is an int32 lane or,
    for an int64 column, its ``uint32[2, n]`` planes."""
    def walk(t, f):
        form = f[0]
        if t[0] == "col":
            return _leaf(leaves[t[1]], form)
        if t[0] == "lit":
            return np.int32(t[1]) if form == "i32" else W.const(t[1])
        if t[0] == "case":    # {0, 1}: an int32 lane in every form
            return predicate(t[1], leaves, valids or {}).astype(jnp.int32)
        fa, fb = f[1], f[2]
        a, b = walk(t[1], fa), walk(t[2], fb)
        if form == "i32":
            # the low words alone decide a result that fits an int32
            a = a if fa[0] == "i32" else W.low_int32(a)
            b = b if fb[0] == "i32" else W.low_int32(b)
            return a + b if t[0] == "add" else a - b if t[0] == "sub" \
                else a * b
        if t[0] == "mul" and fa[0] == fb[0] == "i32":
            a, b = jnp.broadcast_arrays(jnp.asarray(a), jnp.asarray(b))
            return W.mul_i32(a, b)
        a, b = _widen(a, fa[0]), _widen(b, fb[0])
        return {"add": W.add, "sub": W.sub, "mul": W.mul}[t[0]](a, b)

    out = walk(tokens, forms)
    n = next(iter(leaves.values())).shape[-1]
    if dtype == "int32":
        return jnp.broadcast_to(out, (n,)).astype(jnp.int32)
    hi, lo = _widen(out, forms[0])
    return jnp.stack([jnp.broadcast_to(hi, (n,)), jnp.broadcast_to(lo, (n,))])


def range_probe(arrays):
    """The range of every array over every row, masks ignored (a dead or
    a null row's slot holds a value of the column's type too): ONE array
    to fetch, ``uint32[len(arrays), 4]`` in `wideint.minmax`'s words, or
    ``int64[len(arrays), 2]`` with x64 on."""
    if jax.config.jax_enable_x64:
        return jnp.stack([jnp.stack([x.min(), x.max()]).astype(jnp.int64)
                          for x in arrays])
    rows = []
    for x in arrays:
        if x.ndim == 2:
            rows.append(W.minmax((x[0], x[1])))
        elif x.dtype == jnp.uint32:
            rows.append(W.minmax(W.from_uint32(x)))
        else:
            rows.append(W.minmax_int32(x.astype(jnp.int32)))
    return jnp.stack(rows)


def ranges_of(fetched) -> list:
    """`range_probe`'s array, on the host, as (lo, hi) Python integers."""
    fetched = np.asarray(fetched)
    if fetched.shape[1] == 2:
        return [(int(lo), int(hi)) for lo, hi in fetched]
    return [W.range_of(row) for row in fetched]
