"""Logical-plan IR nodes, the filter expression mini-language (column
against literal, column against column, and / or / not) and the integer
value expressions of computed columns (`Value`, `Compute`; `case_when`
makes a predicate a value).

Every node knows its output ``schema`` (column names) and ``types``
(numpy dtype strings, with the sentinel ``"str"`` for string/varbytes
columns — the optimizer needs exactly one fact about a type: whether a
hash-placement witness can exist for it, see
parallel/shard.partition_signature). Column references are POSITIONS,
resolved from names once at construction by the `LazyTable` facade;
the projection-pruning pass remaps them wholesale.

``partitioned_by`` (an ordered tuple of output column positions, or
None) is the optimizer's propagated co-partitioning metadata: "every
row of this node's output lives on the shard its hash over these key
columns routes to". It mirrors — and must stay consistent with — the
runtime witness `Table._hash_partitioned`, because the executor's
shuffle-skipping lowerings re-verify against the runtime witness
before trusting it.
"""
from __future__ import annotations

from typing import List, Optional, Sequence, Tuple, Union

from ..exprtokens import VALUE_SYMBOL as _VALUE_SYMBOL
from ..exprtokens import (pred_columns, pred_remap, pred_repr,  # noqa: F401
                          value_columns, value_remap, value_repr)
from ..status import Code, CylonPlanError

# string-typed columns can never carry a hash-placement witness
# (partition_signature returns None for them: vocabulary unification /
# lane-count pairing re-codes the hashed bits per pairing)
STR_TYPE = "str"


# ---------------------------------------------------------------------------
# filter expressions
# ---------------------------------------------------------------------------


class Expr:
    """Base of the bound filter expression tree (column POSITIONS)."""

    def columns(self) -> set:
        raise NotImplementedError

    def remap(self, mapping) -> "Expr":
        raise NotImplementedError

    def mask(self, table):
        """Evaluate to a bool mask array over ``table``'s capacity —
        same semantics as the eager `Table` comparison operators
        (comparison AND column validity; boolean combinators are plain
        elementwise ops)."""
        raise NotImplementedError

    def tokens(self) -> tuple:
        """The BOUND expression as a token tree, the form a `Compute`
        node holds a predicate in (`case_when`) and the fingerprint
        reads: ``("cmp", pos, op, literal)``, ``("colcmp", a, op, b)``,
        ``("and" | "or", x, y)``, ``("not", x)``."""
        raise NotImplementedError

    def check(self, schema, types) -> None:
        """Raise a `CylonPlanError` where the bound expression cannot be
        evaluated over columns of ``types`` (the plan's type strings)."""

    def __and__(self, other: "Expr") -> "Expr":
        return BoolOp("and", self, other)

    def __or__(self, other: "Expr") -> "Expr":
        return BoolOp("or", self, other)

    def __invert__(self) -> "Expr":
        return Not(self)


class Value:
    """Unbound integer VALUE expression: ``col("a") * (100 - col("b"))``.
    Operands are columns, other value expressions and integer literals;
    the operations ``+``, ``-``, ``*``, and `case_when` (a predicate as
    1 / 0). `LazyTable.with_columns` binds it to a token tree over column
    positions (`bind_value`), which is what a `Compute` node holds, the
    fingerprint reads and the lowering evaluates: ``("col", i)``,
    ``("lit", v)``, ``(op, a, b)``, ``("case", predicate tokens)``."""

    def __add__(self, other):
        return Arith("add", self, other)

    def __radd__(self, other):
        return Arith("add", other, self)

    def __sub__(self, other):
        return Arith("sub", self, other)

    def __rsub__(self, other):
        return Arith("sub", other, self)

    def __mul__(self, other):
        return Arith("mul", self, other)

    def __rmul__(self, other):
        return Arith("mul", other, self)


class Arith(Value):
    def __init__(self, op: str, a, b):
        self.op, self.a, self.b = op, a, b

    def __repr__(self):
        return f"({self.a!r} {_VALUE_SYMBOL[self.op]} {self.b!r})"


class Case(Value):
    """``CASE WHEN predicate THEN 1 ELSE 0 END``: an int32 that is 1 where
    the predicate is true and 0 where it is false or null (so it is never
    null itself). Built by `case_when`."""

    def __init__(self, pred: "Expr"):
        if not isinstance(pred, Expr):
            raise CylonPlanError(
                f"case_when takes a predicate (col('x') > 3), not {pred!r}",
                code=Code.TypeError)
        self.pred = pred

    def __repr__(self):
        return f"case_when({self.pred!r})"


def case_when(pred: "Expr") -> Case:
    """``CASE WHEN pred THEN 1 ELSE 0 END`` as a value expression for
    `LazyTable.with_columns`: summed, it counts the rows ``pred`` holds
    on."""
    return Case(pred)


def bind_value(v, resolver, check=None) -> tuple:
    """The token tree of an unbound value expression, column references
    resolved to positions by ``resolver``; ``check(bound predicate)`` sees
    every `case_when` predicate once it is bound."""
    if isinstance(v, Col):
        return ("col", int(resolver(v.ref)))
    if isinstance(v, Arith):
        return (v.op, bind_value(v.a, resolver, check),
                bind_value(v.b, resolver, check))
    if isinstance(v, Case):
        bound = v.pred.bind(resolver)
        if check is not None:
            check(bound)
        return ("case", bound.tokens())
    if isinstance(v, int) and not isinstance(v, bool):
        return ("lit", int(v))
    raise CylonPlanError(
        f"a value expression takes columns, + - *, case_when and integer "
        f"literals (decimals are scaled integers), not {v!r}",
        code=Code.TypeError)


class Col(Value):
    """Unbound column reference — the user-facing builder. ``col("x") >
    3`` constructs a comparison; `LazyTable.filter` binds names to
    positions against its schema. In arithmetic it is a `Value`."""

    def __init__(self, ref: Union[str, int]):
        self.ref = ref

    def __repr__(self):
        return f"col({self.ref!r})"

    def _cmp(self, op, value):
        if isinstance(value, Col):
            return ColCmp(self.ref, op, value.ref)
        if isinstance(value, (Expr, Value)):
            raise CylonPlanError(
                f"a predicate compares a column with a literal or with "
                f"another column, not with {value!r}",
                code=Code.NotImplemented)
        return Cmp(self.ref, op, value)

    def __eq__(self, v):  # type: ignore[override]
        return self._cmp("eq", v)

    def __ne__(self, v):  # type: ignore[override]
        return self._cmp("ne", v)

    def __lt__(self, v):
        return self._cmp("lt", v)

    def __gt__(self, v):
        return self._cmp("gt", v)

    def __le__(self, v):
        return self._cmp("le", v)

    def __ge__(self, v):
        return self._cmp("ge", v)

    def __hash__(self):
        return hash(("Col", self.ref))


def col(ref: Union[str, int]) -> Col:
    """Column reference for `LazyTable.filter` predicates."""
    return Col(ref)


class Cmp(Expr):
    """column <op> literal. ``pos`` starts as the unbound name/position
    from `col()`; `bind` resolves it."""

    def __init__(self, pos, op: str, value):
        self.pos = pos
        self.op = op
        self.value = value

    def bind(self, resolver) -> "Cmp":
        return Cmp(resolver(self.pos), self.op, self.value)

    def columns(self) -> set:
        return {self.pos}

    def remap(self, mapping) -> "Cmp":
        return Cmp(mapping[self.pos], self.op, self.value)

    def mask(self, table):
        from ..data.table import Table

        # route through the eager comparison machinery (dict/varbytes
        # strings included) so planned filters match eager filters bit
        # for bit; _compare ANDs column validity into the result
        sub = Table([table._columns[self.pos]], table._ctx,
                    table.row_mask)
        return sub._compare(self.value, self.op)._columns[0].data

    def tokens(self) -> tuple:
        return ("cmp", int(self.pos), str(self.op), self.value)

    def __repr__(self):
        return f"c{self.pos} {self.op} {self.value!r}"


# the widest column a column-against-column predicate compares: one 32-bit
# lane a side (a 64-bit column is two word planes where x64 is off)
_COLCMP_TYPES = ("bool", "int8", "int16", "int32", "uint8", "uint16",
                 "uint32", "float16", "float32")


class ColCmp(Expr):
    """column <op> column: two columns of ONE fixed-width type at most 32
    bits wide (integers, floats, dates as their int32 days). Null where
    either side is null, as `Cmp` treats a null."""

    def __init__(self, a, op: str, b):
        self.a, self.op, self.b = a, op, b

    def bind(self, resolver) -> "ColCmp":
        return ColCmp(resolver(self.a), self.op, resolver(self.b))

    def columns(self) -> set:
        return {self.a, self.b}

    def remap(self, mapping) -> "ColCmp":
        return ColCmp(mapping[self.a], self.op, mapping[self.b])

    def check(self, schema, types) -> None:
        ta, tb = str(types[self.a]), str(types[self.b])
        names = f"{schema[self.a]!r} ({ta}) and {schema[self.b]!r} ({tb})"
        if STR_TYPE in (ta, tb):
            raise CylonPlanError(
                f"compare of two columns: {names}: string columns "
                f"(dictionary codes of two vocabularies, or varbytes) are "
                f"not compared with each other; compare each with a "
                f"literal, or join on them", code=Code.NotImplemented)
        if ta != tb:
            raise CylonPlanError(
                f"compare of two columns: {names} are of two types; cast "
                f"one on the host", code=Code.TypeError)
        if ta not in _COLCMP_TYPES:
            raise CylonPlanError(
                f"compare of two columns: {names}: a 64-bit column is held "
                f"as two 32-bit word planes where x64 is off, and a "
                f"compare of planes is not built (ROADMAP 2a-b); columns "
                f"of at most 32 bits compare", code=Code.NotImplemented)

    def mask(self, table):
        from ..data.table import compare_columns

        return compare_columns(table, self.a, self.op, self.b)

    def tokens(self) -> tuple:
        return ("colcmp", int(self.a), str(self.op), int(self.b))

    def __repr__(self):
        return f"c{self.a} {self.op} c{self.b}"


class BoolOp(Expr):
    def __init__(self, op: str, a: Expr, b: Expr):
        self.op = op
        self.a = a
        self.b = b

    def bind(self, resolver) -> "BoolOp":
        return BoolOp(self.op, self.a.bind(resolver), self.b.bind(resolver))

    def columns(self) -> set:
        return self.a.columns() | self.b.columns()

    def remap(self, mapping) -> "BoolOp":
        return BoolOp(self.op, self.a.remap(mapping), self.b.remap(mapping))

    def check(self, schema, types) -> None:
        self.a.check(schema, types)
        self.b.check(schema, types)

    def mask(self, table):
        a, b = self.a.mask(table), self.b.mask(table)
        return (a & b) if self.op == "and" else (a | b)

    def tokens(self) -> tuple:
        return (str(self.op), self.a.tokens(), self.b.tokens())

    def __repr__(self):
        return f"({self.a!r} {self.op} {self.b!r})"


class Not(Expr):
    def __init__(self, a: Expr):
        self.a = a

    def bind(self, resolver) -> "Not":
        return Not(self.a.bind(resolver))

    def columns(self) -> set:
        return self.a.columns()

    def remap(self, mapping) -> "Not":
        return Not(self.a.remap(mapping))

    def check(self, schema, types) -> None:
        self.a.check(schema, types)

    def mask(self, table):
        return ~self.a.mask(table)

    def tokens(self) -> tuple:
        return ("not", self.a.tokens())

    def __repr__(self):
        return f"~{self.a!r}"


# ---------------------------------------------------------------------------
# plan nodes
# ---------------------------------------------------------------------------


class PlanNode:
    kind = "node"

    def __init__(self, children: Sequence["PlanNode"], schema: List[str],
                 types: List[str]):
        self.children = list(children)
        self.schema = list(schema)
        self.types = list(types)
        # ordered output positions this node's rows are hash-placed by,
        # or None — filled in by the optimizer's propagation pass
        self.partitioned_by: Optional[Tuple[int, ...]] = None

    @property
    def width(self) -> int:
        return len(self.schema)

    def args_repr(self) -> str:
        return ""

    def __repr__(self):
        return f"{type(self).__name__}({self.args_repr()})"


class Scan(PlanNode):
    """Leaf: either a direct `Table` reference (``table``), or a
    `table_api` registry id (``table_id``) re-fetched at run time (late
    binding — the handle space bindings already use). Schema/types/
    witness are snapshots taken at construction. Holding the Table
    directly (rather than auto-registering it) keeps plan construction
    from pinning device buffers in the process-global registry."""

    kind = "scan"

    def __init__(self, table_id: Optional[str], schema, types,
                 witness_sig=None, table=None):
        super().__init__([], schema, types)
        self.table_id = table_id
        self.table = table
        self.witness_sig = witness_sig  # Table._hash_partitioned snapshot

    def __deepcopy__(self, memo):
        # plans deepcopy before optimization; the referenced Table's
        # device buffers must be SHARED, never copied
        new = Scan(self.table_id, list(self.schema), list(self.types),
                   self.witness_sig, table=self.table)
        memo[id(self)] = new
        return new

    def args_repr(self):
        src = self.table_id if self.table_id is not None else "<inline>"
        return f"{src!r}, cols={self.schema}"


class Project(PlanNode):
    kind = "project"

    def __init__(self, child: PlanNode, cols: Sequence[int]):
        self.cols = [int(c) for c in cols]
        super().__init__([child], [child.schema[c] for c in self.cols],
                         [child.types[c] for c in self.cols])

    def args_repr(self):
        return f"cols={self.cols}"


class Filter(PlanNode):
    kind = "filter"

    def __init__(self, child: PlanNode, expr: Expr, below_join: int = 0):
        super().__init__([child], child.schema, child.types)
        self.expr = expr
        # conjuncts of this filter that the optimizer moved here through a
        # Join (`optimizer.pushdown_filters`); the executor counts them
        self.below_join = int(below_join)

    def args_repr(self):
        return repr(self.expr) + (
            f", {self.below_join} conjunct(s) pushed below a join"
            if self.below_join else "")


class Compute(PlanNode):
    """Appends named computed columns: output = the child's columns, then
    one column an expression (`Value` bound to a token tree). Expression
    i may read the child's columns and the computed columns before it
    (positions ``child.width + j``, j < i), so a later column reuses an
    earlier one. ``out_types`` are the computed columns' dtypes
    ("int32" / "int64"), given by the builder, which asks the lowering's
    own rule (`data.table.value_dtype`): plan/ imports no ops/."""

    kind = "compute"

    def __init__(self, child: PlanNode, names: Sequence[str],
                 exprs: Sequence[tuple], out_types: Sequence[str]):
        super().__init__([child], list(child.schema) + list(names),
                         list(child.types) + list(out_types))
        self.names = [str(n) for n in names]
        self.exprs = list(exprs)
        self.out_types = [str(t) for t in out_types]

    def args_repr(self):
        return ", ".join(f"{n}={value_repr(e)}"
                         for n, e in zip(self.names, self.exprs))


class Shuffle(PlanNode):
    """Explicit hash repartition by key columns — inserted by the
    physical-planning pass below joins (and by user `.shuffle()`), then
    deleted by the elision pass when its input already satisfies it.

    ``salted``: set by the adaptive pass (optimizer.adapt_from_stats)
    on STANDALONE shuffles whose measured skew crossed the warning
    threshold — the exchange spreads each hot destination's rows
    across ``CYLON_SALT_FACTOR`` sub-buckets, so the output is
    load-balanced but carries NO hash-placement witness (the salt is
    positional; downstream consumers re-establish placement)."""

    kind = "shuffle"

    def __init__(self, child: PlanNode, keys: Sequence[int]):
        super().__init__([child], child.schema, child.types)
        self.keys = [int(k) for k in keys]
        self.salted = False

    def args_repr(self):
        return f"keys={self.keys}" + (", salted" if self.salted else "")


class Join(PlanNode):
    """``algorithm`` is the user-facing local-kernel hint ("auto" /
    "sort" / "hash") — or "broadcast", the adaptive rewrite
    (optimizer.adapt_from_stats): the ``build_side`` (0=left, 1=right)
    is replicated to every shard inside one gather program and probed
    locally, with NO all-to-all on either side. ``build_side`` is set
    only by the rewrite; a user-forced ``algorithm="broadcast"`` leaves
    it None until the optimizer picks the side.

    ``how`` "semi" / "anti" (SQL's ``EXISTS`` / ``NOT EXISTS``): the
    schema is the LEFT side's columns only, under the names every join
    gives its left columns (``lt-0 .. lt-(nl-1)``), and the rows are a
    subset of the left side's."""

    kind = "join"
    LEFT_ONLY = ("semi", "anti")

    def __init__(self, left: PlanNode, right: PlanNode,
                 left_on: Sequence[int], right_on: Sequence[int],
                 how: str = "inner", algorithm: str = "auto"):
        nl = left.width
        schema = [f"lt-{i}" for i in range(nl)]
        types = list(left.types)
        if how not in self.LEFT_ONLY:
            schema += [f"rt-{nl + j}" for j in range(right.width)]
            types += right.types
        super().__init__([left, right], schema, types)
        self.left_on = [int(i) for i in left_on]
        self.right_on = [int(j) for j in right_on]
        self.how = how
        self.algorithm = algorithm
        self.build_side: Optional[int] = None

    def args_repr(self):
        alg = f", algo={self.algorithm}" \
            if self.algorithm not in ("auto",) else ""
        bs = f", build={self.build_side}" \
            if self.build_side is not None else ""
        return f"{self.how}, l{self.left_on}=r{self.right_on}{alg}{bs}"


class GroupBy(PlanNode):
    """Hash aggregate. ``ops`` are op-name strings ("sum", "count",
    "mean", "min", "max") — the lowering converts them; keeping strings
    here keeps `plan/` free of `ops/` imports (the lint gate)."""

    kind = "groupby"

    _AGG_TYPES = {"count": "int64", "mean": "float64"}

    def __init__(self, child: PlanNode, keys: Sequence[int],
                 agg_cols: Sequence[int], ops: Sequence[str]):
        keys = [int(k) for k in keys]
        agg_cols = [int(a) for a in agg_cols]
        schema = [child.schema[k] for k in keys] \
            + [child.schema[a] for a in agg_cols]
        types = [child.types[k] for k in keys] \
            + [self._AGG_TYPES.get(o, child.types[a])
               for a, o in zip(agg_cols, ops)]
        super().__init__([child], schema, types)
        self.keys = keys
        self.agg_cols = agg_cols
        self.ops = [str(o) for o in ops]
        # set by the elision pass: input partitioning satisfies the keys,
        # so the lowering may aggregate per shard with no exchange
        self.local_ok = False

    def args_repr(self):
        aggs = list(zip(self.agg_cols, self.ops))
        return f"keys={self.keys}, aggs={aggs}" + \
            (", local" if self.local_ok else "")


class SetOp(PlanNode):
    """union | subtract | intersect (op held as the Table method name)."""

    kind = "setop"

    def __init__(self, left: PlanNode, right: PlanNode, op: str):
        if left.width != right.width:
            raise CylonPlanError("set ops need equal schemas")
        super().__init__([left, right], left.schema, left.types)
        self.op = str(op)

    def args_repr(self):
        return self.op


class Sort(PlanNode):
    kind = "sort"

    def __init__(self, child: PlanNode, by: Sequence[int], ascending):
        super().__init__([child], child.schema, child.types)
        self.by = [int(b) for b in by]
        self.ascending = list(ascending) \
            if isinstance(ascending, (list, tuple)) \
            else [bool(ascending)] * len(self.by)

    def args_repr(self):
        return f"by={self.by}, asc={self.ascending}"


def walk(node: PlanNode):
    """Pre-order traversal."""
    yield node
    for c in node.children:
        yield from walk(c)


def format_plan(node: PlanNode, indent: str = "") -> str:
    """Indented tree for `LazyTable.explain`."""
    pb = node.partitioned_by
    line = f"{indent}{type(node).__name__}({node.args_repr()})" + \
        (f"  partitioned_by={tuple(pb)}" if pb is not None else "")
    parts = [line]
    for c in node.children:
        parts.append(format_plan(c, indent + "  "))
    return "\n".join(parts)
