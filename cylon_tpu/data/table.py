"""Table — the user-facing columnar table.

Mirrors the reference's `cylon::Table` + free-function operator API
(reference: cpp/src/cylon/table.hpp:43-387) and the pycylon surface
(python/pycylon/data/table.pyx:65-798), re-designed for the TPU execution
model:

* a Table is a GLOBAL view: a list of Columns whose arrays live in device
  HBM. On a distributed context the arrays are row-sharded over the 1-D
  mesh (jax.sharding.NamedSharding) — the reference's "one partition per
  MPI rank" becomes "one shard per chip", but the user holds ONE object,
  exactly like a global jax.Array.
* sharded tables carry a row-validity mask (`row_mask`): shards are padded
  to equal length (XLA static shapes), padding rows are masked out. This is
  the moral equivalent of Cylon's ragged per-rank partitions.
* every local op accepts the mask ("emit") so padded tables flow through
  kernels without host round-trips; compaction happens only at export.

Distributed ops (distributed_join & co) live in cylon_tpu/parallel and are
re-exported as methods here, following the reference's dual local/
distributed API (table.hpp:262-336).
"""
from __future__ import annotations

import functools
import math
from functools import partial
from typing import (Dict, List, NamedTuple, Optional, Sequence, Tuple,
                    Union)

import jax
import jax.numpy as jnp
import numpy as np

from .. import dtypes
from ..config import CSVWriteOptions
from ..context import CylonContext
from ..status import Code, CylonError
from .column import (Column, align_string_columns, as_varbytes,
                     refuse_planes, string_key_arrays, unify_dictionaries)
from .strings import concat_varbytes, pair_k_words
from .. import telemetry as _telemetry
from ..ops import aggregates as _aggregates
from ..ops import expr as _expr
from ..ops import groupby as _groupby
from ..ops import join as _join
from ..ops import order as _order
from ..ops import setops as _setops
from ..ops import tpu_kernels as _kernels


class Table:
    def __init__(self, columns: List[Column], ctx: Optional[CylonContext] = None,
                 row_mask=None):
        self._columns = columns
        self._ctx = ctx or CylonContext.Init()
        self._row_count_cache: Optional[int] = None
        self._row_mask = row_mask  # bool [n] or None (all rows live)
        # co-partitioning witness: (key col idxs, key dtype sig, world) set
        # by shuffle/distribute_by_key; lets a later shuffle on the same
        # keys skip the exchange (parallel/dist_ops.shuffle)
        self._hash_partitioned = None
        # order witness: the live rows are in ascending order (nulls
        # last) of the first `_key_ordered` columns, as a sort by them
        # would leave them; set by the dense groupby, whose slot order
        # is key order (`ordered_by`)
        self._key_ordered = 0
        if columns:
            n = len(columns[0])
            for c in columns:
                if len(c) != n:
                    raise CylonError(Code.Invalid, "ragged columns")

    # ------------------------------------------------------------------
    # properties (pycylon parity: table.pyx column_names/column_count/...)
    # ------------------------------------------------------------------

    @property
    def context(self) -> CylonContext:
        return self._ctx

    @property
    def column_names(self) -> List[str]:
        return [c.name for c in self._columns]

    @property
    def column_count(self) -> int:
        return len(self._columns)

    @property
    def row_mask(self):
        """Row-validity mask: bool [capacity] or None (all rows live)."""
        return self._row_mask

    @row_mask.setter
    def row_mask(self, mask) -> None:
        self._row_mask = mask
        self._row_count_cache = None

    @property
    def row_count(self) -> int:
        """Live row count. Masked tables sync ONE scalar to the host on
        first access; the result is cached (columns/mask never change
        after construction — mutators like clear() reset the cache)."""
        if not self._columns:
            return 0
        if self.row_mask is None:
            return len(self._columns[0])
        if self._row_count_cache is None:
            self._row_count_cache = int(_telemetry.host_fetch(
                "row_count", self.row_mask.sum()))
        return self._row_count_cache

    def columns(self) -> List[Column]:
        return self._columns

    def get_column(self, i: int) -> Column:
        return self._columns[i]

    def rows(self) -> int:
        """Reference: Table::Rows (table.hpp:134)."""
        return self.row_count

    def __len__(self) -> int:
        return self.row_count

    @property
    def capacity(self) -> int:
        """Physical (padded) row slots."""
        return len(self._columns[0]) if self._columns else 0

    def buffers(self) -> List:
        """Every device buffer this table references (data + validity +
        varbytes words/starts + row mask) — the canonical enumeration
        behind ``nbytes``, and the telemetry ledger's identity set for
        deduplicating shared-buffer views (zero-copy project/filter
        outputs must not double-count live bytes)."""
        out = [] if self.row_mask is None else [self.row_mask]
        for c in self._columns:
            out.append(c.data)
            if c.validity is not None:
                out.append(c.validity)
            if c.is_varbytes:
                vb = c.varbytes
                out.append(vb.words)
                out.append(vb.starts)
        return out

    @property
    def nbytes(self) -> int:
        """Device bytes this table's buffers span — shape × itemsize,
        computed on the host with NO device sync. The telemetry layer's
        ``bytes`` measurement for EXPLAIN ANALYZE reports."""
        return sum(a.dtype.itemsize * math.prod(a.shape)
                   for a in self.buffers())

    def emit_mask(self) -> jnp.ndarray:
        if self.row_mask is None:
            return jnp.ones(self.capacity, dtype=bool)
        return self.row_mask

    # ------------------------------------------------------------------
    # constructors (pycylon: from_arrow/from_numpy/from_list/from_pydict/
    # from_pandas, table.pyx:556-624)
    # ------------------------------------------------------------------

    @staticmethod
    def from_arrow(ctx: CylonContext, pa_table) -> "Table":
        cols = [Column.from_pyarrow(pa_table.column(i), pa_table.column_names[i])
                for i in range(pa_table.num_columns)]
        return Table(cols, ctx)

    @staticmethod
    def from_pandas(ctx: CylonContext, df) -> "Table":
        cols = []
        for name in df.columns:
            s = df[name]
            validity = None
            if s.isna().any():
                validity = (~s.isna()).to_numpy()
            arr = s.to_numpy()
            cols.append(Column.from_numpy(arr, str(name), validity))
        return Table(cols, ctx)

    @staticmethod
    def from_numpy(ctx: CylonContext, col_names: Sequence[str],
                   arrays: Sequence[np.ndarray]) -> "Table":
        if len(col_names) != len(arrays):
            raise CylonError(Code.Invalid, "names/arrays length mismatch")
        cols = [Column.from_numpy(np.asarray(a), n)
                for n, a in zip(col_names, arrays)]
        return Table(cols, ctx)

    @staticmethod
    def from_pydict(ctx: CylonContext, data: Dict[str, Sequence]) -> "Table":
        return Table.from_numpy(ctx, list(data.keys()),
                                [np.asarray(v) for v in data.values()])

    @staticmethod
    def from_list(ctx: CylonContext, col_names: Sequence[str],
                  data: Sequence[Sequence]) -> "Table":
        return Table.from_numpy(ctx, col_names, [np.asarray(v) for v in data])

    # ------------------------------------------------------------------
    # exporters (table.pyx:626-693)
    # ------------------------------------------------------------------

    def _compact_indices(self) -> Optional[np.ndarray]:
        if self.row_mask is None:
            return None
        return np.flatnonzero(np.asarray(jax.device_get(self.row_mask)))

    def compact(self) -> "Table":
        """Drop masked rows; returns a dense table."""
        idx = self._compact_indices()
        if idx is None:
            return self
        cols = [c.take(jnp.asarray(idx)) for c in self._columns]
        return Table(cols, self._ctx)

    def _unique_names(self) -> List[str]:
        """Column names with duplicates suffixed (_2, _3, …) so dict
        exports can't silently drop columns (groupby emits one output
        per (column, op) pair — names repeat)."""
        seen: Dict[str, int] = {}
        used = set()
        out = []
        for c in self._columns:
            k = seen.get(c.name, 0) + 1
            name = c.name if k == 1 else f"{c.name}_{k}"
            # suffixes can still collide with literal column names
            while name in used:
                k += 1
                name = f"{c.name}_{k}"
            seen[c.name] = k
            used.add(name)
            out.append(name)
        return out

    def to_pydict(self) -> Dict[str, np.ndarray]:
        t = self.compact()
        return {n: c.to_numpy()
                for n, c in zip(t._unique_names(), t._columns)}

    def to_pydict_local(self) -> Dict[str, np.ndarray]:
        """THIS process's shards' live rows as host numpy — the
        per-process handoff for DDP-style training feeds (see
        parallel/shard.extract_process_local)."""
        from ..parallel import shard as _shard

        return _shard.extract_process_local(self, self._ctx)

    def to_numpy(self, order: str = "F") -> np.ndarray:
        t = self.compact()
        arrs = [c.to_numpy() for c in t._columns]
        return np.array(arrs).T.copy() if order == "F" else \
            np.ascontiguousarray(np.array(arrs).T)

    def to_pandas(self):
        import pandas as pd

        t = self.compact()
        # build positionally then rename: a dict would silently collapse
        # duplicate column names (groupby outputs repeat source names)
        df = pd.DataFrame({i: pd.Series(c.to_numpy())
                           for i, c in enumerate(t._columns)})
        df.columns = [c.name for c in t._columns]
        return df

    def to_arrow(self):
        import pyarrow as pa

        t = self.compact()
        return pa.table([c.to_pyarrow() for c in t._columns],
                        names=[c.name for c in t._columns])

    def to_csv(self, path: str, options: Optional[CSVWriteOptions] = None) -> None:
        from ..io.csv import write_csv

        write_csv(self, path, options)

    # reference: Table::WriteCSV (table.hpp:92)
    write_csv = to_csv

    def to_parquet(self, path: str) -> None:
        from ..io.parquet import write_parquet

        write_parquet(self, path)

    def show(self, row1: int = 0, row2: int = -1, col1: int = 0,
             col2: int = -1) -> None:
        """Print (pycylon table.pyx show/show_by_range)."""
        df = self.to_pandas()
        if row2 == -1:
            row2 = len(df)
        if col2 == -1:
            col2 = df.shape[1]
        print(df.iloc[row1:row2, col1:col2].to_string(index=False))

    print = show  # reference: Table::Print

    def clear(self) -> None:
        # free event: retire this table's ledger entry (if any) so
        # cylon_live_table_bytes drops and leak reports stay honest —
        # _free_if_unretained and finalize both route through here.
        # IDEMPOTENT under double-release: resilience retry/degrade
        # paths can re-enter cleanup (an op frees its non-retained
        # inputs, then the caller's error path finalizes again) — the
        # second call must be a no-op, never a second ledger event
        if getattr(self, "_cleared", False):
            return
        self._cleared = True
        _telemetry.ledger.release(self)
        self._columns = []
        self.row_mask = None
        self._row_count_cache = None

    def retain_memory(self, retain: bool = True) -> None:
        """Reference: Table::retainMemory (table.hpp:178) — free-after-use
        hint: with retain=False, the next operator that consumes this
        table clears its column references after use (reference: Shuffle
        frees non-retained inputs, table.cpp:207), letting the HBM return
        to the arena as soon as XLA's refcounts drop."""
        self._retain = bool(retain)

    def is_retain(self) -> bool:
        """Reference: Table::IsRetain (table.hpp:183)."""
        return getattr(self, "_retain", True)

    def _free_if_unretained(self) -> None:
        if not self.is_retain():
            self.clear()

    def finalize(self) -> None:
        self.clear()

    # ------------------------------------------------------------------
    # row selection / projection
    # ------------------------------------------------------------------

    def take(self, indices) -> "Table":
        """Gather rows by LOGICAL index (live rows in order); −1 produces
        null rows. Masked tables compact first so positional indexing
        never addresses filtered-out rows."""
        t = self.compact()
        idx = jnp.asarray(indices)
        cols = [c.take(idx) for c in t._columns]
        return Table(cols, self._ctx)

    def project(self, columns: Sequence[Union[int, str]]) -> "Table":
        """Zero-copy column subset (reference: Project, table.cpp:1066-1085).
        The hash-placement witness survives (positions remapped) when
        every witnessed key column is kept — projection never moves
        rows, so a later same-key shuffle can still skip."""
        idxs = [self._col_index(c) for c in columns]
        t = Table([self._columns[i] for i in idxs], self._ctx, self.row_mask)
        hp = self._hash_partitioned
        if hp is not None and all(k in idxs for k in hp[0]):
            t._hash_partitioned = (tuple(idxs.index(k) for k in hp[0]),
                                   ) + tuple(hp[1:])
        return t

    def select(self, predicate) -> "Table":
        """Row-lambda filter (reference: Select, table.cpp:698-727 — a host
        row loop in the reference too; prefer mask-based filtering for speed)."""
        t = self.compact()
        data = [c.to_numpy() for c in t._columns]
        n = len(data[0]) if data else 0
        mask = np.zeros(n, dtype=bool)
        from .row import Row

        for i in range(n):
            mask[i] = bool(predicate(Row(t, i, _cache=data)))
        return t.filter_mask(jnp.asarray(mask))

    def filter_mask(self, mask) -> "Table":
        """Filter by a boolean mask array/column. ZERO host syncs: the
        mask folds into ``row_mask`` (every kernel honors emit masks), so
        a filter inside an eager pipeline costs one elementwise AND —
        no count round-trip, no gather. Memory for the dead rows is
        reclaimed at the next shuffle/compact (both drop masked rows)."""
        mask = jnp.asarray(mask)
        # no AND with an all-ones mask: each is a program and a pass
        keep = mask if self.row_mask is None else mask & self.row_mask
        t = Table(list(self._columns), self._ctx, keep)
        t._hash_partitioned = self._hash_partitioned
        return t

    def with_columns(self, names: Sequence[str], exprs: Sequence[tuple]
                     ) -> "Table":
        """This table and, after its columns, one computed column an
        expression: integer token trees over column POSITIONS
        (ops/expr.py; expression i may read computed column j < i at
        position ``column_count + j``). Exact or not at all: ONE program
        probes the range of every column read (`jit_expr_ranges_program`;
        the fetch is ``sync.expr.range``), the host shows from the ranges
        that no step can leave its width - or raises, naming the column
        and the step - and ONE program evaluates every expression
        (`jit_expr_compute_program`), each step in the narrowest form its
        range allows. A computed value is null where a column it reads
        is. Elementwise: a sharded table stays as it is sharded."""
        return _with_columns(self, list(names), list(exprs))

    def ordered_by(self, by: Sequence, ascending=True) -> bool:
        """Whether the live rows are known to be in the order that
        ``sort(by, ascending)`` would leave them in (a witness set by the
        operator that produced the table; False says nothing)."""
        by = [self._col_index(c) for c in
              (by if isinstance(by, (list, tuple)) else [by])]
        asc = ascending if isinstance(ascending, (list, tuple)) \
            else [ascending] * len(by)
        return 0 < len(by) <= self._key_ordered and all(asc) \
            and by == list(range(len(by)))

    def slice(self, start: int, stop: int) -> "Table":
        t = self.compact()
        return Table([c.slice(start, stop) for c in t._columns], self._ctx)

    def _col_index(self, c: Union[int, str]) -> int:
        if isinstance(c, (int, np.integer)):
            return int(c)
        try:
            return self.column_names.index(c)
        except ValueError:
            raise CylonError(Code.KeyError, f"no column named {c!r}")

    # ------------------------------------------------------------------
    # sort / merge
    # ------------------------------------------------------------------

    def sort(self, order_by: Union[int, str, Sequence],
             ascending: Union[bool, Sequence[bool]] = True) -> "Table":
        """Local sort (reference: Sort, table.cpp / util/arrow_utils.cpp:144-184
        — argsort the key column then gather every column)."""
        refuse_planes(self._columns, "sort")
        t = self.compact()
        cols_idx = [t._col_index(c) for c in
                    (order_by if isinstance(order_by, (list, tuple)) else [order_by])]
        asc = ascending if isinstance(ascending, (list, tuple)) \
            else [ascending] * len(cols_idx)
        keys = _sort_keys_mixed([t._columns[i] for i in cols_idx], asc)
        if keys is None:  # varbytes rows beyond the device prefix bound
            return t.take(_host_sort_perm(
                [t._columns[i] for i in cols_idx], asc))
        perm = _order.lexsort_indices(keys)
        return t.take(perm)

    def merge(self, other_or_list) -> "Table":
        """Concatenate tables (reference: Merge, table.hpp:250)."""
        others = other_or_list if isinstance(other_or_list, (list, tuple)) \
            else [other_or_list]
        tables = [self.compact()] + [o.compact() for o in others]
        return concat_tables(tables, self._ctx)

    # ------------------------------------------------------------------
    # joins
    # ------------------------------------------------------------------

    def join(self, table: "Table", join_type: str = "inner",
             algorithm: str = "auto", **kwargs) -> "Table":
        """Local join; self is the LEFT table (pycylon table.pyx:373-390).
        algorithm: "auto" (default — fastest applicable path), "sort", or
        "hash" (reference join_config.hpp:25).

        join_type: "inner", "left", "right", "outer" / "full_outer", and
        "semi" / "anti" (the LEFT semi and anti join, SQL's ``EXISTS`` /
        ``NOT EXISTS``; the reference has neither). "semi" keeps a left
        row when at least one live right row has an equal, non-null key,
        "anti" when none has: a left row whose key is null is kept by
        "anti" and dropped by "semi", null keys on the right match
        nothing. Each kept row comes out once whatever the number of
        matches, and the result holds only the left's columns, named
        ``lt-<i>`` as every join names them, at the LEFT side's capacity
        under a row mask decided on the device: nothing is expanded and
        the sort path fetches no count (the hash path fetches its
        collision count)."""
        blk = kwargs.pop("probe_block_rows", None)
        cfg = self._make_join_config(table, join_type, algorithm, kwargs)
        if blk:
            return join_blocked(self, table, cfg, int(blk))
        return join(self, table, cfg)

    def distributed_join(self, table: "Table", join_type: str = "inner",
                         algorithm: str = "auto", **kwargs) -> "Table":
        """comm="shuffle" (default) repartitions both sides via all-to-all;
        comm="broadcast" replicates ``build_side`` (0=left, 1=right;
        default right) to every shard and probes locally — zero
        all-to-all, the adaptive optimizer's rewrite target for a
        measured-small build side. ``join_type`` as `join`; "semi" and
        "anti" run on the shuffle path alone: neither side of such a join
        may be replicated yet, so comm="broadcast" falls back to it, as
        for a full outer join."""
        from ..parallel import dist_ops

        comm = kwargs.pop("comm", "shuffle")
        build_side = kwargs.pop("build_side", 1)
        cfg = self._make_join_config(table, join_type, algorithm, kwargs)
        if comm == "broadcast":
            return dist_ops.broadcast_hash_join(self, table, cfg,
                                                build_side=int(build_side))
        if comm != "shuffle":
            raise CylonError(Code.Invalid,
                             f"unknown comm mode {comm!r} "
                             "(expected 'shuffle' or 'broadcast')")
        return dist_ops.distributed_join(self, table, cfg)

    def _make_join_config(self, table: "Table", join_type, algorithm, kwargs
                          ) -> _join.JoinConfig:
        exact = bool(kwargs.pop("exact", False))
        lidx, ridx = _resolve_join_columns(self, table, kwargs)
        jt = _JOIN_TYPES.get(join_type if not isinstance(join_type, _join.JoinType)
                             else join_type.name.lower())
        if isinstance(join_type, _join.JoinType):
            jt = join_type
        if jt is None:
            raise CylonError(Code.Invalid, f"Unsupported join type {join_type}")
        alg = _JOIN_ALGOS.get(algorithm, _join.JoinAlgorithm.SORT) \
            if isinstance(algorithm, str) else algorithm
        return _join.JoinConfig(jt, lidx, ridx, alg, exact=exact)

    # ------------------------------------------------------------------
    # set ops (pycylon table.pyx:411-457)
    # ------------------------------------------------------------------

    def union(self, table: "Table") -> "Table":
        return set_op(self, table, _setops.SetOp.UNION)

    def subtract(self, table: "Table") -> "Table":
        return set_op(self, table, _setops.SetOp.SUBTRACT)

    def intersect(self, table: "Table") -> "Table":
        return set_op(self, table, _setops.SetOp.INTERSECT)

    def distributed_union(self, table: "Table") -> "Table":
        from ..parallel import dist_ops

        return dist_ops.distributed_set_op(self, table, _setops.SetOp.UNION)

    def distributed_subtract(self, table: "Table") -> "Table":
        from ..parallel import dist_ops

        return dist_ops.distributed_set_op(self, table, _setops.SetOp.SUBTRACT)

    def distributed_intersect(self, table: "Table") -> "Table":
        from ..parallel import dist_ops

        return dist_ops.distributed_set_op(self, table, _setops.SetOp.INTERSECT)

    # ------------------------------------------------------------------
    # aggregates (pycylon table.pyx:485-522)
    # ------------------------------------------------------------------

    def _agg(self, column, op: str):
        i = self._col_index(column) if not isinstance(column, Column) else None
        col = self._columns[i] if i is not None else column
        refuse_planes([col], op)
        if self.row_mask is not None:
            valid = col.valid_mask() & self.emit_mask()
            col = Column(col.data, col.dtype, valid, col.dictionary, col.name,
                         varbytes=col.varbytes)
        # a sharded column's reduction already spans all shards (XLA
        # inserts the cross-chip all-reduce) — no distributed branch needed
        value = _aggregates.agg_scalar(col, op)
        return Table.from_pydict(self._ctx, {col.name: [value]})

    def sum(self, column) -> "Table":
        return self._agg(column, "sum")

    def count(self, column) -> "Table":
        return self._agg(column, "count")

    def min(self, column) -> "Table":
        return self._agg(column, "min")

    def max(self, column) -> "Table":
        return self._agg(column, "max")

    def mean(self, column) -> "Table":
        return self._agg(column, "mean")

    # ------------------------------------------------------------------
    # groupby (pycylon table.pyx:524-554)
    # ------------------------------------------------------------------

    def groupby(self, index_col: int, aggregate_cols: Sequence,
                aggregate_ops: Sequence) -> "Table":
        ops = [_as_agg_op(o) for o in aggregate_ops]
        if self._ctx.is_distributed() and self._ctx.get_world_size() > 1:
            from ..parallel import dist_ops

            return dist_ops.distributed_groupby(self, index_col,
                                                list(aggregate_cols), ops)
        return groupby_local(self, index_col, list(aggregate_cols), ops)

    # ------------------------------------------------------------------
    # pandas-style sugar (pycylon table.pyx:749-798)
    # ------------------------------------------------------------------

    def __getitem__(self, key):
        if isinstance(key, Table):  # boolean mask table
            if key.column_count != 1:
                # full-table mask: AND across columns? pycylon uses filter result
                raise CylonError(Code.Invalid, "mask table must have one column")
            mask = key._columns[0].data.astype(bool) & key.emit_mask()
            return self.filter_mask(mask)
        if isinstance(key, slice):
            return self.slice(key.start or 0,
                              key.stop if key.stop is not None else self.row_count)
        if isinstance(key, int):
            return self.slice(key, key + 1)
        if isinstance(key, str):
            return self.project([key])
        if isinstance(key, (list, tuple)):
            return self.project(list(key))
        raise CylonError(Code.Invalid, f"unsupported key {key!r}")

    def _compare(self, other, op) -> "Table":
        # keep the padded capacity + row_mask (join/dist results are padded;
        # compacting here would break t[t["c"] > x] shape alignment)
        t = self
        refuse_planes(t._columns, "compare")
        out_cols = []
        for c in t._columns:
            if c.is_varbytes:
                if isinstance(other, str):
                    if op == "eq":
                        res = c.varbytes.equals_literal(other)
                    elif op == "ne":
                        res = ~c.varbytes.equals_literal(other)
                    else:
                        raise CylonError(
                            Code.TypeError,
                            "ordering vs str needs dictionary storage")
                else:
                    raise CylonError(Code.TypeError, "string col vs non-str")
            elif c.is_string:
                if isinstance(other, str):
                    code = np.searchsorted(c.dictionary, other)
                    hit = (code < len(c.dictionary)) and \
                        c.dictionary[code] == other
                    if op == "eq":
                        res = (c.data == int(code)) if hit else \
                            jnp.zeros(len(c), bool)
                    elif op == "ne":
                        res = (c.data != int(code)) if hit else \
                            jnp.ones(len(c), bool)
                    else:
                        raise CylonError(Code.TypeError,
                                         "ordering vs str uses dictionary order")
                else:
                    raise CylonError(Code.TypeError, "string col vs non-str")
            else:
                o = other
                res = _CMP[op](c.data, o)
            if c.validity is not None:
                res = res & c.validity
            out_cols.append(Column(res, dtypes.Bool(), None, None, c.name))
        return Table(out_cols, self._ctx, t.row_mask)

    def __eq__(self, other):  # type: ignore[override]
        if isinstance(other, Table):
            return NotImplemented
        return self._compare(other, "eq")

    def __ne__(self, other):  # type: ignore[override]
        if isinstance(other, Table):
            return NotImplemented
        return self._compare(other, "ne")

    def __lt__(self, other):
        return self._compare(other, "lt")

    def __gt__(self, other):
        return self._compare(other, "gt")

    def __le__(self, other):
        return self._compare(other, "le")

    def __ge__(self, other):
        return self._compare(other, "ge")

    def __hash__(self):
        return id(self)

    def _bool_binop(self, other: "Table", fn) -> "Table":
        cols = [Column(fn(a.data.astype(bool), b.data.astype(bool)),
                       dtypes.Bool(), None, None, a.name)
                for a, b in zip(self._columns, other._columns)]
        return Table(cols, self._ctx, self.row_mask)

    def __and__(self, other: "Table") -> "Table":
        return self._bool_binop(other, jnp.logical_and)

    def __or__(self, other: "Table") -> "Table":
        return self._bool_binop(other, jnp.logical_or)

    def __invert__(self) -> "Table":
        cols = [Column(~c.data.astype(bool), dtypes.Bool(), None, None, c.name)
                for c in self._columns]
        return Table(cols, self._ctx)

    def __repr__(self) -> str:
        return f"Table({self.row_count}x{self.column_count} " \
               f"cols={self.column_names})"


_CMP = _expr.COMPARE   # op name -> elementwise compare

def _comparable_pair(a: Column, b: Column) -> None:
    """Raise unless ``a <op> b`` is a compare of two lanes of one type:
    no strings (two vocabularies' codes, or varbytes), no word planes."""
    if a.is_string or b.is_string:
        raise CylonError(
            Code.NotImplemented,
            f"compare of two columns: {a.name!r} and {b.name!r}: a "
            f"{'varbytes' if a.is_varbytes or b.is_varbytes else 'dictionary'}"
            f" string column is not compared with another column; compare "
            f"it with a literal, or join on it")
    refuse_planes([a, b], "compare of two columns")
    if a.data.dtype != b.data.dtype:
        raise CylonError(
            Code.TypeError,
            f"compare of two columns: {a.name!r} is {a.data.dtype}, "
            f"{b.name!r} is {b.data.dtype}; cast one on the host")


def compare_columns(table: Table, a: int, op: str, b: int):
    """The bool lane ``column a <op> column b`` over ``table``'s capacity
    (the plan's `ir.ColCmp`): two columns of one fixed-width type at most
    32 bits wide; false where either is null, as `Table._compare` has a
    compare with a literal."""
    ca, cb = table._columns[a], table._columns[b]
    _comparable_pair(ca, cb)
    res = _CMP[op](ca.data, cb.data)
    for c in (ca, cb):
        if c.validity is not None:
            res = res & c.validity
    return res


def _resolve_predicate(tokens, columns) -> tuple:
    """A `case_when` predicate's tokens as `ops/expr.predicate` takes
    them: a string literal against a dictionary column becomes the
    literal's code in THIS column's vocabulary (``("miss", i, op)`` where
    it has none), and what no compare is built for raises, naming the
    column. ``columns``: position -> Column (computed ones too)."""
    kind = tokens[0]
    if kind == "cmp":
        _, pos, op, value = tokens
        c = columns[pos]
        refuse_planes([c], "case_when")
        if c.is_varbytes or isinstance(value, str) != c.is_string:
            raise CylonError(
                Code.TypeError,
                f"case_when: column {c.name!r} against {value!r}: a "
                f"dictionary string column compares with a string, a "
                f"number column with a number (no varbytes)")
        if not c.is_string:
            return tokens
        if op not in ("eq", "ne"):
            raise CylonError(Code.TypeError,
                             "ordering vs str uses dictionary order")
        code = int(np.searchsorted(c.dictionary, value))
        if code < len(c.dictionary) and c.dictionary[code] == value:
            return ("cmp", pos, op, code)
        return ("miss", pos, op)
    if kind == "colcmp":
        _comparable_pair(columns[tokens[1]], columns[tokens[3]])
        return tokens
    return (kind,) + tuple(_resolve_predicate(t, columns)
                           for t in tokens[1:])


def _resolve_value(tokens, columns) -> tuple:
    if tokens[0] in ("col", "lit"):
        return tokens
    if tokens[0] == "case":
        return ("case", _resolve_predicate(tokens[1], columns))
    return (tokens[0],) + tuple(_resolve_value(t, columns)
                                for t in tokens[1:])


_JOIN_TYPES = {
    "inner": _join.JoinType.INNER,
    "left": _join.JoinType.LEFT,
    "right": _join.JoinType.RIGHT,
    "outer": _join.JoinType.FULL_OUTER,
    "full_outer": _join.JoinType.FULL_OUTER,
    "semi": _join.JoinType.SEMI,
    "anti": _join.JoinType.ANTI,
}

_JOIN_ALGOS = {"sort": _join.JoinAlgorithm.SORT,
               "hash": _join.JoinAlgorithm.HASH,
               "auto": _join.JoinAlgorithm.AUTO}


def _as_agg_op(o) -> _groupby.AggregationOp:
    if isinstance(o, _groupby.AggregationOp):
        return o
    if isinstance(o, str):
        return _groupby.AggregationOp[o.upper()]
    return _groupby.AggregationOp(int(o))


from ..util import capacity as _capacity
from ..util import pow2 as _pow2  # shared capacity-rounding policy


# ---------------------------------------------------------------------------
# Device compaction: a table whose row mask keeps few of its slots, cut to
# the capacity its live rows need, before an operator that sorts its slots
# ---------------------------------------------------------------------------

# rows a block of `stream_compact` holds here: block_rows * 128
COMPACT_BLOCK_ROWS = 256


def compact_path() -> str:
    """"stream": the Pallas kernel `stream_compact` (a TPU); "xla": one
    `nonzero` and a gather a stream (everything else: the interpreter
    would take seconds a program). By the backend alone, no knob."""
    return "stream" if jax.default_backend() == "tpu" else "xla"


@_telemetry.counted_cache
def _compact_count_program_fn():
    """The live rows of a row mask, one scalar
    (`jit_compact_count_program`)."""
    def kernel(mask):
        return mask.sum(dtype=jnp.int32)

    return jax.jit(kernel)


def _to_word(x):
    """A lane of at most 32 bits as a uint32 stream, bit for bit for a
    4-byte dtype and by value for a narrower one."""
    if x.dtype == jnp.uint32:
        return x
    if x.dtype.itemsize == 4:
        return jax.lax.bitcast_convert_type(x, jnp.uint32)
    if x.dtype == jnp.bool_:
        return x.astype(jnp.uint32)
    if jnp.issubdtype(x.dtype, jnp.floating):    # float16 / bfloat16
        return jax.lax.bitcast_convert_type(x, jnp.uint16).astype(jnp.uint32)
    return jax.lax.bitcast_convert_type(x.astype(jnp.int32), jnp.uint32)


def _from_word(w, dtype):
    dtype = jnp.dtype(dtype)
    if dtype == jnp.uint32:
        return w
    if dtype.itemsize == 4:
        return jax.lax.bitcast_convert_type(w, dtype)
    if dtype == jnp.bool_:
        return w != 0
    if jnp.issubdtype(dtype, jnp.floating):
        return jax.lax.bitcast_convert_type(w.astype(jnp.uint16), dtype)
    return jax.lax.bitcast_convert_type(w, jnp.int32).astype(dtype)


@_telemetry.counted_cache
def _compact_program_fn(cap: int, path: str, interpret: bool = False):
    """The live rows of every array (rows on the last axis: a lane
    ``[n]``, 8 bytes wide only with x64 on, or an int64's word planes
    ``uint32[2, n]``) and of every validity mask, in row order in the
    first slots of ``cap``, and the row mask of the cut table: ONE program
    (`jit_compact_program`). The masks ride 32 to a stream; a plane-held
    or 8-byte column is two streams."""
    def kernel(mask, arrays, valids):
        streams = []
        for a in arrays:
            if a.ndim == 2:
                streams += [a[0], a[1]]
            elif a.dtype.itemsize == 8:     # a native 64-bit lane (x64 on)
                words = jax.lax.bitcast_convert_type(a, jnp.uint32)
                streams += [words[:, 0], words[:, 1]]
            else:
                streams.append(_to_word(a))
        for lo in range(0, len(valids), 32):
            word = jnp.zeros(mask.shape, jnp.uint32)
            for bit, v in enumerate(valids[lo:lo + 32]):
                word = word | (v.astype(jnp.uint32) << np.uint32(bit))
            streams.append(word)
        if path == "stream":
            outs, count = _kernels.stream_compact(
                mask, streams, block_rows=COMPACT_BLOCK_ROWS,
                interpret=interpret, out_elems=cap)
        else:
            idx = jnp.nonzero(mask, size=cap, fill_value=0)[0]
            outs = [jnp.take(s, idx) for s in streams]
            count = mask.sum(dtype=jnp.int32)
        outs = list(outs)
        new_arrays = []
        for a in arrays:
            if a.ndim == 2:
                new_arrays.append(jnp.stack([outs.pop(0), outs.pop(0)]))
            elif a.dtype.itemsize == 8:
                new_arrays.append(jax.lax.bitcast_convert_type(
                    jnp.stack([outs.pop(0), outs.pop(0)], axis=1), a.dtype))
            else:
                new_arrays.append(_from_word(outs.pop(0), a.dtype))
        new_valids = [(outs[j // 32] >> np.uint32(j % 32)) & np.uint32(1) != 0
                      for j in range(len(valids))]
        live = jnp.arange(cap, dtype=jnp.int32) < count
        return new_arrays, new_valids, live

    return jax.jit(kernel)


def compact_streams(columns: Sequence[Column]) -> int:
    """The 32-bit streams `compact_live` moves for ``columns``."""
    masks = sum(c.validity is not None for c in columns)
    return sum(2 if c.is_planes or c.data.dtype.itemsize == 8 else 1
               for c in columns) + -(-masks // 32)


def compaction_pays(cap: int, slots: int) -> bool:
    """Whether cutting a table of ``slots`` slots to ``cap`` saves the
    sort behind it more than the pass costs: where at least an EIGHTH of
    the slots go. Measured on the chip (PERF.md section 6, PR 50: one
    uint32 stream of 75,000,000 rows compacted and the semi join's three
    operands sorted at the cut size, against the sort of every slot): a
    slot costs the join's sort and plan pass 4-5.5 ns, a row costs the
    compaction 0.18 ns whatever the density, so the pair still won by
    3.4% of the sort at 95% alive and by 7% at 87.5%; an eighth is where
    the win stops being small, with room for the 6.25% that ``cap`` may
    lie over the live rows. A constant derived from that measurement,
    decided by the fetched count alone."""
    return 8 * cap < 7 * slots


def compact_live(table: Table):
    """(``table`` cut to the capacity its live rows need, or ``table``
    itself; what was done, for the caller's span). A table with a row
    mask has its live rows counted (ONE fetch, ``sync.compact.count``,
    memoised on the mask's buffer as the join's count fetch is on its
    inputs'). The capacity they need is `util.capacity`'s (the
    16-an-octave grid); where `compaction_pays` (at least an eighth of
    the slots go) ONE device program moves the live rows of every
    column, in row order, to the first slots of that capacity
    (`_compact_program_fn`: no index array leaves or reaches the host),
    and the result's row mask is the prefix of live rows. Else (no row
    mask, too few dead slots, a varbytes column, a table sharded over
    several devices) the table is handed back as it is: every operator
    honours a row mask."""
    from ..parallel.shuffle import _count_cached

    info = {"rows_in": table.capacity, "compacted": False}
    mask = table.row_mask
    if mask is None or not table._columns \
            or any(c.is_varbytes for c in table._columns) \
            or len(table._columns[0].data.sharding.device_set) != 1:
        return table, info
    count = _count_cached(
        ("compact_count", id(mask)), (mask,),
        lambda: int(_telemetry.host_fetch("compact.count",
                                          _compact_count_program_fn()(mask))))
    table._row_count_cache = count
    # util.capacity's grid (at most 6.25% over, 16 shapes an octave), not
    # bucket_cap's octave: the join behind the cut sorts every SLOT it is
    # handed and its own programs are keyed on raw capacities already.
    # THE one count that reaches a program's cache key this way
    # (analysis/specialization.FINE_KEYED_FACTORY_PARAMS, docs/analysis.md)
    cap = _capacity(count)
    info.update(rows_out=count, capacity=cap)
    if not compaction_pays(cap, table.capacity):
        return table, info
    cols = table._columns
    nullable = [c for c in cols if c.validity is not None]
    arrays, valids, live = _compact_program_fn(cap, compact_path())(
        mask, [c.data for c in cols], [c.validity for c in nullable])
    valids = iter(valids)
    out = Table([Column(a, c.dtype,
                        None if c.validity is None else next(valids),
                        c.dictionary, c.name)
                 for a, c in zip(arrays, cols)], table._ctx, live)
    out._row_count_cache = count
    out._hash_partitioned = table._hash_partitioned
    out._key_ordered = table._key_ordered
    streams = compact_streams(cols)
    info.update(compacted=True, streams=streams)
    _telemetry.counter("cylon_compact_rows_in_total").inc(table.capacity)
    _telemetry.counter("cylon_compact_rows_out_total").inc(count)
    _telemetry.counter("cylon_compact_slots_out_total").inc(cap)
    _telemetry.counter("cylon_compact_streams_total").inc(streams)
    return out, info


def _sort_keys_mixed(cols: Sequence[Column], asc: Sequence[bool]):
    """Sort keys for a mix of plain and varbytes columns. Varbytes sort
    lexicographically via big-endian prefix words + length (exact up to
    strings.SORT_PREFIX_WORDS*4 bytes; longer → None, host fallback)."""
    keys = []
    for c, a in zip(cols, asc):
        if c.is_varbytes:
            if not c.varbytes.sortable_on_device:
                return None
            ks = c.varbytes.sort_prefix_keys()
            if not a:
                ks = [k ^ jnp.uint32(0xFFFFFFFF) for k in ks]
            if c.validity is not None:
                # nulls last: extreme on every prefix key
                ext = jnp.uint32(0xFFFFFFFF)
                ks = [jnp.where(c.validity, k, ext) for k in ks]
            keys.extend(ks)
        else:
            keys.extend(_order.sort_keys([c], [a]))
    return keys


def _host_sort_perm(cols: Sequence[Column], asc: Sequence[bool]):
    """Host lexsort fallback for varbytes rows past the device prefix
    bound (>64-byte strings): decode only the SORT columns."""
    import pandas as pd

    df = pd.DataFrame({str(i): c.to_numpy() for i, c in enumerate(cols)})
    perm = df.sort_values(by=[str(i) for i in range(len(cols))],
                          ascending=list(asc), kind="stable").index.to_numpy()
    return jnp.asarray(perm.astype(np.int32))


def _resolve_join_columns(left: Table, right: Table, kwargs
                          ) -> Tuple[List[int], List[int]]:
    """pycylon's on=/left_on=/right_on= resolution (table.pyx:228-266)."""
    on = kwargs.get("on")
    left_on = kwargs.get("left_on")
    right_on = kwargs.get("right_on")
    if on is not None:
        names = on if isinstance(on, (list, tuple)) else [on]
        li = [left._col_index(c) for c in names]
        ri = [right._col_index(c) for c in names]
        return li, ri
    if left_on is not None and right_on is not None:
        lo = left_on if isinstance(left_on, (list, tuple)) else [left_on]
        ro = right_on if isinstance(right_on, (list, tuple)) else [right_on]
        return ([left._col_index(c) for c in lo],
                [right._col_index(c) for c in ro])
    raise CylonError(Code.Invalid,
                     "kwargs 'on' or 'left_on' and 'right_on' must be provided")


# ---------------------------------------------------------------------------
# Key preparation shared by join/set ops/shuffle
# ---------------------------------------------------------------------------

def align_key_columns(left: Table, right: Table, lidx: List[int],
                      ridx: List[int]) -> Tuple[List[Column], List[Column]]:
    """Promote dtypes / unify string vocabularies so both sides' key columns
    are directly comparable on device."""
    lcols, rcols = [], []
    for li, ri in zip(lidx, ridx):
        a, b = left._columns[li], right._columns[ri]
        if a.is_string != b.is_string:
            raise CylonError(Code.TypeError,
                             f"join key type mismatch: {a.name} vs {b.name}")
        if a.is_string:
            a, b = align_string_columns(a, b)
        elif a.is_planes or b.is_planes:
            # word planes compare as they are: no promotion between them
            if a.dtype.np_dtype != b.dtype.np_dtype:
                raise CylonError(
                    Code.NotImplemented,
                    f"join key type mismatch: {a.name} is "
                    f"{a.dtype.type.name}, {b.name} is {b.dtype.type.name}, "
                    f"and a 64-bit column held as word planes (x64 is off) "
                    f"is not promoted; cast on the host")
        elif a.data.dtype != b.data.dtype:
            common = jnp.promote_types(a.data.dtype, b.data.dtype)
            a = Column(a.data.astype(common), a.dtype, a.validity, None, a.name)
            b = Column(b.data.astype(common), b.dtype, b.validity, None, b.name)
        lcols.append(a)
        rcols.append(b)
    return lcols, rcols


def sole_key_index(aligned: Sequence[Column], source: Sequence[Column],
                   idx: Sequence[int]) -> Optional[int]:
    """The column of ``source`` that is the join's ONE key AND the very
    array the plan's key bits are made from, else None: what
    `ops/join.plan_lane_descs` needs to let the key ride its sort once.
    An aligned key column that is a promoted copy, a re-coded dictionary
    or a varbytes lift is another array than its source, and a string's
    bits are codes, not values."""
    if len(idx) != 1:
        return None
    a, c = aligned[0], source[idx[0]]
    if a.is_string or a.data is not c.data:
        return None
    return idx[0]


def _all_valid(cols: Sequence[Column]) -> jnp.ndarray:
    v = cols[0].valid_mask()
    for c in cols[1:]:
        v = v & c.valid_mask()
    return v


def row_gids(left: Table, right: Table) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Shared dense FULL-ROW ids for set ops; nulls compare equal (validity
    participates in the key, matching set-distinct semantics)."""
    if left.column_count != right.column_count:
        raise CylonError(Code.Invalid, "set ops need equal schemas")
    lidx = list(range(left.column_count))
    lcols, rcols = align_key_columns(left, right, lidx, lidx)
    keys_l, keys_r = [], []
    for a, b in zip(lcols, rcols):
        if a.is_varbytes:
            kw = pair_k_words(a, b)
            ka, _va, _fa = string_key_arrays(a, kw)
            kb, _vb, _fb = string_key_arrays(b, kw)
            keys_l.extend(ka)
            keys_r.extend(kb)
        else:
            keys_l.append(_order.sort_keys([a])[0])
            keys_r.append(_order.sort_keys([b])[0])
        if a.validity is not None or b.validity is not None:
            keys_l.append(a.valid_mask().astype(jnp.uint8))
            keys_r.append(b.valid_mask().astype(jnp.uint8))
    return _order.dense_ranks_two(keys_l, keys_r)


# ---------------------------------------------------------------------------
# Free-function operator API (reference: table.hpp:228-387)
# ---------------------------------------------------------------------------

def _expanded_keys(cols: Sequence[Column], paired: Sequence[Column] = None):
    """Key arrays for join/groupby kernels: one array per plain column;
    varbytes columns expand to raw word lanes (short rows, byte-exact)
    or (h1, h2, h3, len) content hashes (long rows) — data/strings.py.
    ``paired``: the other side's aligned key columns, so both sides
    emit the same lane count (max of the two max_words). The fourth
    tuple names, for a key held as word planes, its logical 64-bit dtype
    (else None): ops/join.plan_program's ``key_wide``."""
    keys, valids, flags, wide = [], [], [], []
    for j, c in enumerate(cols):
        if c.is_varbytes:
            kw = pair_k_words(c, paired[j]) if paired is not None else None
            ks, vs, fs = string_key_arrays(c, kw)
            keys.extend(ks)
            valids.extend(vs)
            flags.extend(fs)
            wide.extend([None] * len(ks))
        else:
            keys.append(c.data)
            valids.append(c.validity)
            flags.append(c.is_string)
            wide.append(c.host_dtype.name if c.is_planes else None)
    return tuple(keys), tuple(valids), tuple(flags), tuple(wide)


def _memo_refs(cols: Sequence[Column]) -> Tuple[Tuple, Tuple]:
    """(id-key, liveness refs) over EVERY buffer a count result depends
    on: data, validity, and varbytes words/starts (ADVICE r5 low —
    keying on id(data) alone would return stale counts for a column
    sharing a data buffer with different validity or string content,
    and weakref-anchoring only data would let a recycled id alias a
    dead entry). Shared by the join count memos here and the splitter
    memo in parallel/dist_ops."""
    ids, refs = [], []
    for c in cols:
        bufs = [c.data]
        if c.validity is not None:
            bufs.append(c.validity)
        if c.is_varbytes:
            bufs.append(c.varbytes.words)
            bufs.append(c.varbytes.starts)
        for b in bufs:
            ids.append(id(b))
            refs.append(b)
    return tuple(ids), tuple(refs)


def join(left: Table, right: Table, config: _join.JoinConfig) -> Table:
    """Local join (reference: cylon::Join, table.cpp:640-654). Exactly TWO
    compiled programs (count, then materialize) — only the 4 output-count
    scalars touch the host; the result keeps pow2 capacity with padding
    rows masked via row_mask. Varbytes key columns join on their
    content-hash identity; varbytes payload columns are re-gathered by
    the materialized row indices (one varlen gather per column).

    Working sets beyond HBM: when the estimated plan memory exceeds the
    pool's headroom, the probe side is processed in blocks
    (``join_blocked``); `Table.join(probe_block_rows=...)` forces it."""
    est = _join_plan_bytes_estimate(left, right)
    avail = left._ctx.memory_pool.available_bytes()
    probe_cap = right.capacity if config.type == _join.JoinType.RIGHT \
        else left.capacity
    if avail and est > avail // 2 and probe_cap > (1 << 20):
        blk = max((1 << 20),
                  probe_cap // max(2 * est // max(avail, 1), 2))
        return join_blocked(left, right, config, int(blk))
    return _join_once(left, right, config)


def _join_plan_bytes_estimate(left: Table, right: Table) -> int:
    """Rough plan+materialize working-set bytes: sort operands + payload
    gathers, ~6 u32-equivalents per row per column-ish; varbytes columns
    add their word-buffer bytes (the content dominates large strings)."""
    n = left.capacity + right.capacity
    width = sum((8 if c.is_planes
                 else max(np.dtype(c.data.dtype).itemsize, 4)) + 1
                for c in left._columns + right._columns)
    vb_bytes = sum(4 * int(c.varbytes.words.shape[0])
                   for c in left._columns + right._columns
                   if c.is_varbytes)
    return int(n) * (width + 24) + 2 * vb_bytes


def count_plan_sort(keys, str_flags, n_cols: int, a_desc=None, b_desc=None,
                    hash_mode: bool = False,
                    block_rows: Optional[int] = None, rows: int = 0) -> None:
    """Count what the host hands a join's plan sort, what the join will
    gather and what its expand kernel sweeps, once a join, where the
    operand list is built (the local join here,
    `parallel/dist_ops.distributed_join`): the four ``cylon_join_*``
    counters of docs/telemetry.md and the slots of both sides that sort
    was handed (``rows``, `cylon_join_plan_sort_rows_total`: dead slots
    are sorted as live ones are). ``keys``: one side's key arrays;
    ``n_cols``: the input columns of both sides; the lane descriptors
    and the kernels' ``block_rows`` only on the stream path."""
    _telemetry.counter("cylon_join_plan_sort_rows_total").inc(rows)
    _telemetry.counter("cylon_join_sort_operands_total").inc(
        _join.plan_sort_operand_count(keys, str_flags, a_desc, b_desc,
                                      hash_mode))
    _telemetry.counter("cylon_join_key_lanes_total").inc(
        _join.plan_key_lane_count(keys, str_flags))
    _telemetry.counter("cylon_join_gathered_columns_total").inc(
        _join.plan_gathered_column_count(n_cols, a_desc, b_desc))
    _telemetry.counter("cylon_join_expand_sweep_rows_total").inc(
        _join.expand_sweep_rows(block_rows))


def _stream_route(alg, lkeys, rkeys, str_flags, join_type) -> Optional[str]:
    """The path a local join takes for its algorithm hint and key arrays:
    "sort" (the sort-stream path: one 4-byte or plane-held key), "hash"
    (the hash-stream path: several key columns, wide keys) or None (the
    XLA plan)."""
    if alg != _join.JoinAlgorithm.HASH and _join.stream_plan_applicable(
            lkeys, rkeys, str_flags, join_type):
        return "sort"
    if alg in (_join.JoinAlgorithm.HASH, _join.JoinAlgorithm.AUTO) \
            and _join.hash_stream_applicable(lkeys, rkeys, str_flags,
                                             join_type):
        return "hash"
    return None


def _semi_join_once(left: Table, right: Table,
                    config: _join.JoinConfig) -> Table:
    """The local SEMI / ANTI join (`ops/join.JoinType`): the left rows
    with (without) a live right row of an equal, non-null key, each once,
    the left's columns only. ONE device program and no second phase:

    * sort path (one 4-byte or plane-held key) and hash path (several
      key columns, wide keys): the stream plan program in its semi form
      (`ops/join._plan_program_stream_impl`): the right side rides the
      sort with its key and tag alone, the plan pass keeps each probe
      row at most once and compacts the kept rows' lanes in key order;
      the result has the LEFT side's capacity and a prefix row mask
      decided on the device. No expand kernel, and on the sort path no
      fetch at all; the hash path fetches its counts for the collision
      check, and recomputes exactly on a collision;
    * XLA path (everything else): `ops/join.semi_plan_program` gives the
      left table's new row mask in its own row order; the result is the
      left table under it, with no gather."""
    from ..data.strings import EXACT_KEY_WORDS

    jt = config.type
    if config.exact and any(
            a.is_varbytes and b.is_varbytes
            and pair_k_words(a, b) > EXACT_KEY_WORDS
            for a, b in ((left._columns[li], right._columns[rj])
                         for li, rj in zip(config.left_column_idx,
                                           config.right_column_idx))):
        # a long varbytes key joins on its content hash; no matched pair
        # comes out of a semi join to verify afterwards, so exact=True
        # joins on shared dictionary codes from the start
        return _exact_dict_fallback_join(left, right, config)
    lcols, rcols = align_key_columns(left, right, config.left_column_idx,
                                     config.right_column_idx)
    lkeys, lkvalid, str_flags, key_wide = _expanded_keys(lcols, rcols)
    rkeys, rkvalid, _, _ = _expanded_keys(rcols, lcols)
    lemit, remit = left.row_mask, right.row_mask
    ldat = tuple(c.data for c in left._columns)
    lval = tuple(c.validity for c in left._columns)
    seq = left._ctx.get_next_sequence()
    route = _stream_route(config.algorithm, lkeys, rkeys, str_flags, jt)
    _telemetry.counter("cylon_join_semi_total",
                       {"kind": jt.name.lower()}).inc()
    rows = left.capacity + right.capacity

    def _stream(hash_mode: bool):
        interp = jax.default_backend() != "tpu"
        wide_key = None if hash_mode else key_wide[0]
        lkey = None if hash_mode else sole_key_index(
            lcols, left._columns, config.left_column_idx)
        a_desc, b_desc = _join.plan_lane_descs(ldat, lval, (), (), jt,
                                               lkey, None, wide_key)
        br = _join.stream_block_rows(lkeys[0].shape[-1], rkeys[0].shape[-1])
        count_plan_sort(lkeys, str_flags, len(ldat), a_desc, b_desc,
                        hash_mode, rows=rows)
        with _telemetry.phase("join.semi", seq):
            counts, lod, lov, emit, lidx = _join.plan_program_stream(
                lkeys, lkvalid, lemit, rkeys, rkvalid, remit,
                ldat, lval, (), (), str_flags, jt,
                a_desc=a_desc, b_desc=b_desc, block_rows=br,
                hash_mode=hash_mode, interpret=interp, wide_key=wide_key)
            if hash_mode and int(_telemetry.host_fetch(
                    "join.count", counts)[3]) > 0:
                return None  # hash collision: recompute exactly
        cols = []
        for i, (d, v, c) in enumerate(zip(lod, lov, left._columns)):
            v = None if c.validity is None else v
            if c.is_varbytes:
                vb = c.varbytes.take(lidx)
                cols.append(Column(vb.lengths, c.dtype, v, None, f"lt-{i}",
                                   varbytes=vb))
            else:
                cols.append(Column(d, c.dtype, v, c.dictionary, f"lt-{i}"))
        return Table(cols, left._ctx, emit)

    out = _stream(hash_mode=route == "hash") if route else None
    if out is None:
        count_plan_sort(lkeys, str_flags, 0, rows=rows)
        with _telemetry.phase("join.semi", seq):
            keep = _join.semi_plan_program(
                lkeys, lkvalid, lemit, rkeys, rkvalid, remit, str_flags, jt,
                key_wide=key_wide)
        out = Table([c.rename(f"lt-{i}")
                     for i, c in enumerate(left._columns)], left._ctx, keep)
        out._key_ordered = left._key_ordered
    return out


def _join_once(left: Table, right: Table, config: _join.JoinConfig) -> Table:
    from ..data.strings import EXACT_KEY_WORDS, LANE_WORDS_MAX, VarBytes

    if _join.is_semi(config.type):
        return _semi_join_once(left, right, config)
    lcols, rcols = align_key_columns(left, right, config.left_column_idx,
                                     config.right_column_idx)
    # varbytes alignment may have lifted a dictionary key column: joins
    # read keys from the ALIGNED columns, payload from the originals
    lkeys, lkvalid, str_flags, key_wide = _expanded_keys(lcols, rcols)
    rkeys, rkvalid, _, _ = _expanded_keys(rcols, lcols)
    lemit, remit = left.row_mask, right.row_mask
    planes = any(c.is_planes for c in left._columns + right._columns)
    if planes and config.type == _join.JoinType.FULL_OUTER:
        # its unmatched-build tail goes through set membership and concat
        refuse_planes(left._columns + right._columns, "full outer join")

    lvb = [i for i, c in enumerate(left._columns) if c.is_varbytes]
    rvb = [i for i, c in enumerate(right._columns) if c.is_varbytes]
    # INNER joins on byte-exact (word-lane) string keys emit identical
    # bytes for both key columns — the right key's output aliases the
    # left's, skipping its lanes and its materialization entirely
    alias_rkeys = {}
    if config.type == _join.JoinType.INNER:
        for li, rj in zip(config.left_column_idx, config.right_column_idx):
            a, b = left._columns[li], right._columns[rj]
            if a.is_varbytes and b.is_varbytes:
                kp = max(a.varbytes.max_words, b.varbytes.max_words)
                if kp <= EXACT_KEY_WORDS:
                    alias_rkeys[rj] = li
    # short varbytes columns ride the materialize as fixed u32 word
    # lanes appended after the real columns (output = strided layout,
    # no varlen gather at all); long ones re-gather via VarBytes.take
    lvb_fast = [i for i in lvb
                if left._columns[i].varbytes.max_words <= LANE_WORDS_MAX]
    rvb_fast = [j for j in rvb
                if right._columns[j].varbytes.max_words <= LANE_WORDS_MAX
                and j not in alias_rkeys]
    ldat = tuple(c.data for c in left._columns)
    lval = tuple(c.validity for c in left._columns)
    rdat = tuple(c.data for c in right._columns)
    rval = tuple(c.validity for c in right._columns)
    l_lane_slots, r_lane_slots = {}, {}
    for i in lvb_fast:
        vb = left._columns[i].varbytes
        l_lane_slots[i] = (len(ldat), vb.max_words)
        ldat = ldat + tuple(vb.word_lanes())
        lval = lval + (None,) * vb.max_words
    for j in rvb_fast:
        vb = right._columns[j].varbytes
        r_lane_slots[j] = (len(rdat), vb.max_words)
        rdat = rdat + tuple(vb.word_lanes())
        rval = rval + (None,) * vb.max_words

    seq = left._ctx.get_next_sequence()
    # route: the sort-stream path handles single 4-byte keys; the
    # hash-stream path (JoinAlgorithm.HASH — reference hash join,
    # arrow_hash_kernels.hpp:48-225) covers multi-column/wide keys by
    # sorting a 2x32-bit row hash with exact collision fallback.
    # FULL_OUTER streams as LEFT + one unmatched-build membership tail
    # (_append_unmatched_right); the XLA plan remains the general
    # fallback (forced algorithms, collisions, non-streamable shapes).
    alg = config.algorithm
    if config.type == _join.JoinType.FULL_OUTER and \
            (_join.stream_plan_applicable(lkeys, rkeys, str_flags,
                                          _join.JoinType.LEFT)
             or _join.hash_stream_applicable(lkeys, rkeys, str_flags,
                                             _join.JoinType.LEFT)):
        sub = _join.JoinConfig(_join.JoinType.LEFT,
                               config.left_column_idx,
                               config.right_column_idx, alg,
                               exact=config.exact)
        out = _join_once(left, right, sub)
        return _append_unmatched_right(left, right, config, out,
                                       aligned=(lcols, rcols))
    route = _stream_route(alg, lkeys, rkeys, str_flags, config.type)

    def _stream_join(hash_mode: bool):
        from ..parallel.shuffle import _count_cached

        interp = jax.default_backend() != "tpu"
        # the sort path admits ONE key held as word planes (the hash path
        # none): its logical dtype, by name
        wide_key = None if hash_mode else key_wide[0]
        # the sort path's key bits are the key column's own: it rides once
        lkey, rkey = (None, None) if hash_mode else (
            sole_key_index(lcols, left._columns, config.left_column_idx),
            sole_key_index(rcols, right._columns, config.right_column_idx))
        a_desc, b_desc = _join.plan_lane_descs(ldat, lval, rdat, rval,
                                               config.type, lkey, rkey,
                                               wide_key)
        br = _join.stream_block_rows(lkeys[0].shape[-1], rkeys[0].shape[-1])
        count_plan_sort(lkeys, str_flags, len(ldat) + len(rdat), a_desc,
                        b_desc, hash_mode, br,
                        rows=left.capacity + right.capacity)
        with _telemetry.phase("join.plan", seq):
            counts, a_streams, b_streams = _join.plan_program_stream(
                lkeys, lkvalid, lemit, rkeys, rkvalid, remit,
                ldat, lval, rdat, rval, str_flags, config.type,
                a_desc=a_desc, b_desc=b_desc, block_rows=br,
                hash_mode=hash_mode, interpret=interp, wide_key=wide_key)
            # the COUNT FETCH memoizes on the source buffers (weakref
            # identity — jax arrays are immutable): repeat joins of the
            # same tables skip this ~100 ms host sync; the device
            # `counts` still feeds materialize either way
            lids, lrefs = _memo_refs(lcols)
            rids, rrefs = _memo_refs(rcols)
            ck = ("join_counts", int(config.type), bool(hash_mode),
                  tuple(config.left_column_idx),
                  tuple(config.right_column_idx),
                  lids, rids, id(lemit), id(remit))
            refs = lrefs + rrefs \
                + tuple(x for x in (lemit, remit) if x is not None)
            host_counts = _count_cached(
                ck, refs,
                lambda: _telemetry.host_fetch("join.count", counts))
            n_primary = int(host_counts[0])
        if hash_mode and int(host_counts[3]) > 0:
            return None  # hash collision — caller recomputes exactly
        if n_primary < 0:
            raise CylonError(Code.ExecutionError,
                             "join output exceeds 2^31 rows per shard; "
                             "repartition over more shards")
        cap_e = _join.stream_expand_capacity(n_primary, br)
        with _telemetry.phase("join.materialize", seq):
            return _join.materialize_program_stream(
                counts, a_streams, b_streams,
                ldat, lval, rdat, rval, config.type, cap_e,
                a_desc=a_desc, b_desc=b_desc, block_rows=br,
                interpret=interp, wide_key=wide_key), n_primary

    res = _stream_join(hash_mode=route == "hash") if route else None
    if res is not None:
        (lod, lov, rod, rov, emit, lidx, ridx), n_primary = res
    else:
        from ..parallel.shuffle import _count_cached

        count_plan_sort(lkeys, str_flags, len(ldat) + len(rdat),
                        rows=left.capacity + right.capacity)
        with _telemetry.phase("join.plan", seq):
            counts2, lo, m, bperm, un_mask = _join.plan_program(
                lkeys, lkvalid, lemit, rkeys, rkvalid, remit, str_flags,
                config.type, key_wide=key_wide)
            # same memoization as the stream path: repeat joins of the
            # same tables skip the count host sync
            lids, lrefs = _memo_refs(lcols)
            rids, rrefs = _memo_refs(rcols)
            ck = ("join_counts_xla", int(config.type),
                  tuple(config.left_column_idx),
                  tuple(config.right_column_idx),
                  lids, rids, id(lemit), id(remit))
            refs = lrefs + rrefs \
                + tuple(x for x in (lemit, remit) if x is not None)
            n_primary, n_un = (int(v) for v in _count_cached(
                ck, refs,
                lambda: _telemetry.host_fetch("join.count", counts2)))
        cap_p = _capacity(n_primary)
        cap_u = _capacity(n_un) \
            if config.type == _join.JoinType.FULL_OUTER else 0
        aemit = remit if config.type == _join.JoinType.RIGHT else lemit

        with _telemetry.phase("join.materialize", seq):
            lod, lov, rod, rov, emit, lidx, ridx = _join.materialize_program(
                lo, m, bperm, un_mask, aemit,
                ldat, lval, rdat, rval, config.type, cap_p, cap_u)

    nl = left.column_count
    cols = [Column(d, c.dtype, v, c.dictionary, f"lt-{i}")
            for i, (d, v, c) in enumerate(zip(lod, lov, left._columns))]
    cols += [Column(d, c.dtype, v, c.dictionary, f"rt-{nl + j}")
             for j, (d, v, c) in enumerate(zip(rod, rov, right._columns))]

    def lane_vb(od, slots, col_i, idx):
        off, k = slots[col_i]
        # miss/dead rows carry garbage lane values and lengths — zero
        # the lengths so the strided gap-zero/read-range invariants hold
        lens = jnp.where(idx >= 0, od[col_i], 0)
        return VarBytes.from_lanes([od[off + q] for q in range(k)], lens)

    for i in lvb:
        if i in l_lane_slots:
            vb = lane_vb(lod, l_lane_slots, i, lidx)
        else:
            vb = left._columns[i].varbytes.take(lidx)
        cols[i] = Column(vb.lengths, left._columns[i].dtype, cols[i].validity,
                         None, cols[i].name, varbytes=vb)
    for j in rvb:
        if j in alias_rkeys:
            src = cols[alias_rkeys[j]]
            cols[nl + j] = Column(src.data, right._columns[j].dtype,
                                  cols[nl + j].validity, None,
                                  cols[nl + j].name, varbytes=src.varbytes)
            continue
        if j in r_lane_slots:
            vb = lane_vb(rod, r_lane_slots, j, ridx)
        else:
            vb = right._columns[j].varbytes.take(ridx)
        cols[nl + j] = Column(vb.lengths, right._columns[j].dtype,
                              cols[nl + j].validity, None, cols[nl + j].name,
                              varbytes=vb)
    if config.exact:
        emit, collided = _exact_verify_keys(config, lcols, rcols,
                                            lidx, ridx, emit)
        if collided:
            # non-INNER collision: rows would need reclassification as
            # unmatched (and FULL_OUTER would need appended rows) —
            # redo the join on exact shared-vocabulary dictionary codes
            return _exact_dict_fallback_join(left, right, config)
    if planes and not (lvb or rvb or config.exact):
        # INNER/LEFT/RIGHT: the live rows are the dense prefix [0, n) of
        # the capacity. Cut a result that holds word planes to it, so
        # that its row mask is None: a consumer that indexes a column's
        # array by the row mask (boolean indexing on the host) cannot do
        # that to a [2, n] array. One small program a distinct n.
        dat, val = _join_prefix_program(
            [c.data for c in cols], [c.validity for c in cols],
            n=n_primary)
        return Table([Column(d, c.dtype, v, c.dictionary, c.name)
                      for d, v, c in zip(dat, val, cols)], left._ctx)
    return Table(cols, left._ctx, emit)


@partial(jax.jit, static_argnames=("n",))
def _join_prefix_program(dat, val, n: int):
    """The first ``n`` rows of every array (rows are the last axis)."""
    return jax.tree.map(lambda a: a[..., :n], (dat, val))


def _exact_verify_keys(config, lcols, rcols, lidx, ridx, emit):
    """Opt-in byte verification of hash-identified varbytes join keys
    (VERDICT r03 #4). Short keys are byte-exact by construction; long
    keys join on the 96-bit content hash, so exact=True re-checks true
    bytes after the match, the way the reference's hash-join kernel
    re-checks true keys (arrow_hash_kernels.hpp:110-185). INNER joins
    filter collision rows out of the output; for outer joins a detected
    collision returns ``collided=True`` and the caller redoes the join
    on dictionary codes (exact by construction) — never raises
    (round-5: VERDICT r04 #8 closed the raise carve-out)."""
    from ..data.strings import EXACT_KEY_WORDS

    for a, b in zip(lcols, rcols):
        if not (a.is_varbytes and b.is_varbytes):
            continue
        if pair_k_words(a, b) <= EXACT_KEY_WORDS:
            continue  # word-lane keys: already byte-exact
        eq = a.varbytes.take(lidx).equals_rows(b.varbytes.take(ridx))
        matched = (lidx >= 0) & (ridx >= 0)
        if config.type == _join.JoinType.INNER:
            emit = emit & (~matched | eq)
            continue
        if bool(_telemetry.host_fetch(
                "join.exact_verify", (emit & matched & ~eq).any())):
            return emit, True
    return emit, False


def _exact_dict_fallback_join(left: Table, right: Table,
                              config: _join.JoinConfig) -> Table:
    """Collision recovery for exact outer joins on long varbytes keys:
    re-encode each long key pair as dictionary columns over ONE shared
    sorted vocabulary (a host round trip — paid only when a 96-bit
    content-hash collision was actually detected, i.e. ~never), then
    redo the join on the int32 codes, which are exact by construction.
    Unmatched-row reclassification and FULL_OUTER appends come out right
    because the join itself now runs on collision-free keys. Reference
    bar: arrow_hash_kernels.hpp:110-185 verifies true keys inline."""
    from ..data.strings import EXACT_KEY_WORDS

    lcols2 = list(left._columns)
    rcols2 = list(right._columns)
    for li, rj in zip(config.left_column_idx, config.right_column_idx):
        a, b = left._columns[li], right._columns[rj]
        if not (a.is_varbytes and b.is_varbytes):
            continue
        if pair_k_words(a, b) <= EXACT_KEY_WORDS:
            continue
        lcols2[li], rcols2[rj] = _dict_encode_pair(a, b)
    cfg = _join.JoinConfig(config.type, config.left_column_idx,
                           config.right_column_idx, config.algorithm,
                           exact=False)
    return _join_once(Table(lcols2, left._ctx, left.row_mask),
                      Table(rcols2, right._ctx, right.row_mask), cfg)


def _dict_encode_pair(a: Column, b: Column) -> Tuple[Column, Column]:
    """Re-encode two varbytes key columns as dictionary columns over ONE
    shared sorted vocabulary — codes then compare exactly (collision
    recovery for exact=True; shared by the local and distributed
    fallbacks). Host round trip by design: only runs after an actual
    detected hash collision."""
    filler = b"" if a.dtype.type == dtypes.Type.BINARY else ""

    def _safe_host(c):
        return np.array([filler if v is None else v for v in c.to_numpy()],
                        dtype=object)

    sa, sb = _safe_host(a), _safe_host(b)
    vocab = np.unique(np.concatenate([sa, sb]))
    return (
        Column(jnp.asarray(np.searchsorted(vocab, sa).astype(np.int32)),
               a.dtype, a.validity, vocab, a.name),
        Column(jnp.asarray(np.searchsorted(vocab, sb).astype(np.int32)),
               b.dtype, b.validity, vocab, b.name),
    )


def join_blocked(left: Table, right: Table, config: _join.JoinConfig,
                 probe_block_rows: int) -> Table:
    """Chunked local join for working sets beyond HBM (SURVEY §5.7; the
    reference's analog is incremental buffer-at-a-time serialization,
    arrow_all_to_all.cpp:83-135): the PROBE side (left; right for RIGHT
    joins) is processed in row blocks of ``probe_block_rows``, each block
    joined against the resident build side at bounded capacity, results
    concatenated. Peak device memory ≈ build side + one block's join,
    instead of the full probe×build plan.

    FULL_OUTER runs blocked LEFT plus ONE key-membership pass that
    appends build rows whose key matches no probe row (keys-only memory,
    no payload blowup)."""
    jt = config.type
    if jt == _join.JoinType.RIGHT:
        probe, other = right, left
    else:
        probe, other = left, right
    n = probe.capacity
    blocks = []
    sub_type = _join.JoinType.LEFT if jt == _join.JoinType.FULL_OUTER \
        else jt
    for lo in range(0, max(n, 1), probe_block_rows):
        blk = probe.slice(lo, min(lo + probe_block_rows, n)) \
            if probe.row_mask is None else Table(
                [c.slice(lo, min(lo + probe_block_rows, n))
                 for c in probe._columns], probe._ctx,
                probe.row_mask[lo:min(lo + probe_block_rows, n)])
        if jt == _join.JoinType.RIGHT:
            blocks.append(_join_once(other, blk, config))
        else:
            cfg = _join.JoinConfig(sub_type, config.left_column_idx,
                                   config.right_column_idx,
                                   config.algorithm, exact=config.exact)
            blocks.append(_join_once(blk, other, cfg))
    out = concat_tables(blocks, left._ctx) if len(blocks) > 1 \
        else blocks[0]
    if jt != _join.JoinType.FULL_OUTER:
        return out
    return _append_unmatched_right(left, right, config, out)


def _append_unmatched_right(left: Table, right: Table,
                            config: _join.JoinConfig, out: Table,
                            aligned=None) -> Table:
    """FULL_OUTER = LEFT output + right rows whose key matches no left
    row (null keys never match): ONE keys-only membership pass appends
    the unmatched build rows — how both the blocked join and the
    streaming path lift their LEFT machinery to FULL_OUTER.
    ``aligned``: (lcols, rcols) already aligned by the caller (skips a
    repeat dictionary-unification / content-hash pass)."""
    lcols, rcols = aligned if aligned is not None else align_key_columns(
        left, right, config.left_column_idx, config.right_column_idx)
    # pairing is load-bearing: both sides must emit the same lane count
    # per varbytes key column or dense_ranks_two zips misaligned arrays
    lkeys, _lv_, _f, _w = _expanded_keys(lcols, rcols)
    rkeys, _rv_, _f2, _w2 = _expanded_keys(rcols, lcols)
    lv = _all_valid(lcols) & left.emit_mask()
    rv = _all_valid(rcols) & right.emit_mask()
    gl, gr = _order.dense_ranks_two(
        [jnp.where(lv, jnp.asarray(k), jnp.asarray(k).dtype.type(0))
         for k in lkeys],
        [jnp.where(rv, jnp.asarray(k), jnp.asarray(k).dtype.type(0))
         for k in rkeys])
    from ..ops.setops import _isin

    in_l = _isin(jnp.where(rv, gr, -2), jnp.where(lv, gl, -1), None)
    un = right.emit_mask() & jnp.where(rv, ~in_l, True)
    # compact: the tail must carry only the unmatched rows (filter_mask
    # is a mask view, and the >HBM blocked path relies on the tail NOT
    # being build-side-capacity wide)
    r_unmatched = right.filter_mask(un).compact()

    def _null_col(c: Column, n: int) -> Column:
        if c.is_varbytes:
            from .strings import VarBytes

            z = jnp.zeros(n, jnp.int32)
            return Column.from_varbytes(
                VarBytes(jnp.zeros(1, jnp.uint32), z, z, 1, 0),
                jnp.zeros(n, bool), c.name, c.dtype)
        return Column(jnp.zeros(n, c.data.dtype), c.dtype,
                      jnp.zeros(n, bool), c.dictionary, c.name)

    ncap = r_unmatched.capacity
    tail = Table([_null_col(c, ncap) for c in left._columns]
                 + list(r_unmatched._columns), left._ctx,
                 r_unmatched.emit_mask())
    tail = Table([c.rename(nm) for c, nm in
                  zip(tail._columns, out.column_names)], left._ctx,
                 tail.row_mask)
    return concat_tables([out, tail], left._ctx)


def _aligned_setop_columns(left: Table, right: Table):
    """Schema-aligned column pairs for set ops: dtypes promoted,
    dictionaries unified."""
    lcols, rcols = [], []
    for ci in range(left.column_count):
        a, b = left._columns[ci], right._columns[ci]
        if a.is_string:
            a, b = align_string_columns(a, b)
        elif a.data.dtype != b.data.dtype:
            common = jnp.promote_types(a.data.dtype, b.data.dtype)
            a = a.astype(dtypes.from_np_dtype(common))
            b = b.astype(dtypes.from_np_dtype(common))
        lcols.append(a)
        rcols.append(b)
    return lcols, rcols


def set_op(left: Table, right: Table, op) -> Table:
    """Local union/subtract/intersect (reference: table.cpp:729-942).
    The streaming full-row-hash path handles lane-packable schemas in
    one sort + one Pallas pass; the dense-ranks path is the general
    (and collision) fallback."""
    if left.column_count != right.column_count:
        raise CylonError(Code.Invalid, "set ops need equal schemas")
    refuse_planes(left._columns + right._columns,
                  _setops.SetOp(op).name.lower())
    lcols, rcols = _aligned_setop_columns(left, right)
    out = _setops.setop_stream_table(left, right, lcols, rcols, op)
    if out is not None:
        return out

    gl, gr = row_gids(left, right)
    rows = _setops.setop_rows(gl, gr, left.emit_mask(), right.emit_mask(), op)
    out_cols = []
    for a, b in zip(lcols, rcols):
        validity = None
        if a.validity is not None or b.validity is not None:
            validity = jnp.concatenate([a.valid_mask(), b.valid_mask()])
        if a.is_varbytes:
            merged = Column.from_varbytes(
                concat_varbytes([a.varbytes, b.varbytes]), validity, a.name,
                a.dtype)
        else:
            data = jnp.concatenate([a.data, b.data])
            merged = Column(data, a.dtype, validity, a.dictionary, a.name)
        out_cols.append(merged.take(jnp.asarray(rows)))
    return Table(out_cols, left._ctx)


def concat_tables(tables: Sequence[Table], ctx: CylonContext) -> Table:
    """Reference: Merge (table.cpp:388-427) — schema-aligned concat."""
    first = tables[0]
    out_cols = []
    for ci in range(first.column_count):
        cs = [t._columns[ci] for t in tables]
        refuse_planes(cs, "merge")
        if any(c.is_varbytes for c in cs):
            cs = [as_varbytes(c) for c in cs]
            vb = concat_varbytes([c.varbytes for c in cs])
            has_null = any(c.validity is not None for c in cs)
            validity = jnp.concatenate([c.valid_mask() for c in cs]) \
                if has_null else None
            out_cols.append(Column.from_varbytes(vb, validity, cs[0].name,
                                                 cs[0].dtype))
            continue
        if cs[0].is_string:
            # unify all vocabularies pairwise-left-fold
            base = cs[0]
            unified = [base]
            for c in cs[1:]:
                base, c2 = unify_dictionaries(base, c)
                unified = [Column(u.data if u.dictionary is base.dictionary
                                  else jnp.take(jnp.asarray(
                                      np.searchsorted(base.dictionary,
                                                      u.dictionary).astype(np.int32)),
                                      u.data),
                                  u.dtype, u.validity, base.dictionary, u.name)
                           for u in unified]
                unified.append(c2)
            cs = unified
        data = jnp.concatenate([c.data for c in cs])
        has_null = any(c.validity is not None for c in cs)
        validity = jnp.concatenate([c.valid_mask() for c in cs]) if has_null \
            else None
        out_cols.append(Column(data, cs[0].dtype, validity, cs[0].dictionary,
                               cs[0].name))
    mask = None
    if any(t.row_mask is not None for t in tables):
        mask = jnp.concatenate([t.emit_mask() for t in tables])
    return Table(out_cols, ctx, mask)


def groupby_local(table: Table, index_col, aggregate_cols: List,
                  aggregate_ops: List, second_phase: bool = False) -> Table:
    """Local hash-groupby equivalent (reference: LocalHashGroupBy,
    groupby_hash.hpp:321-359). ``second_phase`` merges partials with the
    corrected ops (COUNT→SUM)."""
    idx_cols = index_col if isinstance(index_col, (list, tuple)) else [index_col]
    idx_cols = [table._col_index(c) for c in idx_cols]
    val_cols = [table._col_index(c) for c in aggregate_cols]
    # a plane-held int64 VALUE column is summed by the dense table
    # alone (`_groupby_dense`); what is left of the path refuses it
    refuse_planes([table._columns[i] for i in idx_cols], "groupby")
    refuse_planes([c for c in (table._columns[i] for i in val_cols)
                   if c.dtype.type != dtypes.Type.INT64], "groupby")
    ops = [(_groupby.second_phase_op(o) if second_phase else o)
           for o in aggregate_ops]

    for vi, op in zip(val_cols, ops):
        if table._columns[vi].is_varbytes and \
                op != _groupby.AggregationOp.COUNT:
            raise CylonError(
                Code.NotImplemented,
                "varbytes value columns support COUNT only (MIN/MAX need "
                "a total order the content-hash identity does not carry; "
                "dictionary-encode the column for string MIN/MAX)")
    key_columns = [table._columns[i] for i in idx_cols]
    # None (no row mask): every row is live, and no dead flag rides
    emit = table.row_mask
    values = tuple(table._columns[i].data for i in val_cols)
    # None for all-valid columns: the mask never rides the sort
    valids = tuple(table._columns[i].validity for i in val_cols)
    vdtypes = [v.dtype for v in values]
    dense, key_range = _groupby_dense(table, key_columns, val_cols, values,
                                      valids, ops, aggregate_ops)
    if dense is not None:
        return dense
    for c in (table._columns[i] for i in val_cols):
        if c.is_planes:
            raise CylonError(
                Code.NotImplemented,
                f"groupby: column {c.name!r} is INT64 held as two 32-bit "
                f"word planes (jax_enable_x64 is off); only the dense "
                f"table sums such a column (SUM / COUNT / MEAN by integer "
                f"keys whose observed ranges span at most "
                f"{_groupby.DENSE_MAX_SLOTS} slots, on one device), and "
                f"this groupby has to sort")
    keys = []
    for c in key_columns:
        if c.is_varbytes:
            # group identity = content hashes (grouping needs equality,
            # not order)
            ks, _vs, _fs = string_key_arrays(c)
            keys.extend(ks)
        else:
            keys.extend(_order.sort_keys([c]))
        if c.validity is not None:
            keys.append(c.valid_mask().astype(jnp.uint8))
    # how to read each output key column back off its sorted lanes; a
    # varbytes key (hash lanes) has no way back
    key_spec = None if any(c.is_varbytes for c in key_columns) else tuple(
        (np.dtype(c.data.dtype), c.is_string, c.validity is not None)
        for c in key_columns)
    index = _groupby.sort_carries_index(keys, key_spec, vdtypes, ops,
                                        table.capacity)
    # ONE fused sort groups rows contiguously (dead rows last); the
    # n_groups fetch below is the op's single host sync, and the reduce
    # step then works on the sorted RUNS — see ops/groupby.presort_groups
    # (round-5 rework of the dense-rank + scatter-back path; the old gid
    # scatter cost ~15-30 ns/element). Its operands are counted here,
    # where the host can see them (the same pure function of mask, lanes,
    # columns, index and packing that presort_groups builds its list by)
    plan, params = _sort_pack_plan(_sort_pack_probe(
        table.capacity // len(key_columns[0].data.sharding.device_set),
        None if key_range is None else (key_columns[0], key_range), values))
    _telemetry.counter("cylon_groupby_sort_operands_total").inc(
        _groupby.sort_operand_count(keys, emit, values, valids, index, plan))
    _telemetry.counter("cylon_groupby_sort_packed_columns_total").inc(
        _groupby.packed_members(plan))
    values_s, valids_s, emit_s, first_s, new_grp, ng = \
        _groupby.presort_groups_jit(tuple(keys), emit, values, valids,
                                    index=index, plan=plan, params=params)
    num_groups = max(int(_telemetry.host_fetch("groupby.groups", ng)), 1)
    cap = _pow2(num_groups)

    # the path sorted_segment_aggregate will take, counted where the
    # host can see it (the same pure function of backend and widths)
    _telemetry.counter("cylon_groupby_reduce_path_total", {
        "path": _groupby.reduce_path(vdtypes, ops, table.capacity)}).inc()
    firsts, group_valid, results = _groupby.sorted_segment_aggregate_jit(
        new_grp, emit_s, first_s, values_s, valids_s, cap, tuple(ops),
        tuple(val_cols),
        tuple(table._columns[i].validity is None for i in val_cols),
        key_spec=None if index else key_spec)

    # materialize at pow2 group capacity: dead slots (gid-space holes from
    # masked rows, pow2 padding) stay on device masked via row_mask —
    # num_groups above was the only host sync in this op
    if index:
        # firsts = rep: each group's first original row, to gather its key
        safe = jnp.minimum(firsts, max(table.capacity - 1, 0))
        out_cols = []
        for c in key_columns:
            g = c.take(safe)
            validity = None if g.validity is None \
                else g.validity & group_valid
            out_cols.append(Column(g.data, g.dtype, validity, g.dictionary,
                                   g.name, varbytes=g.varbytes))
    else:
        # the key columns came out of the reduce program itself
        out_cols = [Column(data, c.dtype, validity, c.dictionary, c.name)
                    for c, (data, validity) in zip(key_columns, firsts)]
    for (arr, avalid), vi, op in zip(results, val_cols, aggregate_ops):
        src = table._columns[vi]
        out_cols.append(Column(
            arr, _agg_dtype(src, op, arr), avalid & group_valid,
            src.dictionary if op in (_groupby.AggregationOp.MIN,
                                     _groupby.AggregationOp.MAX)
            and src.is_string else None,
            src.name))
    return Table(out_cols, table._ctx, group_valid)


@_telemetry.counted_cache
def _groupby_key_range_fn():
    """The probe of the groupby's path choice: (lo, hi) of the key over
    its live rows, one small program (`jit_groupby_key_range`)."""
    def kernel(key, emit, key_valid):
        return _groupby.key_range_probe(key, emit, key_valid)

    return jax.jit(kernel)


@_telemetry.counted_cache
def _groupby_dense_fn(slots: int, ops: tuple, col_ids: tuple,
                      interpret: bool):
    """The no-sort groupby over ``slots`` slots (`jit_groupby_dense`:
    ops/groupby.dense_aggregate, the Pallas pass inside)."""
    def kernel(key, key_valid, emit, lohi, values, valids):
        return _groupby.dense_aggregate(key, key_valid, emit, lohi, values,
                                        valids, slots, ops, col_ids,
                                        interpret=interpret)

    return jax.jit(kernel)


@_telemetry.counted_cache
def _groupby_value_range_fn():
    """The probe of the sort's packing: (lo, hi) of each integer value
    column over every row, one small program (`jit_groupby_value_range`)."""
    def kernel(values):
        return _groupby.value_range_probe(values)

    return jax.jit(kernel)


@_telemetry.counted_cache
def _groupby_pack_ranges_program_fn():
    """The probe of the sort's packing where nothing has observed the key
    yet (across chips): the ONE key's (lo, hi) over the live rows and each
    integer value column's over every row, ONE array
    (`jit_groupby_pack_ranges_program`; on a sharded table a min / max
    over all the shards)."""
    def kernel(key, emit, values):
        return _groupby.pack_ranges_probe(key, emit, values)

    return jax.jit(kernel)


class _SortPackProbe(NamedTuple):
    """A dispatched probe of the sort's packing (`_sort_pack_probe`)."""
    ranges: object      # on the device, not fetched yet
    probed: tuple       # the value columns it looked at, by position
    n_values: int
    key: object         # the key column that may share its word, or None
    key_range: object   # its (lo, hi) observed before; None: in ``ranges``


def _key_word(key: Column, lo: int, hi: int):
    """(bits, lowest ordered lane) of a key observed in [lo, hi]; no live
    row (lo > hi): a key of one bit, every row is dead."""
    bits = max(_groupby.range_bits(lo, hi), 1) if lo <= hi else 1
    return bits, _groupby.key_lane_lo(lo, key.data.dtype, key.is_string)


def _sort_pack_probe(rows: int, key, values, emit=None):
    """Dispatch the probe by which `_sort_pack_plan` packs the groupby's
    fused sort (`ops/groupby.presort_groups`), or None where none is paid:
    ONE decision for the one-chip and the distributed groupby, from what
    the host can see before it dispatches (no knob).

    ``rows``: a shard's rows. ``key``: None, or (column, range) of the ONE
    key column whose lane is the sort's only key and may share its word
    (no nulls, not varbytes, at most 32 bits wide): ``range`` its (lo, hi)
    where an earlier probe observed it (`_groupby_dense`'s, on one chip),
    None where nothing has (across chips no dense check runs): then this
    probe looks at the key too, over the live rows (``emit``; None: all),
    in the same program and the same array as the value columns
    (`jit_groupby_pack_ranges_program`, else `jit_groupby_value_range`).
    The probe and its fetch are paid only where the static side says a
    word could be saved (an integer value column at most 32 bits wide,
    and the key's spare bits or a second such column to share a word
    with), for shards of SORT_PACK_MIN_ROWS rows or more. Both programs
    are correct on sharded arrays (a min / max over all the shards: the
    plan is one for the whole table)."""
    column, key_range = (None, None) if key is None else key
    key_hope = None if column is None else 1 if key_range is None \
        else _key_word(column, *key_range)[0]
    # the static side: a bit a column that could pack, the least it takes
    hoped = [1 if _groupby.packs(v.dtype) else None for v in values]
    if rows < _groupby.SORT_PACK_MIN_ROWS \
            or _groupby.sort_pack_plan(key_hope, hoped) is None:
        return None
    probed = tuple(j for j, bit in enumerate(hoped) if bit is not None)
    looked = tuple(values[j] for j in probed)
    if column is not None and key_range is None:
        ranges = _groupby_pack_ranges_program_fn()(column.data, emit, looked)
    else:
        ranges = _groupby_value_range_fn()(looked)
    return _SortPackProbe(ranges, probed, len(values), column, key_range)


def _sort_pack_plan(probe):
    """(plan, params) for `ops/groupby.presort_groups` off a dispatched
    `_sort_pack_probe`: which integer value columns ride inside another
    operand's word, by what the host observed, or (None, None) where
    every column rides alone (no probe, or ranges that do not fit:
    nothing is truncated). The ONE fetch of the probe is here
    (`sync.groupby.valuerange`; `sync.groupby.packranges` where the array
    holds the key's range too), so a caller may dispatch what does not
    depend on the ranges between the two calls."""
    if probe is None:
        return None, None
    key_bits = key_lo = None
    if probe.key is not None and probe.key_range is None:
        ranges = _telemetry.host_fetch("groupby.packranges", probe.ranges)
        key_bits, key_lo = _key_word(probe.key, *_groupby.key_range_of(
            ranges[0], probe.key.data.dtype))
        ranges = ranges[1:, :2]
    else:
        ranges = _telemetry.host_fetch("groupby.valuerange", probe.ranges)
        if probe.key is not None:
            key_bits, key_lo = _key_word(probe.key, *probe.key_range)
    value_lo = [None] * probe.n_values
    value_bits = [None] * probe.n_values
    for j, (lo, hi) in zip(probe.probed, ranges.tolist()):
        value_lo[j], value_bits[j] = lo, _groupby.range_bits(lo, hi)
    plan = _groupby.sort_pack_plan(key_bits, value_bits)
    if plan is None:
        return None, None
    return plan, _groupby.sort_pack_params(plan, key_lo, key_bits, value_lo,
                                           value_bits)


def _groupby_dense(table: Table, key_columns, val_cols, values, valids, ops,
                   aggregate_ops):
    """(the groupby over few groups, with no sort, or None where
    `ops/groupby.group_path` says "sort"; ONE key's observed (lo, hi), or
    None where nothing was probed or there are several). The static
    conditions cost nothing; only a table that meets them (and lives on
    ONE device: the kernel is no sharded program) pays the probe, a
    fused min / max over the keys and the fetch of the array
    (`sync.groupby.keyrange`). With the observed slots inside
    DENSE_MAX_SLOTS the dense program runs: `_pow2(slots)` slots in key
    order, ``row_mask`` the slots a live row reached.

    ONE key column and 32-bit values is the path as it always was
    (`jit_groupby_key_range`, `jit_groupby_dense`). Several key columns,
    or a plane-held int64 value column, take `_groupby_dense_keys`."""
    kdtypes = [None if c.is_varbytes else np.dtype(c.data.dtype)
               for c in key_columns]
    knull = [c.validity is not None for c in key_columns]
    vcols = [table._columns[i] for i in val_cols]
    vdtypes = [_groupby.PLANES_INT64 if c.is_planes
               else None if c.is_varbytes else np.dtype(c.data.dtype)
               for c in vcols]
    n = table.capacity
    if _groupby.group_path(kdtypes, knull, vdtypes, ops, n) != "dense" \
            or len(key_columns[0].data.sharding.device_set) != 1:
        return None, None
    if len(key_columns) > 1 or _groupby.PLANES_INT64 in vdtypes:
        return _groupby_dense_keys(table, key_columns, vcols, val_cols,
                                   values, valids, ops, aggregate_ops,
                                   (kdtypes, knull, vdtypes)), None
    kc = key_columns[0]
    emit = table.row_mask
    lohi = _groupby_key_range_fn()(kc.data, emit, kc.validity)
    lo, hi = (int(x) for x in _telemetry.host_fetch("groupby.keyrange",
                                                    lohi))
    # no live row with a key: one empty slot
    key_range = hi - lo + 1 if lo <= hi else 1
    if _groupby.group_path(kdtypes, knull, vdtypes, ops, n,
                           key_range) != "dense":
        # a key that holds no null keeps its range for the sort's packing
        return None, None if knull[0] else (lo, hi)
    slots = _pow2(key_range + knull[0])
    _count_dense(slots, 1)
    key_data, key_validity, group_valid, results = _groupby_dense_fn(
        slots, tuple(ops), tuple(val_cols),
        jax.default_backend() != "tpu")(
            kc.data, kc.validity, emit, lohi, values, valids)
    out_cols = [Column(key_data, kc.dtype, key_validity, kc.dictionary,
                       kc.name)]
    for (arr, avalid), src, op in zip(results, vcols, aggregate_ops):
        out_cols.append(Column(arr, _agg_dtype(src, op, arr), avalid, None,
                               src.name))
    out = Table(out_cols, table._ctx, group_valid)
    out._key_ordered = 1
    return out, None


def _count_dense(slots: int, keys: int) -> None:
    """Counted where the host evaluates the decision (the sort path
    counts its reduce step, `stream` or `segment`, where it is taken)."""
    _telemetry.counter("cylon_groupby_reduce_path_total",
                       {"path": "dense"}).inc()
    _telemetry.counter("cylon_groupby_dense_slots_total").inc(slots)
    _telemetry.counter("cylon_groupby_dense_keys_total").inc(keys)


@_telemetry.counted_cache
def _groupby_ranges_program_fn():
    """The probe of a dense groupby over several keys: every key's (lo,
    hi) over its live rows, ONE array (`jit_groupby_ranges_program`)."""
    def kernel(keys, emit, key_valids):
        return _groupby.ranges_probe(keys, emit, key_valids)

    return jax.jit(kernel)


@_telemetry.counted_cache
def _groupby_dense_keys_fn(slots: int, ops: tuple, col_ids: tuple,
                           wide: tuple, interpret: bool):
    """The no-sort groupby over several keys and 64-bit sums
    (`jit_groupby_dense_keys`: ops/groupby.dense_aggregate_keys, the
    Pallas pass inside); the live groups' count and the overflow bits
    ride out with it as one array of two."""
    def kernel(keys, key_valids, emit, ranges, values, valids):
        key_cols, group_valid, results, overflow = \
            _groupby.dense_aggregate_keys(
                keys, key_valids, emit, ranges, values, valids, slots, ops,
                col_ids, wide, interpret=interpret)
        return key_cols, group_valid, results, jnp.stack(
            [group_valid.sum(dtype=jnp.int32), overflow])

    return jax.jit(kernel)


@functools.lru_cache(maxsize=None)
def _known_key_ranges(sizes: tuple):
    """`ops/groupby.ranges_probe`'s array for keys whose values are known
    to lie in [0, size): made once a tuple of sizes, kept on the device."""
    return jnp.asarray(np.array([(0, size - 1, 0) for size in sizes],
                                np.uint32))


# the count of live groups the last `_groupby_dense_keys` of a shape (slots,
# ops, columns, which are wide) found: the next one's cut is dispatched for
# it before its own count is down (and then held to it)
_last_live: dict = {}


@partial(jax.jit, static_argnames=("n",))
def _dense_cut_program(arrays, group_valid, n: int):
    """The ``n`` live slots of every array, in slot order (rows are the
    last axis; None stays None)."""
    idx = jnp.nonzero(group_valid, size=n, fill_value=0)[0]
    return jax.tree.map(lambda a: jnp.take(a, idx, axis=-1), arrays)


def _groupby_dense_keys(table: Table, key_columns, vcols, val_cols, values,
                        valids, ops, aggregate_ops, static):
    """`_groupby_dense` for several key columns and for plane-held int64
    value columns, or None where the observed slots say "sort". ONE probe
    program and ONE fetch for all the keys (`sync.groupby.keyrange`), and
    none where the keys' values are known without looking (dictionary
    codes, bools) and span few enough slots.

    A 64-bit column is summed exactly WHATEVER it holds: the kernel cuts
    it into limbs and the program adds them up wider than an int64, so
    nothing about the values is observed first; a total that is no int64
    is seen in the second fetch (`sync.groupby.densegroups`: the live
    groups' count and the overflow bits) and raises, naming the column.
    Nothing wrapped is ever handed on. A result that holds a plane-held
    column is cut to its live groups by that count (one small gather,
    dispatched before the fetch for the count the last groupby of this
    shape had, and again where this one's is another): its ``row_mask``
    is None, as a join result's that holds planes."""
    kdtypes, knull, vdtypes = static
    n = table.capacity
    emit = table.row_mask
    keys = tuple(c.data for c in key_columns)
    key_valids = tuple(c.validity for c in key_columns)
    # a dictionary column's codes lie in [0, len(dictionary)) and a bool is
    # 0 or 1: where every key is one of these and their slots fit, nothing
    # is observed at all (no probe program, no fetch)
    key_ranges = [max(len(c.dictionary), 1) if c.dictionary is not None
                  else 2 if kd == np.bool_ else None
                  for c, kd in zip(key_columns, kdtypes)]
    if None not in key_ranges and _groupby.group_path(
            kdtypes, knull, vdtypes, ops, n, key_ranges) == "dense":
        ranges = _known_key_ranges(tuple(key_ranges))
    else:
        ranges = _groupby_ranges_program_fn()(keys, emit, key_valids)
        fetched = np.asarray(_telemetry.host_fetch("groupby.keyrange",
                                                   ranges))
        key_ranges = []
        for row, kd in zip(fetched, kdtypes):
            lo, hi = _groupby.key_range_of(row, kd)
            key_ranges.append(hi - lo + 1 if lo <= hi else 1)
        if _groupby.group_path(kdtypes, knull, vdtypes, ops, n,
                               key_ranges) != "dense":
            return None
    wide = tuple(d is _groupby.PLANES_INT64
                 and op != _groupby.AggregationOp.COUNT
                 for d, op in zip(vdtypes, ops))
    live_slots = 1
    for r, nullable in zip(key_ranges, knull):
        live_slots *= r + nullable
    slots = _pow2(live_slots)
    _count_dense(slots, len(keys))
    key_out, group_valid, results, tail = _groupby_dense_keys_fn(
        slots, tuple(ops), tuple(val_cols), wide,
        jax.default_backend() != "tpu")(
            keys, key_valids, emit, ranges, values, valids)
    arrays = [list(key_out), [tuple(r) for r in results]]

    def result(arrays, mask):
        out_cols = [Column(data, kc.dtype, validity, kc.dictionary, kc.name)
                    for kc, (data, validity) in zip(key_columns, arrays[0])]
        for (arr, avalid), src, op in zip(arrays[1], vcols, aggregate_ops):
            out_cols.append(Column(arr, _agg_dtype(src, op, arr), avalid,
                                   None, src.name))
        out = Table(out_cols, table._ctx, mask)
        out._key_ordered = len(keys)
        return out

    if not any(wide):
        return result(arrays, group_valid)
    # the cut is dispatched, and its table built, for the count of live
    # groups the last groupby of this shape had, BEFORE this one's count is
    # down: where it is the same nothing is left to do when the fetch returns
    planes = any(arr.ndim == 2 for arr, _ in results)
    shape = (slots, tuple(ops), tuple(val_cols), wide)
    guess = _last_live.get(shape) if planes else None
    out = None if guess is None else result(
        _dense_cut_program(arrays, group_valid, n=guess), None)
    live, overflow = (int(x) for x in _telemetry.host_fetch(
        "groupby.densegroups", tail))
    if overflow:
        names = sorted({c.name for j, c in enumerate(vcols)
                        if wide[j] and overflow >> (j % 31) & 1})
        raise CylonError(
            Code.Invalid,
            f"groupby: the sum of column {', '.join(map(repr, names))} "
            f"does not fit an int64 in some group; no result (nothing "
            f"wrapped) is returned")
    if not planes:
        return result(arrays, group_valid)
    if live != guess:
        if len(_last_live) >= 64:
            _last_live.clear()
        _last_live[shape] = live
        out = result(_dense_cut_program(arrays, group_valid, n=live), None)
    return out


@_telemetry.counted_cache
def _expr_ranges_program_fn():
    """The range of every column an expression reads, ONE array
    (`jit_expr_ranges_program`)."""
    def kernel(arrays):
        return _expr.range_probe(arrays)

    return jax.jit(kernel)


@_telemetry.counted_cache
def _expr_compute_program_fn(exprs: tuple, forms: tuple, out_dtypes: tuple,
                             width: int):
    """Every computed column of one `Table.with_columns`, and its
    validity, in ONE program (`jit_expr_compute_program`); ``forms``
    None: native int64 steps (x64 on). Expression i reads computed column
    j < i at position ``width + j``; a value is null where a column it
    reads is (``valids``: position -> mask, the nullable columns only)."""
    def kernel(leaves, valids):
        leaves, valids = dict(leaves), dict(valids)
        out = []
        for i, (tokens, dtype) in enumerate(zip(exprs, out_dtypes)):
            col = _expr.evaluate_native(tokens, leaves, dtype, valids) \
                if forms is None else _expr.evaluate_words(
                    tokens, forms[i], leaves, dtype, valids)
            # a case_when is 0 where its predicate reads a null: never null
            masks = [valids[p] for p in
                     sorted(_expr.columns_of(tokens, values_only=True))
                     if p in valids]
            validity = functools.reduce(jnp.logical_and, masks) \
                if masks else None
            leaves[width + i] = col
            if validity is not None:
                valids[width + i] = validity
            out.append((col, validity))
        return out

    return jax.jit(kernel)


def value_dtype(tokens, types) -> str:
    """The dtype string ("int32" / "int64") of a value expression's
    column over columns of ``types`` (the plan's type strings)."""
    return _expr.result_dtype(tokens, types)


# the forms the last `_with_columns` of a shape (expressions, dtypes, width)
# proved: what the next one of that shape is dispatched with BEFORE its own
# ranges are down (and then held to them)
_last_forms: dict = {}


def _with_columns(table: Table, names, exprs) -> Table:
    """`Table.with_columns`. The probe is dispatched, then the compute
    program with the forms the last call of this shape proved (with x64
    on there are no forms to choose), and only then are the ranges
    fetched (`sync.expr.range`): the chip computes while the host waits
    and proves. `plan_forms` raises where a step cannot be shown to fit,
    and forms other than the guessed ones compute again; no column leaves
    this function that its own table's ranges have not proven."""
    w = table.column_count
    read = sorted({p for t in exprs for p in _expr.columns_of(t) if p < w})
    cols = [table._columns[p] for p in read]
    types = ["str" if c.is_string else str(c.host_dtype)
             for c in table._columns]
    out_dtypes = []
    for tokens in exprs:    # raises on a column that is no integer
        out_dtypes.append(_expr.result_dtype(tokens, types + out_dtypes))
    # a case_when's string literals as this table's dictionary codes (a
    # computed column is a number: Column's defaults stand for it)
    by_pos = dict(enumerate(table._columns))
    native = bool(jax.config.jax_enable_x64)
    for i, (name, dtype) in enumerate(zip(names, out_dtypes)):
        by_pos[w + i] = Column(
            np.zeros(0, dtype) if native or dtype == "int32"
            else np.zeros((2, 0), np.uint32),
            dtypes.from_np_dtype(np.dtype(dtype)), name=name)
    exprs = [_resolve_value(t, by_pos) for t in exprs]
    # only what a VALUE reads is probed: a case_when's range is {0, 1}
    # whatever its predicate reads, and a table whose expressions are all
    # of that kind is neither probed nor fetched
    probed = sorted({p for t in exprs
                     for p in _expr.columns_of(t, values_only=True) if p < w})
    probe = _expr_ranges_program_fn()(
        tuple(table._columns[p].data for p in probed)) if probed else None
    shape = (tuple(exprs), tuple(out_dtypes), w)

    def compute(forms):
        return _expr_compute_program_fn(shape[0], forms, *shape[1:])(
            {p: c.data for p, c in zip(read, cols)},
            {p: c.validity for p, c in zip(read, cols)
             if c.validity is not None})

    guess = None if native else _last_forms.get(shape)
    computed = compute(guess) if native or guess is not None else None
    ranges = {} if probe is None else dict(zip(probed, _expr.ranges_of(
        _telemetry.host_fetch("expr.range", probe))))
    all_names = list(table.column_names) + list(names)
    forms = []
    for i, (tokens, dtype) in enumerate(zip(exprs, out_dtypes)):
        f, ranges[w + i] = _expr.plan_forms(tokens, ranges, dtype, names[i],
                                            all_names)
        forms.append(f)
    forms = None if native else tuple(forms)
    if computed is None or forms != guess:
        computed = None   # a wrong guess's columns go before the right ones
        computed = compute(forms)
        if len(_last_forms) >= 64:
            _last_forms.clear()
        _last_forms[shape] = forms
    new_cols = [Column(arr, dtypes.from_np_dtype(np.dtype(dtype)), validity,
                       None, name)
                for (arr, validity), dtype, name in zip(computed, out_dtypes,
                                                        names)]
    _telemetry.counter("cylon_expr_columns_total").inc(len(new_cols))
    _telemetry.counter("cylon_expr_materialized_bytes_total").inc(
        sum(c.data.dtype.itemsize * math.prod(c.data.shape)
            for c in new_cols))
    out = Table(list(table._columns) + new_cols, table._ctx, table.row_mask)
    out._hash_partitioned = table._hash_partitioned
    return out


def _agg_dtype(src: Column, op, arr) -> dtypes.DataType:
    """The label of an aggregate's column: what ``arr`` IS. A COUNT is an
    int64 and a MEAN a float64 only with x64 on; without it they are the
    int32 and the float32 the chip computed, and say so."""
    if op in (_groupby.AggregationOp.COUNT, _groupby.AggregationOp.MEAN):
        return dtypes.from_np_dtype(np.dtype(arr.dtype))
    return src.dtype
