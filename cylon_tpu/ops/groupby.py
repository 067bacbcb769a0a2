"""Group-by aggregation kernels — sort-based segmented reduction.

Replaces the reference's hash-map group-by (reference:
cpp/src/cylon/groupby/groupby_hash.hpp:28-359 — `unordered_map` with
compile-time `AggregateKernel<T,Op>{Init,Update,Finalize}`, and the
sorted-run pipeline variant groupby_pipeline.hpp:28-257) with the TPU
formulation: ONE fused stable sort groups the rows contiguously
(`presort_groups`), then group g is the g-th run and every aggregation
is a reduction over contiguous runs (`sorted_segment_aggregate`). On a
TPU with 4-byte accumulators that is ONE streaming Pallas pass with no
scatter (`tpu_kernels.groupby_run_reduce`: running reductions that
restart at each run start, run ends compacted to slot g); elsewhere —
the CPU, 8-byte accumulators under x64 — the portable path, which is
also the tests' oracle: `jax.ops.segment_*` over sorted ids, an XLA
scatter a stream that costs 8.8 ns a row on a v5e whatever the locality
(3,515 ms of groupby-q5's 4,467 before PR 26; PERF.md section 6).

Distributed semantics (fixing the reference's re-aggregation subtlety noted
in SURVEY §3.2): partial aggregates are combined with the correct SECOND-
PHASE op — COUNT partials are SUMmed, MEAN carries (sum, count) pairs and
divides at the end. The reference re-applies the same op twice, which makes
distributed COUNT wrong when a key spans ranks (groupby/groupby.cpp:96-139).
"""
from __future__ import annotations

import enum
from functools import partial
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


class AggregationOp(enum.IntEnum):
    """Reference: groupby/groupby_aggregate_ops.hpp `GroupByAggregationOp`
    (SUM/COUNT/MIN/MAX); MEAN added (the reference left it commented out,
    groupby_hash.hpp:118-138)."""

    SUM = 0
    COUNT = 1
    MIN = 2
    MAX = 3
    MEAN = 4


def second_phase_op(op: AggregationOp) -> AggregationOp:
    """The op used to merge per-shard partials (COUNT partials are summed)."""
    if op in (AggregationOp.COUNT,):
        return AggregationOp.SUM
    return op


def _identity_for(op: AggregationOp, dtype):
    if op in (AggregationOp.SUM, AggregationOp.COUNT, AggregationOp.MEAN):
        return jnp.zeros((), dtype)
    if op == AggregationOp.MIN:
        return jnp.asarray(_max_of(dtype), dtype)
    return jnp.asarray(_min_of(dtype), dtype)


def _max_of(dtype):
    d = np.dtype(dtype)
    if d.kind == "f":
        return np.inf
    if d.kind == "b":
        return True
    return np.iinfo(d).max


def _min_of(dtype):
    d = np.dtype(dtype)
    if d.kind == "f":
        return -np.inf
    if d.kind == "b":
        return False
    return np.iinfo(d).min


def presort_groups(keys: Tuple[jnp.ndarray, ...], emit: jnp.ndarray,
                   values: Tuple[jnp.ndarray, ...],
                   valids: Tuple[jnp.ndarray, ...]):
    """ONE fused stable sort carries the key bits, every value column,
    every validity mask, emit and iota as operands (dead rows last via a
    dead-flag primary key — the join/sort kernels' trick). Output rows
    are grouped contiguously: group g is the g-th run of live rows, so
    the reduce step (sorted_segment_aggregate) works on runs and the
    dense-rank scatter-back the old path paid (a ~15-30 ns/element
    .at[perm].set at full row count) disappears entirely.

    ``valids`` entries may be None (all-valid column): None masks don't
    ride the sort — the aggregate reads them as "live row = valid".

    Returns (values_s, valids_s, emit_s, iota_s, new_grp, n_groups):
    new_grp marks each live run's first SORTED row, n_groups is a device
    scalar (the caller's single host sync)."""
    n = emit.shape[0]
    dead = (~emit).astype(jnp.uint8)
    iota = jnp.arange(n, dtype=jnp.int32)
    nk, nv = len(keys), len(values)
    real_v = [v for v in valids if v is not None]
    ops_in = (dead,) + tuple(keys) + tuple(values) + tuple(real_v) \
        + (emit, iota)
    res = jax.lax.sort(ops_in, num_keys=1 + nk, is_stable=True)
    ks = res[1:1 + nk]
    values_s = tuple(res[1 + nk:1 + nk + nv])
    it = iter(res[1 + nk + nv:1 + nk + nv + len(real_v)])
    valids_s = tuple(None if v is None else next(it) for v in valids)
    emit_s, iota_s = res[-2], res[-1]
    # row differs from its predecessor on any key lane (row 0 = True);
    # dead rows are all last, so live rows form a prefix
    neq = jnp.zeros(n, dtype=bool).at[0].set(True)
    for k in ks:
        neq = neq | jnp.concatenate([jnp.ones(1, bool), k[1:] != k[:-1]])
    new_grp = neq & emit_s
    return (values_s, valids_s, emit_s, iota_s, new_grp,
            new_grp.sum(dtype=jnp.int32))


_STREAM_DTYPES = (jnp.int32, jnp.float32)


def _accumulator_dtypes(dtype, op: AggregationOp):
    """What sorted_segment_aggregate accumulates ``op`` over a ``dtype``
    column in (validity tallies are int32 whatever the column)."""
    canon = jax.dtypes.canonicalize_dtype
    if op == AggregationOp.COUNT:
        return (canon(jnp.int64),)
    if op == AggregationOp.MEAN:
        return (canon(jnp.float64), canon(jnp.int64))
    return (np.dtype(dtype),)


def reduce_path(value_dtypes, ops, n: int, interpret: bool = False) -> str:
    """Which reduce step sorted_segment_aggregate takes, decided from
    what the code can observe (the model: join.stream_plan_applicable):
    ``"stream"`` — the scatter-free Pallas pass — on a TPU backend when
    every accumulator is an int32 or a float32 (the kernel's lanes are
    32 bits wide and it compares signed); ``"segment"`` otherwise: the
    CPU, 8-byte COUNT/MEAN accumulators under x64, narrow, unsigned or
    bool value columns. ``interpret`` stands for the TPU backend (the
    tests' way in, eagerly under the Pallas interpreter)."""
    if not (interpret or jax.default_backend() == "tpu"):
        return "segment"
    if not 1 <= n < (1 << 30):
        return "segment"
    for dtype, op in zip(value_dtypes, ops):
        if any(a not in _STREAM_DTYPES
               for a in _accumulator_dtypes(dtype, op)):
            return "segment"
    return "stream"


def _reduce_segments(new_grp, emit_s, iota_s, subs, num_segments: int):
    """The portable reduce step: dense ids by a cumsum over the run
    starts, then one ``indices_are_sorted`` XLA scatter a stream."""
    n = new_grp.shape[0]
    gid_s = jnp.cumsum(new_grp.astype(jnp.int32)) - 1
    seg = jnp.where(emit_s, gid_s, num_segments)  # masked -> overflow slot
    rep = jnp.full(num_segments + 1, n, jnp.int32).at[seg].min(
        jnp.where(emit_s, iota_s, n), indices_are_sorted=True)
    reducers = {"add": jax.ops.segment_sum, "min": jax.ops.segment_min,
                "max": jax.ops.segment_max}
    return rep[:num_segments], {
        key: reducers[kind](x, seg, num_segments=num_segments + 1,
                            indices_are_sorted=True)[:num_segments]
        for key, (kind, x) in subs.items()}


def _reduce_runs(new_grp, emit_s, iota_s, subs, num_segments: int,
                 interpret: bool):
    """The streaming reduce step: every stream in ONE pass of
    tpu_kernels.groupby_run_reduce (no gather or scatter of n or of
    num_segments elements); slots at and past the run count are filled
    as the segment path leaves them (rep = n, each op's identity)."""
    from . import tpu_kernels as tk

    n = new_grp.shape[0]
    keys = list(subs)
    # the sort is stable: a run's first sorted row is its first original
    outs, count = tk.groupby_run_reduce(
        new_grp, emit_s, [iota_s] + [subs[k][1] for k in keys],
        ["first"] + [subs[k][0] for k in keys], num_segments,
        interpret=interpret)
    live = jnp.arange(num_segments, dtype=jnp.int32) < count
    empty = {"add": lambda d: 0, "min": _max_of, "max": _min_of}
    return jnp.where(live, outs[0], jnp.int32(n)), {
        k: jnp.where(live, o, jnp.asarray(empty[subs[k][0]](o.dtype),
                                          o.dtype))
        for k, o in zip(keys, outs[1:])}


def sorted_segment_aggregate(new_grp, emit_s, iota_s,
                             values_s: Tuple[jnp.ndarray, ...],
                             valids_s: Tuple[jnp.ndarray, ...],
                             num_segments: int,
                             ops: Tuple[AggregationOp, ...],
                             col_ids: Tuple[int, ...],
                             all_valid: Tuple[bool, ...],
                             interpret: bool = False):
    """Aggregate presorted value columns into per-group slots: group g
    (the g-th run that ``new_grp`` starts) lands in slot g.

    Duplicate sub-reductions dedup across the op list (static
    ``col_ids`` name each value's source column — the same traced array
    appears as distinct tracers per arg position, so identity can't):
    SUM/MIN/MAX/COUNT repeated on one column run once; MEAN reuses
    COUNT's tally; all-valid columns (``all_valid``) skip both the
    any-valid pass (it equals group_valid) and get one shared count.
    The distinct reductions then ALL go one way, chosen here by
    ``reduce_path`` (no knob): the streaming Pallas pass or the
    ``segment_*`` scatters. Integer results, MIN/MAX, rep and the masks
    are bit-equal between the two; a float sum differs by association
    only (each path adds a group's own values and partials, nothing
    else).

    Returns (rep_idx, group_valid, list_of_(agg_array, agg_valid)):
      rep_idx[g] = first ORIGINAL row index holding group g (n past
      the group count), agg arrays have shape [num_segments].
    MEAN returns a float64 array; COUNT returns int64 of non-null values
    (Arrow count semantics) — float32 / int32 with x64 off."""
    n = new_grp.shape[0]
    stream = reduce_path([v.dtype for v in values_s], ops, n,
                         interpret) == "stream"
    subs = {}   # key -> (kind, masked stream): each DISTINCT reduction

    def sub(key, kind, make):
        if key not in subs:
            subs[key] = (kind, make())
        return key

    kind_of = {AggregationOp.SUM: "add", AggregationOp.MIN: "min",
               AggregationOp.MAX: "max"}
    plan = []
    for arr, vmask, op, cid, av in zip(values_s, valids_s, ops, col_ids,
                                       all_valid):
        # the rows that count; None = every row. The streaming pass
        # never reads a dead row into a result, so only a validity mask
        # costs it a pass (an all-valid column goes in as it is)
        if stream:
            use = vmask
        else:
            use = emit_s if vmask is None else (emit_s & vmask)

        def masked(x, fill):
            return x if use is None else jnp.where(use, x, fill)

        def tally(dtype):
            return jnp.ones(n, dtype) if use is None else use.astype(dtype)

        vkey = "all" if av else cid
        if op in (AggregationOp.COUNT, AggregationOp.MEAN):
            count = sub(("count", vkey), "add", lambda: tally(jnp.int64))
            msum = None if op == AggregationOp.COUNT else sub(
                ("msum", cid), "add",
                lambda: masked(arr, 0).astype(jnp.float64))
            plan.append((op, msum, count))
            continue
        out = sub((kind_of[op], cid), kind_of[op],
                  lambda: masked(arr, _identity_for(op, arr.dtype)))
        # all rows valid: a group exists iff it has a live row
        anyv = None if av else sub(("anyv", cid), "max",
                                   lambda: tally(jnp.int32))
        plan.append((op, out, anyv))

    if stream:
        rep, red = _reduce_runs(new_grp, emit_s, iota_s, subs,
                                num_segments, interpret)
    else:
        rep, red = _reduce_segments(new_grp, emit_s, iota_s, subs,
                                    num_segments)
    group_valid = rep < n

    results = []
    for op, a, b in plan:
        if op == AggregationOp.COUNT:
            results.append((red[b], group_valid))
        elif op == AggregationOp.MEAN:
            c = red[b].astype(jnp.float64)
            results.append((red[a] / jnp.maximum(c, 1),
                            group_valid & (c > 0)))
        else:
            results.append((red[a], group_valid if b is None
                            else group_valid & (red[b] > 0)))
    return rep, group_valid, results


presort_groups_jit = jax.jit(presort_groups)

sorted_segment_aggregate_jit = partial(
    jax.jit, static_argnames=("num_segments", "ops", "col_ids",
                              "all_valid", "interpret"))(
                                  sorted_segment_aggregate)
