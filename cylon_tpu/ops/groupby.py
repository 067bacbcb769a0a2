"""Group-by aggregation kernels — sort-based segmented reduction.

Replaces the reference's hash-map group-by (reference:
cpp/src/cylon/groupby/groupby_hash.hpp:28-359 — `unordered_map` with
compile-time `AggregateKernel<T,Op>{Init,Update,Finalize}`, and the
sorted-run pipeline variant groupby_pipeline.hpp:28-257) with the TPU
formulation: ONE fused sort groups the rows contiguously
(`presort_groups`), then group g is the g-th run and every aggregation
is a reduction over contiguous runs (`sorted_segment_aggregate`). On a
TPU with 4-byte accumulators that is ONE streaming Pallas pass with no
scatter (`tpu_kernels.groupby_run_reduce`: running reductions that
restart at each run start, run ends compacted to slot g); elsewhere —
the CPU, 8-byte accumulators under x64 — the portable path, which is
also the tests' oracle: `jax.ops.segment_*` over sorted ids, an XLA
scatter a stream that costs 8.8 ns a row on a v5e whatever the locality
(3,515 ms of groupby-q5's 4,467 before PR 26; PERF.md section 6).

What rides the sort (it is most of the query, and its time goes with its
operands: PERF.md section 6, PR 29): only what cannot be derived after
it. Always the key lanes (the sort keys), the value columns and the
validity masks that exist. A dead flag, as the primary key, only when the
table has a row mask (`emit` is read back off it, never carried). The
row index only when a caller needs the first ORIGINAL row of a group
(`sort_carries_index`): the `segment_*` path, an 8-byte or a varbytes
key. Otherwise the sorted key lanes themselves
are compacted to slot g by the reduce pass and the output key columns
are read off them (`order.from_ordered_bits_raw`), with no index, no
gather of the key column and no need for a stable sort. And what rides
takes as few operands as its OBSERVED ranges allow (`sort_pack_plan`,
PR 35): integer value columns narrow enough ride in the spare low bits
of the key's word, or several to a word, and are unpacked exactly after
the sort, inside the same program. The distributed groupby's per-shard
step (`parallel/dist_ops._groupby_fn`) is handed the same lists by the
same rules (PR 43).

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

from . import order as _order


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


# under this many rows a sort's packing is not looked for: the value
# probe's dispatch and fetch cost more than the operands they could save.
# From ONE sweep on a v5e (PERF.md section 6, PR 35): a packed groupby is
# 0.3-0.9 ms slower than a plain one at 2^20 rows and 0.4-1.3 ms faster
# at 2^21, whether one operand is saved or two; twice the level, and the
# power of two over it.
SORT_PACK_MIN_ROWS = 1 << 22

_WORD_BITS = 32
_KEY = -1      # the key's member id in a plan's word; value column i is i


def range_bits(lo: int, hi: int) -> int:
    """Bits that hold every ``x - lo`` for x in [lo, hi] (0 for lo = hi)."""
    return (hi - lo).bit_length()


def packs(dtype) -> bool:
    """Whether a column whose array is of ``dtype`` may ride inside
    another operand's word: an integer kind, bool and dictionary codes
    too, at most 32 bits wide. A float never does."""
    d = np.dtype(dtype)
    return d.kind in "iub" and d.itemsize * 8 <= _WORD_BITS


def sort_pack_plan(key_bits, value_bits):
    """WHICH operands the fused sort carries, from what the code observed
    (no knob): a tuple of words, each a tuple of the members that ride in
    it (``-1`` the key, ``i`` value column i), or None where no column
    shares a word, which is the sort as it always was.

    ``key_bits``: bits of the key's observed range, None when it was not
    observed (several key columns, a nullable, varbytes or 8-byte key).
    ``value_bits[i]``: bits of value column i's observed physical range,
    None for a column that cannot pack (`packs`) or was not probed. First
    fit in column order: the key's word first (the key keeps the HIGH
    bits, so the word sorts as the key does and the rows of one group
    fall in an order nobody reads), then the words opened by
    earlier columns; a column that fits nowhere opens a word of its own,
    and a word of one member rides as the column itself, untouched. A
    range that does not fit is never truncated: its column rides alone.

    The plan is the compiled program's only static part: offsets, shifts
    and masks go in as an array (`sort_pack_params`)."""
    words = []      # [members, bits used or None for a closed word]
    if key_bits is not None:
        words.append([[_KEY], key_bits])
    for i, bits in enumerate(value_bits):
        if bits is not None:
            home = next((w for w in words if w[1] is not None
                         and w[1] + bits <= _WORD_BITS), None)
            if home is not None:
                home[0].append(i)
                home[1] += bits
                continue
        words.append([[i], bits])
    if all(len(members) == 1 for members, _ in words):
        return None
    return tuple(tuple(members) for members, _ in words)


def packed_members(plan) -> int:
    """Value columns that ride inside another operand's word (counted as
    ``cylon_groupby_sort_packed_columns_total``): a shared word's members
    but its first, which is the operand they ride in."""
    return sum(len(word) - 1 for word in plan or ())


def key_lane_lo(lo: int, dtype, is_string: bool) -> int:
    """The ordered lane (`order.ordered_bits_raw`) of the key value
    ``lo``, on the host: a signed key's sign bit flipped; an unsigned or
    bool key and dictionary codes as they are."""
    d = np.dtype(dtype)
    signed = d.kind == "i" and not is_string
    return lo + (1 << (8 * d.itemsize - 1)) if signed else lo


def sort_pack_params(plan, key_lo: int, key_bits, value_lo, value_bits):
    """The numbers of ``plan`` as ONE uint32 array (one transfer; two
    tables of one plan share one program): a row (offset, shift, mask) a
    member of a shared word, in the plan's order. Offsets are in the
    ordered-lane domain (`_lane32`: ``key_lo`` is the key LANE's lowest
    observed value). The key sits at the top of its word, every other
    member is stacked from bit 0 up in plan order."""
    rows = []
    for word in plan:
        if len(word) == 1:
            continue
        at = 0
        for m in word:
            if m == _KEY:
                rows.append((key_lo, _WORD_BITS - key_bits,
                             (1 << key_bits) - 1))
                continue
            bits = value_bits[m]
            # a member of no bits (lo = hi) holds nothing: any shift
            rows.append((value_lo[m], min(at, _WORD_BITS - 1),
                         (1 << bits) - 1))
            at += bits
        assert at + (key_bits if word[0] == _KEY else 0) <= _WORD_BITS, word
    return np.asarray(rows, np.uint32)


def _lane32(x):
    """An integer column of at most 32 bits as the uint32 lane that
    orders as it does (a key lane is one already)."""
    return _order.ordered_bits_raw(x).astype(jnp.uint32)


def _from_lane32(lane, dtype):
    """`_lane32`'s inverse, to a column of ``dtype``."""
    dt = np.dtype(dtype)
    return _order.from_ordered_bits_raw(
        lane.astype(_order._WIDTH_UINT[dt.itemsize]), dt)


def value_range_probe(values):
    """uint32[len(values), 2]: (lo, hi) of each integer column's PHYSICAL
    array as an ordered lane, over every row, masks ignored: a packing
    by these ranges holds dead and null rows' slots too, whatever they
    hold. One fused pass, ONE array to fetch."""
    return jnp.stack([jnp.stack([lane.min(), lane.max()])
                      for lane in map(_lane32, values)])


def pack_ranges_probe(key, emit, values):
    """``uint32[1 + len(values), 3]``, ONE array to fetch: `ranges_probe`'s
    row of the ONE key (lo, hi, empty: over the live rows, ``emit`` None
    for all of them), then `value_range_probe`'s (lo, hi, 0) a value
    column. For a sort whose key nothing has observed yet (across chips no
    dense check runs before it); correct on sharded arrays, where the
    min / max is the whole table's."""
    return jnp.concatenate([
        ranges_probe((key,), emit, (None,)),
        jnp.pad(value_range_probe(values), ((0, 0), (0, 1)))])


def sort_operand_count(keys, emit, values, valids, index: bool,
                       plan=None) -> int:
    """How many operands presort_groups hands the sort for these
    arguments: a pure function of what the host sees before it
    dispatches (counted there as ``cylon_groupby_sort_operands_total``).
    With a ``plan`` (`sort_pack_plan`) the value columns and a packed key
    count by its words."""
    carried = len(keys) + len(values) if plan is None else \
        len(plan) + len(keys) - (plan[0][0] == _KEY)
    return ((emit is not None) + carried
            + sum(v is not None for v in valids) + bool(index))


def _pack(plan, params, cols):
    """One operand a word of ``plan`` (``cols``: member id -> array): the
    column itself where it rides alone, else its members' ordered lanes,
    each less its offset, shifted into place."""
    words, row = [], 0
    for word in plan:
        if len(word) == 1:
            words.append(cols[word[0]])
            continue
        packed = 0
        for m in word:
            # wraps only for a dead row's key, outside the live rows'
            # range: its high bits fall off the top, the riders' stay
            packed = packed | ((_lane32(cols[m]) - params[row, 0])
                               << params[row, 1])
            row += 1
        words.append(packed)
    return words


def _unpack(plan, params, words_s, cols):
    """member id -> sorted column, off `_pack`'s sorted words: what an
    unpacked sort hands on, bit for bit."""
    cols_s, row = {}, 0
    for word, packed in zip(plan, words_s):
        if len(word) == 1:
            cols_s[word[0]] = packed
            continue
        for m in word:
            lane = ((packed >> params[row, 1]) & params[row, 2]) \
                + params[row, 0]
            cols_s[m] = _from_lane32(lane, cols[m].dtype)
            row += 1
    return cols_s


def presort_groups(keys: Tuple[jnp.ndarray, ...], emit,
                   values: Tuple[jnp.ndarray, ...],
                   valids: Tuple[jnp.ndarray, ...], index: bool = True,
                   plan=None, params=None):
    """ONE fused sort by the key lanes that carries every value column
    and every validity mask as operands, and nothing that can be derived
    after it. Output rows are grouped contiguously: group g is the g-th
    run of live rows, so the reduce step (sorted_segment_aggregate)
    works on runs.

    ``emit`` None says every row is live (a table with no row mask): no
    dead flag rides. With a mask the dead flag is the primary sort key
    (dead rows last; the join/sort kernels' trick) and the sorted emit
    is read back off it. ``valids`` entries may be None (all-valid
    column): None masks don't ride either; the aggregate reads them as
    "live row = valid". ``index`` (static; `sort_carries_index` decides)
    adds the row index as the last operand of a STABLE sort, so that a
    run's first sorted row is its first original row; without it the
    sorted key lanes go out in its place and the sort need not be
    stable (a group's rows arrive in any order). ``plan`` (static;
    `sort_pack_plan`, None: every column rides alone) packs integer
    columns into shared words by the offsets, shifts and masks of
    ``params`` (`sort_pack_params`) and unpacks them after the sort: what
    is handed on is what the unpacked sort hands on, only a group's rows
    in another order. Under `shard_map` too (the distributed groupby's
    per-shard step): the host observes the ranges of the whole sharded
    table BEFORE it dispatches, the plan is one for all shards and
    ``params`` a replicated operand.

    Returns (values_s, valids_s, emit_s, first_s, new_grp, n_groups):
    emit_s is None when emit was; first_s is the sorted row index, or
    the tuple of sorted key lanes when ``index`` is off; new_grp marks
    each live run's first SORTED row; n_groups is a device scalar (the
    caller's single host sync)."""
    n = keys[0].shape[0]
    nk, nv = len(keys), len(values)
    real_v = tuple(v for v in valids if v is not None)
    head = () if emit is None else ((~emit).astype(jnp.uint8),)
    tail = (jnp.arange(n, dtype=jnp.int32),) if index else ()
    if plan is None:
        carried = tuple(keys) + tuple(values)
    else:
        cols = dict(enumerate(values))
        if plan[0][0] == _KEY:      # an observed key is ONE lane
            cols[_KEY], = keys
        # the key's word, where it has one, is the first: the sort's key
        lanes = () if _KEY in cols else tuple(keys)
        carried = lanes + tuple(_pack(plan, params, cols))
    ops_in = head + carried + real_v + tail
    assert len(ops_in) == sort_operand_count(keys, emit, values, valids,
                                             index, plan)
    h = len(head)
    res = jax.lax.sort(ops_in, num_keys=h + nk, is_stable=index)
    carried_s = res[h:h + len(carried)]
    if plan is None:
        ks, values_s = carried_s[:nk], tuple(carried_s[nk:])
    else:
        cols_s = _unpack(plan, params, carried_s[len(lanes):], cols)
        ks = carried_s[:nk] if lanes else (cols_s[_KEY],)
        values_s = tuple(cols_s[i] for i in range(nv))
    it = iter(res[h + len(carried):h + len(carried) + len(real_v)])
    valids_s = tuple(None if v is None else next(it) for v in valids)
    # row differs from its predecessor on any key lane (row 0 = True);
    # dead rows are all last, so live rows form a prefix
    new_grp = jnp.zeros(n, dtype=bool).at[0].set(True)
    for k in ks:
        new_grp = new_grp | jnp.concatenate(
            [jnp.ones(1, bool), k[1:] != k[:-1]])
    emit_s = None
    if emit is not None:
        emit_s = res[0] == 0
        new_grp = new_grp & emit_s
    return (values_s, valids_s, emit_s, res[-1] if index else tuple(ks),
            new_grp, new_grp.sum(dtype=jnp.int32))


_STREAM_DTYPES = (jnp.int32, jnp.float32)

# what a caller puts in ``value_dtypes`` for an int64 column held as two
# word planes (x64 off): the dense table sums it exactly, wider than 64
# bits, from limbs the kernel cuts (`dense_aggregate_keys`); nothing else
# takes it
PLANES_INT64 = "int64-planes"


def _accumulator_dtypes(dtype, op: AggregationOp):
    """What sorted_segment_aggregate accumulates ``op`` over a ``dtype``
    column in (validity tallies are int32 whatever the column). A
    plane-held int64's SUM and MEAN accumulate int32 limbs."""
    canon = jax.dtypes.canonicalize_dtype
    if op == AggregationOp.COUNT:
        return (canon(jnp.int64),)
    if dtype is PLANES_INT64:
        return (np.dtype(np.int32),)
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


def sort_carries_index(key_lanes, key_spec, value_dtypes, ops, n: int,
                       interpret: bool = False) -> bool:
    """Whether presort_groups must carry the row index, from what the
    code can observe (no knob). False — the sorted key lanes ride out
    in the index's place — when the reduce step streams (``reduce_path``)
    and the output key columns can be read back off the lanes:
    ``key_spec`` names them (None: some key is varbytes, whose lanes are
    content hashes with no inverse) and no lane is wider than the
    kernel's 32 bits (an 8-byte key under x64)."""
    if key_spec is None or any(
            np.dtype(k.dtype).itemsize > 4 for k in key_lanes):
        return True
    return reduce_path(value_dtypes, ops, n, interpret) != "stream"


# the most slots the dense table may have: past it the no-sort pass
# (whose time goes with slots x rows) is handed back to the sort path.
# From ONE sweep on a v5e at 1e8 rows (PERF.md section 6, PR 34): 0.164
# ms a slot, level with the sort path's ~630 ms near 3,800 slots; half
# of that, and the power of two under it.
DENSE_MAX_SLOTS = 1024

_DENSE_OPS = (AggregationOp.SUM, AggregationOp.COUNT, AggregationOp.MEAN)


def group_path(key_dtypes, key_nullable, value_dtypes, ops, n: int,
               key_range=None) -> str:
    """HOW a local groupby groups its rows, decided before any sort from
    what the code can observe (no knob): ``"dense"`` — no sort, every
    row goes straight to slot ``key - lo`` of a small table
    (`dense_aggregate`) — or ``"sort"``, the fused sort and the reduce
    step over its runs.

    Static conditions first: every key column of an integer kind at most
    32 bits wide (bool and dictionary codes are; ``key_dtypes`` holds
    None for a varbytes key), every op a SUM, COUNT or MEAN, every
    summed column an int32, a float32 or a plane-held int64
    (``PLANES_INT64``) and every accumulator an int32 or a float32 (the
    kernel's lanes: under x64 a COUNT or a MEAN accumulates 8 bytes and
    sorts), 1 <= n < 2^30. Only when they hold are the keys' ranges worth
    observing: called with ``key_range`` None the answer is ``"dense"``
    for "probe it". With the OBSERVED ``key_range`` (hi - lo + 1 over
    the live rows, one number a key column, or the number itself for one
    key; a nullable key takes one slot more) it is ``"dense"`` while the
    PRODUCT of the keys' slots fits DENSE_MAX_SLOTS."""
    if not key_dtypes or any(k is None for k in key_dtypes):
        return "sort"
    for kd in map(np.dtype, key_dtypes):
        if kd.kind not in "iub" or kd.itemsize > 4:
            return "sort"
    if not 1 <= n < (1 << 30):
        return "sort"
    for dtype, op in zip(value_dtypes, ops):
        if op not in _DENSE_OPS:
            return "sort"
        if op != AggregationOp.COUNT and dtype is not PLANES_INT64 and (
                dtype is None or dtype not in _STREAM_DTYPES):
            return "sort"
        if any(a not in _STREAM_DTYPES
               for a in _accumulator_dtypes(dtype, op)):
            return "sort"
    if key_range is not None:
        ranges = key_range if isinstance(key_range, (list, tuple)) \
            else [key_range]
        slots = 1
        for r, nullable in zip(ranges, key_nullable):
            slots *= r + bool(nullable)
        if slots > DENSE_MAX_SLOTS:
            return "sort"
    return "dense"


def _reduce_segments(new_grp, emit_s, iota_s, subs, num_segments: int):
    """The portable reduce step: dense ids by a cumsum over the run
    starts, then one ``indices_are_sorted`` XLA scatter a stream.
    Returns ((rep,), group_valid, reductions)."""
    n = new_grp.shape[0]
    seg = jnp.cumsum(new_grp.astype(jnp.int32)) - 1
    first = iota_s
    if emit_s is not None:   # masked -> overflow slot
        seg = jnp.where(emit_s, seg, num_segments)
        first = jnp.where(emit_s, iota_s, n)
    rep = jnp.full(num_segments + 1, n, jnp.int32).at[seg].min(
        first, indices_are_sorted=True)[:num_segments]
    reducers = {"add": jax.ops.segment_sum, "min": jax.ops.segment_min,
                "max": jax.ops.segment_max}
    return (rep,), rep < n, {
        key: reducers[kind](x, seg, num_segments=num_segments + 1,
                            indices_are_sorted=True)[:num_segments]
        for key, (kind, x) in subs.items()}


def _reduce_runs(new_grp, emit_s, firsts, first_fill, subs,
                 num_segments: int, interpret: bool):
    """The streaming reduce step: every stream in ONE pass of
    tpu_kernels.groupby_run_reduce (no gather or scatter of n or of
    num_segments elements): each of ``firsts`` (int32) gives its run's
    first element, each of ``subs`` its reduction. Slots at and past the
    run count hold ``first_fill`` and each op's identity, as the segment
    path leaves them. Returns (firsts, group_valid, reductions)."""
    from . import tpu_kernels as tk

    n = new_grp.shape[0]
    keys = list(subs)
    nf = len(firsts)
    outs, count = tk.groupby_run_reduce(
        new_grp, jnp.ones(n, bool) if emit_s is None else emit_s,
        list(firsts) + [subs[k][1] for k in keys],
        ["first"] * nf + [subs[k][0] for k in keys], num_segments,
        interpret=interpret)
    live = jnp.arange(num_segments, dtype=jnp.int32) < count
    empty = {"add": lambda d: 0, "min": _max_of, "max": _min_of}
    return (tuple(jnp.where(live, o, jnp.int32(first_fill))
                  for o in outs[:nf]), live, {
        k: jnp.where(live, o, jnp.asarray(empty[subs[k][0]](o.dtype),
                                          o.dtype))
        for k, o in zip(keys, outs[nf:])})


def _lane_to_stream(lane):
    """A sorted key lane (unsigned, at most 32 bits) as the int32 stream
    the reduce kernel takes; `_stream_to_lane` undoes it."""
    return jax.lax.bitcast_convert_type(lane.astype(jnp.uint32), jnp.int32)


def _stream_to_lane(stream, dtype):
    return jax.lax.bitcast_convert_type(stream, jnp.uint32).astype(dtype)


def _key_columns(firsts, lane_dtypes, key_spec, group_valid):
    """The output key columns, off the compacted key lanes: per key
    column of ``key_spec`` one ordered-bits lane, inverted to the
    column's dtype, then its validity lane if the column is nullable."""
    it = (_stream_to_lane(f, d) for f, d in zip(firsts, lane_dtypes))
    return tuple(
        (_order.from_ordered_bits_raw(next(it), dtype, is_string),
         (next(it) != 0) & group_valid if nullable else None)
        for dtype, is_string, nullable in key_spec)


def _mean(total, count, group_valid):
    """MEAN's finalisation, shared by the sort and the dense path: the
    group's sum over its non-null count, both in the op's accumulator
    dtypes; (mean, valid)."""
    c = count.astype(jnp.float64)
    return total / jnp.maximum(c, 1), group_valid & (c > 0)


def sorted_segment_aggregate(new_grp, emit_s, first_s,
                             values_s: Tuple[jnp.ndarray, ...],
                             valids_s: Tuple[jnp.ndarray, ...],
                             num_segments: int,
                             ops: Tuple[AggregationOp, ...],
                             col_ids: Tuple[int, ...],
                             all_valid: Tuple[bool, ...],
                             key_spec=None,
                             interpret: bool = False):
    """Aggregate presorted value columns into per-group slots: group g
    (the g-th run that ``new_grp`` starts) lands in slot g. The first
    three arguments are presort_groups' outputs (``emit_s`` None: every
    row is live).

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

    ``key_spec`` None: ``first_s`` is the sorted row index, and the
    return is (rep_idx, group_valid, list_of_(agg_array, agg_valid)):
      rep_idx[g] = first ORIGINAL row index holding group g (n past
      the group count), agg arrays have shape [num_segments].
    ``key_spec`` a tuple of (numpy dtype, is_string, nullable), one a
    key column: the sort carried no index (`sort_carries_index`),
    ``first_s`` is the tuple of sorted key lanes, the reduce pass
    compacts them beside the reductions, and in rep_idx's place comes a
    tuple of (key data, key validity or None), one a key column, the
    data read off the ordered bits (a float key's -0.0 is +0.0).
    MEAN returns a float64 array; COUNT returns int64 of non-null values
    (Arrow count semantics) — float32 / int32 with x64 off."""
    n = new_grp.shape[0]
    stream = reduce_path([v.dtype for v in values_s], ops, n,
                         interpret) == "stream"
    assert stream or key_spec is None, "key lanes ride the stream path only"
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
        if stream or emit_s is None:
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

    if not stream:
        firsts, group_valid, red = _reduce_segments(
            new_grp, emit_s, first_s, subs, num_segments)
    else:
        # the index of a STABLE sort (a run's first sorted row is its
        # first original row), or the key lanes
        lanes, fill = ((first_s,), n) if key_spec is None else (
            [_lane_to_stream(k) for k in first_s], 0)
        firsts, group_valid, red = _reduce_runs(
            new_grp, emit_s, lanes, fill, subs, num_segments, interpret)

    results = []
    for op, a, b in plan:
        if op == AggregationOp.COUNT:
            results.append((red[b], group_valid))
        elif op == AggregationOp.MEAN:
            results.append(_mean(red[a], red[b], group_valid))
        else:
            results.append((red[a], group_valid if b is None
                            else group_valid & (red[b] > 0)))
    if key_spec is None:
        return firsts[0], group_valid, results
    return (_key_columns(firsts, [k.dtype for k in first_s], key_spec,
                         group_valid), group_valid, results)


def key_range_probe(key, emit, key_valid):
    """[lo, hi] of ``key`` over the rows that are live and whose key is
    not null (either mask may be None), in the key's own order (a bool
    key as 0 / 1); lo > hi when there is no such row. One fused pass
    over the key column: what `group_path` needs to see. ONE array of
    two: the host fetches it in one transfer, and `dense_aggregate`
    takes it as it is, on the device (two scalars sent back up cost a
    dense query 0.4 ms: PERF.md section 6, PR 34)."""
    k = key.astype(jnp.int32) if key.dtype == jnp.bool_ else key
    live = emit
    if key_valid is not None:
        live = key_valid if live is None else live & key_valid
    if live is None:
        return jnp.stack([k.min(), k.max()])
    top, bottom = _max_of(k.dtype), _min_of(k.dtype)
    return jnp.stack([
        jnp.where(live, k, jnp.asarray(top, k.dtype)).min(),
        jnp.where(live, k, jnp.asarray(bottom, k.dtype)).max()])


def _key_as_int32(key):
    """A key of an integer kind at most 32 bits wide as the int32 the
    dense kernel subtracts ``lo`` from (wrapping: a uint32 key keeps
    its bits, and differences of in-range keys are exact)."""
    if key.dtype == jnp.uint32:
        return jax.lax.bitcast_convert_type(key, jnp.int32)
    return key.astype(jnp.int32)


def dense_aggregate(key, key_valid, emit, lohi,
                    values: Tuple[jnp.ndarray, ...],
                    valids: Tuple[jnp.ndarray, ...], slots: int,
                    ops: Tuple[AggregationOp, ...],
                    col_ids: Tuple[int, ...], interpret: bool = False):
    """The groupby WITHOUT a sort (`group_path` says when): slot s of a
    table of ``slots`` holds the group whose key is ``lo + s``, for s in
    [0, key_range); a nullable key's null group sits in slot key_range.
    ``lohi`` is `key_range_probe`'s array as it left the device (lo > hi:
    no live row, one empty slot), so one program serves every range of
    a slot count. ONE pass of tpu_kernels.groupby_dense_reduce over the
    key and the value columns; a dead row (``emit``) reaches no slot, a
    null value no accumulator of its column (Arrow's count semantics,
    as `sorted_segment_aggregate`'s). Distinct reductions run once
    (``col_ids`` name the source columns, as there).

    Integer sums and counts are exact; a float sum (and MEAN's, which
    sums an int32 column as float32 too) is compensated: within about
    2^-24 of the exact sum of its float32 inputs, whatever the rows.

    Returns (key_data, key_validity or None, group_valid, results):
    key_data (slots,) in the key's dtype, group_valid marks the slots
    that hold a live row, results one (array, valid) an op."""
    from . import tpu_kernels as tk

    empty = lohi[0] > lohi[1]
    lo32 = _key_as_int32(lohi)
    lo = jnp.where(empty, jnp.int32(0), lo32[0])
    key_range = jnp.where(empty, jnp.int32(1),
                          lo32[1] - lo32[0] + jnp.int32(1))
    k32 = _key_as_int32(key)
    live_slots = key_range
    if key_valid is not None:
        k32 = jnp.where(key_valid, k32, lo + key_range)
        live_slots = key_range + jnp.int32(1)
    if emit is not None:
        k32 = jnp.where(emit, k32, lo - jnp.int32(1))

    streams, kinds, plan = _dense_streams(values, valids, ops, col_ids)
    rows, sums = tk.groupby_dense_reduce(k32, lo, live_slots, streams, kinds,
                                         slots, interpret=interpret)
    group_valid = rows > 0
    results, _ = _dense_results(rows, sums, plan, group_valid)
    slot = jnp.arange(slots, dtype=jnp.int32)
    # the null group's slot holds a key of the column's own (a
    # dictionary code has to stay inside its dictionary)
    kd = lo + jnp.where(slot == key_range, jnp.int32(0), slot)
    key_data = _key_from_int32(kd, key.dtype)
    key_validity = None if key_valid is None else \
        group_valid & (slot != key_range)
    return key_data, key_validity, group_valid, results


def _key_from_int32(kd, dtype):
    """`_key_as_int32`'s inverse."""
    if dtype == jnp.bool_:
        return kd != 0
    if dtype == jnp.uint32:
        return jax.lax.bitcast_convert_type(kd, jnp.uint32)
    return kd.astype(dtype)


def _dense_streams(values, valids, ops, col_ids, wide=()):
    """The distinct streams the dense kernel sums for these aggregates
    (``col_ids`` name the source columns: a stream two ops share runs
    once) and, an op, where its numbers are: (streams, kinds, plan), plan
    entries (op, total, count) with ``count`` None for the kernel's own
    row count. ``wide[j]`` says value j is a plane-held int64 column
    (``uint32[2, n]``): its stream is the array itself, a null row's
    words as zero."""
    streams, kinds, where_ = [], [], {}

    def stream(name, kind, make):
        if name not in where_:
            where_[name] = len(streams)
            streams.append(make())
            kinds.append(kind)
        return where_[name]

    def planes(arr, vmask):
        return arr if vmask is None else jnp.where(vmask[None, :], arr,
                                                   jnp.uint32(0))

    plan = []
    for j, (arr, vmask, op, cid) in enumerate(zip(values, valids, ops,
                                                  col_ids)):
        # rows a slot for an all-valid column: the kernel's own count
        count = None if vmask is None else stream(
            ("count", cid), "int", lambda: vmask.astype(jnp.int32))
        total = None
        if op != AggregationOp.COUNT and j < len(wide) and wide[j]:
            total = ("planes", stream(("planes", cid), "planes",
                                      lambda: planes(arr, vmask)))
        elif op != AggregationOp.COUNT:
            is_sum_int = op == AggregationOp.SUM and arr.dtype == jnp.int32
            total = stream(
                ("int" if is_sum_int else "float", cid),
                "int" if is_sum_int else "float",
                lambda: arr if vmask is None
                else jnp.where(vmask, arr, jnp.zeros((), arr.dtype)))
        plan.append((op, total, count))
    return streams, kinds, plan


def _dense_results(rows, sums, plan, group_valid):
    """(results, overflow): one (array, valid) an op of `_dense_streams`'
    plan, off the kernel's count and sums. A plane-held int64's SUM is
    its exact total as ``uint32[2, slots]`` planes; its MEAN that total
    over the exact count in ONE float32 division carried past 24 bits
    (`wideint.divide_float32`): within about 2^-24 of the true mean.
    The kernel's total is wider than 64 bits: ``overflow`` (an int32) has
    bit j set when op j's total, in some slot, is no int64 - the caller
    raises, nothing wrapped is handed on."""
    from . import wideint as W

    canon = jax.dtypes.canonicalize_dtype
    results, overflow = [], jnp.int32(0)
    for j, (op, total, count) in enumerate(plan):
        c = rows if count is None else sums[count]
        if op == AggregationOp.COUNT:
            results.append((c.astype(canon(jnp.int64)), group_valid))
        elif isinstance(total, tuple):
            w = sums[total[1]]
            overflow = overflow | (jnp.any(~W.fits_int64(w)).astype(
                jnp.int32) << (j % 31))
            t64 = (w[1], w[2])
            if op == AggregationOp.SUM:
                results.append((jnp.stack(t64), group_valid & (c > 0)))
            else:
                den = W.to_float32_pair(W.from_int32(jnp.maximum(c, 1)))
                results.append((W.divide_float32(W.to_float32_pair(t64),
                                                 den),
                                group_valid & (c > 0)))
        elif op == AggregationOp.MEAN:
            results.append(_mean(sums[total].astype(canon(jnp.float64)),
                                 c, group_valid))
        else:
            results.append((sums[total], group_valid & (c > 0)))
    return results, overflow


def ranges_probe(keys, emit, key_valids):
    """``uint32[len(keys), 3]``, ONE array to fetch: a row a key column,
    (lo, hi, empty) with lo and hi the bits of `key_range_probe`'s pair
    as an int32 (`_key_as_int32`) and ``empty`` 1 when no live row holds
    a key."""
    rows = []
    for key, kv in zip(keys, key_valids):
        lohi = key_range_probe(key, emit, kv)
        words = jax.lax.bitcast_convert_type(_key_as_int32(lohi),
                                             jnp.uint32)
        rows.append(jnp.stack([words[0], words[1],
                               (lohi[0] > lohi[1]).astype(jnp.uint32)]))
    return jnp.stack(rows)


def key_range_of(words, dtype) -> tuple:
    """(lo, hi) of a key column of ``dtype`` as Python integers from its
    row of `ranges_probe` on the host; lo > hi when ``empty`` is set."""
    lo, hi, empty = (int(w) for w in words[:3])
    if empty:
        return 1, 0
    if np.dtype(dtype) != np.uint32:     # the words are an int32's bits
        lo, hi = (w - (1 << 32) if w >> 31 else w for w in (lo, hi))
    return lo, hi


def dense_aggregate_keys(keys, key_valids, emit, ranges,
                         values: Tuple[jnp.ndarray, ...],
                         valids: Tuple[jnp.ndarray, ...], slots: int,
                         ops: Tuple[AggregationOp, ...],
                         col_ids: Tuple[int, ...],
                         wide: Tuple[bool, ...] = (),
                         interpret: bool = False):
    """`dense_aggregate` over SEVERAL key columns and over plane-held
    int64 value columns. Key column i spans ``range_i`` slots (one more
    when it is nullable: its null group, last), and a row's slot is its
    keys' digits in that mixed radix, the first key the most significant:
    slot order is key order, nulls last. ``ranges`` is `ranges_probe`'s
    array as it left the device, so one program serves every set of
    ranges whose slots round to ``slots`` (the kernel is handed the
    composite slot with lo = 0).

    ``wide[j]`` says value column j is a plane-held int64
    (``uint32[2, n]``): the kernel cuts it into limbs and sums it exactly,
    whatever its values (nothing about them is observed first).

    Returns (key_columns, group_valid, results, overflow): key_columns
    one (data, validity or None) a key, (slots,) each; ``overflow`` as
    `_dense_results` gives it."""
    from . import tpu_kernels as tk

    nk = len(keys)
    i32 = lambda w: jax.lax.bitcast_convert_type(w, jnp.int32)
    rel = jnp.int32(0)
    total = jnp.int32(1)
    spans = []
    for i, (key, kv) in enumerate(zip(keys, key_valids)):
        empty = ranges[i, 2] != 0
        lo = jnp.where(empty, jnp.int32(0), i32(ranges[i, 0]))
        key_range = jnp.where(empty, jnp.int32(1),
                              i32(ranges[i, 1]) - i32(ranges[i, 0])
                              + jnp.int32(1))
        d = _key_as_int32(key) - lo
        span = key_range
        if kv is not None:
            d = jnp.where(kv, d, key_range)
            span = key_range + jnp.int32(1)
        rel = rel * span + d
        total = total * span
        spans.append((lo, key_range, span))
    if emit is not None:
        rel = jnp.where(emit, rel, jnp.int32(-1))
    streams, kinds, plan = _dense_streams(values, valids, ops, col_ids, wide)
    rows, sums = tk.groupby_dense_reduce(rel, jnp.int32(0), total, streams,
                                         kinds, slots, interpret=interpret)
    group_valid = rows > 0
    results, overflow = _dense_results(rows, sums, plan, group_valid)
    # a slot's digits, the last key's first
    slot = jnp.arange(slots, dtype=jnp.int32)
    key_columns = [None] * nk
    for i in reversed(range(nk)):
        lo, key_range, span = spans[i]
        d = slot % span
        slot = slot // span
        data = _key_from_int32(
            lo + jnp.where(d == key_range, jnp.int32(0), d), keys[i].dtype)
        key_columns[i] = (data, None if key_valids[i] is None
                          else group_valid & (d != key_range))
    return tuple(key_columns), group_valid, tuple(results), overflow


presort_groups_jit = partial(jax.jit, static_argnames=("index", "plan"))(
    presort_groups)

sorted_segment_aggregate_jit = partial(
    jax.jit, static_argnames=("num_segments", "ops", "col_ids",
                              "all_valid", "key_spec", "interpret"))(
                                  sorted_segment_aggregate)
