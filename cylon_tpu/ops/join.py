"""Local join kernels — vectorized sort-merge join with static shapes.

Replaces the reference's three local join paths (reference:
cpp/src/cylon/join/join.cpp:77-540 — `do_sorted_join`,
`do_inplace_sorted_join`, `do_hash_join` with the multimap kernel in
arrow_hash_kernels.hpp:48-225) with ONE TPU-idiomatic algorithm:

1. key columns of both tables are mapped to shared dense integer ids
   (ops/order.dense_ranks_two — a single fused device sort);
2. because the ids are DENSE, per-left-row match ranges come from one
   fused sort + prefix-scans (`_match_lo_m`) and duplicate expansion from
   run-head scatters + cumsum + gathers — no binary search, no
   duplicate-index scatter, no cumulative max (all three are TPU
   pathologies; see the kernel-block comment below);
3. output size is data-dependent, so materialization is two-phase
   (count → allocate static capacity → gather), the XLA static-shape
   discipline described in SURVEY §7.

`JoinConfig.algorithm` SORT lowers to the key-sort kernels;
HASH lowers to the hash-stream path (`hash_stream_applicable` /
`plan_program_stream(hash_mode=True)`): rows sort by a 2x32-bit row hash
— two operands regardless of key arity — with true key bits as verify
lanes and an exact XLA-plan fallback on any detected collision. A scalar
VMEM build/probe table was considered and rejected: random single-
element inserts/probes are scalar-unit work (~30 cycles/row — 0.5 s for
a 16M-row probe side, worse than the ENTIRE sort path), which is why the
reference's multimap design (arrow_hash_kernels.hpp:48-225) has no
profitable literal TPU translation.

All kernels accept "emit" row-validity masks so padded rows (from pow2
capacity rounding or from sharded shuffles) flow through without host
round-trips.
"""
from __future__ import annotations

import enum
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..data.column import is_word_planes, join_planes, split_planes
from ..util import bucket_cap as _bucket_cap, pow2 as _pow2


class JoinType(enum.IntEnum):
    """Reference: join/join_config.hpp:22 `JoinType`, which stops at
    FULL_OUTER. SEMI and ANTI are additions (the reference has neither):
    the LEFT semi and LEFT anti join, SQL's ``EXISTS`` and ``NOT EXISTS``.
    SEMI keeps a left row when at least one live right row has an equal,
    non-null key; ANTI keeps it when none has (a left row whose key is
    null is kept by ANTI and dropped by SEMI; null keys on the right
    match nothing). Each kept row comes out once whatever the number of
    matches, and only the left's columns."""

    INNER = 0
    LEFT = 1
    RIGHT = 2
    FULL_OUTER = 3
    SEMI = 4
    ANTI = 5


def is_semi(join_type: JoinType) -> bool:
    """The two kinds whose result is a subset of the left rows."""
    return join_type in (JoinType.SEMI, JoinType.ANTI)


class JoinAlgorithm(enum.IntEnum):
    """Reference: join/join_config.hpp:25 `JoinAlgorithm` (SORT/HASH).
    AUTO is an extension: pick the fastest applicable path — sort-stream
    for single 4-byte keys, hash-stream for multi-column/wide keys
    (measured 7.3x over the XLA plan at 16M x 16M two-key rows on v5e),
    XLA plan otherwise."""

    SORT = 0
    HASH = 1
    AUTO = 2


class JoinConfig:
    """Reference: join/join_config.hpp:29-89. Accepts single ints or lists
    of column indices (multi-column keys are first-class here)."""

    def __init__(self, join_type: JoinType, left_column_idx, right_column_idx,
                 algorithm: JoinAlgorithm = JoinAlgorithm.SORT,
                 exact: bool = False):
        self.type = join_type
        self.algorithm = algorithm
        self.left_column_idx = _as_list(left_column_idx)
        self.right_column_idx = _as_list(right_column_idx)
        # opt-in byte-verification of hash-identified varbytes keys
        # (keys <= EXACT_KEY_WORDS are byte-exact by construction; long
        # keys join on the 96-bit content hash unless exact=True)
        self.exact = exact

    @staticmethod
    def InnerJoin(l, r, algorithm: JoinAlgorithm = JoinAlgorithm.SORT):
        return JoinConfig(JoinType.INNER, l, r, algorithm)

    @staticmethod
    def LeftJoin(l, r, algorithm: JoinAlgorithm = JoinAlgorithm.SORT):
        return JoinConfig(JoinType.LEFT, l, r, algorithm)

    @staticmethod
    def RightJoin(l, r, algorithm: JoinAlgorithm = JoinAlgorithm.SORT):
        return JoinConfig(JoinType.RIGHT, l, r, algorithm)

    @staticmethod
    def FullOuterJoin(l, r, algorithm: JoinAlgorithm = JoinAlgorithm.SORT):
        return JoinConfig(JoinType.FULL_OUTER, l, r, algorithm)

    @staticmethod
    def SemiJoin(l, r, algorithm: JoinAlgorithm = JoinAlgorithm.SORT):
        return JoinConfig(JoinType.SEMI, l, r, algorithm)

    @staticmethod
    def AntiJoin(l, r, algorithm: JoinAlgorithm = JoinAlgorithm.SORT):
        return JoinConfig(JoinType.ANTI, l, r, algorithm)

    def GetType(self) -> JoinType:
        return self.type

    def GetAlgorithm(self) -> JoinAlgorithm:
        return self.algorithm

    def GetLeftColumnIdx(self):
        return self.left_column_idx

    def GetRightColumnIdx(self):
        return self.right_column_idx


def _as_list(v):
    if isinstance(v, (list, tuple)):
        return list(v)
    return [int(v)]


# ---------------------------------------------------------------------------
# Kernels. Inputs:
#   gl, gr : int32 dense key ids on a shared id space (>= 0); rows whose key
#            must never match carry a negative sentinel (-1 left, -2 right).
#   lemit, remit : bool masks — rows eligible for emission (False for padding).
#
# NO jnp.searchsorted anywhere: its binary-search lowering is pathologically
# slow on TPU (measured ~4 s per 16M×16M call vs 0.14 s for a full sort).
# Equally banned: duplicate-index scatters (segment_sum over gid buckets —
# minutes at 16M) and associative_scan(maximum) (215 s COMPILE at 2M).
# Everything below is sorts, cumsums, gathers and unique-index scatters.
# ---------------------------------------------------------------------------

def _match_lo_m(ga, gb) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-a-row match info against b: lo[i] = #b-rows with gid < ga[i]
    (= start of the equal-gid run in gid-sorted b order), m[i] = #b-rows
    with gid == ga[i].

    One fused 3-operand sort with b ordered BEFORE a inside each gid run,
    so at every a position the inclusive b-prefix count minus the count at
    the run start IS the run's b total. Scatter-backs hit unique
    destinations (TPU serializes duplicate-index scatters; segment_sum over
    a gid-sized bucket array was measured minutes-slow at 16M rows —
    everything here is sort/scan/gather/unique-scatter).
    Sentinel gids (negative, side-distinct) never match across sides."""
    na, nb = ga.shape[0], gb.shape[0]
    n = na + nb
    if n == 0 or na == 0:
        return jnp.zeros(na, jnp.int32), jnp.zeros(na, jnp.int32)
    g = jnp.concatenate([ga, gb])
    side = jnp.concatenate([jnp.ones(na, jnp.int32),
                            jnp.zeros(nb, jnp.int32)])
    iota = jnp.arange(n, dtype=jnp.int32)
    g_s, side_s, idx_s = jax.lax.sort((g, side, iota), num_keys=2)
    is_b = side_s == 0
    cum_b = jnp.cumsum(is_b.astype(jnp.int32))  # inclusive prefix b-count
    neq = jnp.zeros(n, bool).at[0].set(True)
    neq = neq.at[1:].set(g_s[1:] != g_s[:-1])
    # run_start[p] = position of p's run head. NOT a cumulative max —
    # associative_scan(maximum) compiles catastrophically slowly on TPU
    # (measured 215 s compile at 2M rows); run ids are cumsum(neq), run
    # heads scatter to unique slots, and a gather broadcasts them back.
    run_id = jnp.cumsum(neq.astype(jnp.int32)) - 1
    first_pos = jnp.zeros(n, jnp.int32).at[
        jnp.where(neq, run_id, n)].set(iota, mode="drop")
    run_start = jnp.take(first_pos, run_id)
    b_before = jnp.take(cum_b, run_start) - \
        jnp.take(is_b.astype(jnp.int32), run_start)
    m_at = cum_b - b_before  # valid at a positions: run b's all precede
    dest = jnp.where(is_b, na, idx_s)
    lo = jnp.zeros(na, jnp.int32).at[dest].set(b_before, mode="drop")
    m = jnp.zeros(na, jnp.int32).at[dest].set(m_at, mode="drop")
    return lo, m


def _masked_indices(mask, out_size: int) -> jnp.ndarray:
    """Positions of True values in order, padded with −1 to out_size.
    Sort-based (stable sort by ~mask) — jnp.nonzero's lowering is scatter-
    heavy and ignores fill_value on empty operands."""
    n = mask.shape[0]
    if n == 0:
        return jnp.full(out_size, -1, jnp.int32)
    iota = jnp.arange(n, dtype=jnp.int32)
    _, srt = jax.lax.sort(((~mask).astype(jnp.int32), iota), num_keys=1)
    cnt = mask.sum()
    j = jnp.arange(out_size, dtype=jnp.int32)
    idx = jnp.take(srt, j, mode="fill", fill_value=0)
    return jnp.where(j < cnt, idx, -1).astype(jnp.int32)


# ---------------------------------------------------------------------------
# plan / materialize. A join is TWO device programs separated by one
# 2-scalar host sync (the static-shape capacity decision):
#
#   plan:        key bits → match info (lo, m), key-sorted live-b
#                permutation, unmatched-b mask, output COUNTS — all from
#                ONE fused sort of the concatenated keys (see
#                `join_plan_keys`).
#   materialize: consumes the plan's DEVICE arrays — duplicate-run
#                expansion + payload gathers. No re-sorting: the expensive
#                match sort is computed once and reused across the phases.
#
# "A/B space": A is the probe side (left, or right for RIGHT joins so the
# same expansion kernel serves all types), B the build side.
# ---------------------------------------------------------------------------


def join_plan_keys(lbits, lkv, lemit, rbits, rkv, remit,
                   join_type: JoinType):
    """Traceable single-sort join plan.

    Replaces a dense-rank sort + match sort + b-permutation sort (three
    33M-element device sorts at bench scale) with ONE fused sort over the
    concatenated keys. Dead rows (not emitted, or null key) get their key
    bits FORCED to the all-ones maximum so they sink to the tail runs, and
    one packed u32 tag operand `side<<31 | live<<29 | iota` replaces the
    old (class, side, iota) triple — the sort moves 2 operands instead of
    4, and within a key run build (b) rows (tag bit31=0) sort before probe
    (a) rows, so at any a position the inclusive live-b prefix count minus
    the count at the run head IS the run's match count. Live rows whose
    keys are genuinely all-ones share the dead run harmlessly: match
    counts only ever count LIVE opposite-side rows, and dead rows' m is
    zeroed in a-space after the scatter.

    Profiling note (TPU v5e): XLA gathers/scatters cost ~10-15 ns/element
    regardless of locality, so this plan's cost model counts them — it
    spends 1 sort + 2 cumsums + 1 gather + 4 scatters (FULL_OUTER adds 2
    gathers + 1 scatter).

    Returns (counts2, lo, m, bperm, un_mask): counts2 = [n_primary,
    n_unmatched_b] (int64 under x64, else int32); lo[i]/m[i] = start and
    length of probe row i's match run inside `bperm` (the key-ordered
    compaction of live build rows, original indices); un_mask marks
    emitted build rows with no match (FULL_OUTER only).
    """
    if join_type == JoinType.RIGHT:
        abits, akv, aemit = rbits, rkv, remit
        bbits, bkv, bemit = lbits, lkv, lemit
    else:
        abits, akv, aemit = lbits, lkv, lemit
        bbits, bkv, bemit = rbits, rkv, remit
    na, nb = aemit.shape[0], bemit.shape[0]
    n = na + nb
    cdt = jnp.int64 if jax.config.jax_enable_x64 else jnp.int32

    if na == 0 or n == 0:
        if join_type == JoinType.FULL_OUTER:
            un_mask = bemit
            n_un = un_mask.sum(dtype=cdt)
        else:
            un_mask = jnp.zeros(nb, bool)
            n_un = jnp.zeros((), cdt)
        counts2 = jnp.stack([jnp.zeros((), cdt), n_un])
        z = jnp.zeros(na, jnp.int32)
        return counts2, z, z, jnp.zeros(nb, jnp.int32), un_mask

    assert n < (1 << 29), "per-shard row count must fit the 29-bit tag"
    live_a = aemit & akv
    live_b = bemit & bkv
    live = jnp.concatenate([live_a, live_b])
    iota = jnp.arange(n, dtype=jnp.uint32)
    tag = (jnp.concatenate([jnp.full(na, jnp.uint32(1 << 31)),
                            jnp.zeros(nb, jnp.uint32)])
           | (live.astype(jnp.uint32) << 29) | iota)
    bits = []
    for x, y in zip(abits, bbits):
        b = jnp.concatenate([x, y])
        allones = jnp.asarray(~np.uint64(0) >> (64 - 8 * b.dtype.itemsize),
                              b.dtype)
        bits.append(jnp.where(live, b, allones))
    res = jax.lax.sort(tuple(bits) + (tag,), num_keys=1 + len(bits))
    bits_s, tag_s = res[:-1], res[-1]

    is_a = (tag_s >> 31) == 1
    live_s = (tag_s >> 29) & 1
    idx_s = (tag_s & jnp.uint32((1 << 29) - 1)).astype(jnp.int32)
    ib = jnp.where(~is_a, live_s, 0).astype(jnp.int32)
    cum_b = jnp.cumsum(ib)
    neq_tail = jnp.zeros(n - 1, bool)
    for k in bits_s:
        neq_tail = neq_tail | (k[1:] != k[:-1])
    neq = jnp.concatenate([jnp.ones(1, bool), neq_tail])
    run_id = jnp.cumsum(neq.astype(jnp.int32)) - 1
    # live-b count before each run, broadcast via run heads (scatter to
    # unique head slots + gather by run id — never a cumulative max)
    head_b = jnp.zeros(n, jnp.int32).at[
        jnp.where(neq, run_id, n)].set(cum_b - ib, mode="drop")
    b_before = jnp.take(head_b, run_id)
    m_at = cum_b - b_before  # valid at a positions: run b's all precede

    dest_a = jnp.where(is_a, idx_s, na)
    semi = is_semi(join_type)
    if not semi:
        lo = jnp.zeros(na, jnp.int32).at[dest_a].set(b_before, mode="drop")
    m = jnp.zeros(na, jnp.int32).at[dest_a].set(m_at, mode="drop")
    # dead a rows sharing the all-ones run with live max-key b rows must
    # not match them
    m = jnp.where(live_a, m, 0)
    if semi:
        # a semi / anti join reads m alone: nothing is expanded, so no
        # run start and no build permutation
        kept = aemit & ((m > 0) if join_type == JoinType.SEMI else (m == 0))
        counts2 = jnp.stack([kept.sum(dtype=cdt), jnp.zeros((), cdt)])
        return (counts2, jnp.zeros(na, jnp.int32), m,
                jnp.zeros(nb, jnp.int32), jnp.zeros(nb, bool))
    bperm = jnp.zeros(nb, jnp.int32).at[
        jnp.where(ib == 1, cum_b - 1, nb)].set(idx_s - na, mode="drop")

    # accumulate counts in int64 (where x64 is enabled) so >2^31-pair
    # outputs don't silently wrap before the host capacity decision
    if join_type == JoinType.INNER:
        n_primary = m.sum(dtype=cdt)
    else:
        n_primary = jnp.where(aemit, jnp.maximum(m, 1), 0).sum(dtype=cdt)
    if join_type == JoinType.FULL_OUTER:
        ia = jnp.where(is_a, live_s, 0).astype(jnp.int32)
        cum_a = jnp.cumsum(ia)
        head_a = jnp.zeros(n + 1, jnp.int32).at[
            jnp.where(neq, run_id, n + 1)].set(cum_a - ia, mode="drop")
        nruns = run_id[-1] + 1
        head_a = head_a.at[nruns].set(cum_a[-1], mode="drop")
        # live-a total of each run = next run's prefix minus this run's
        m_b_at = jnp.take(head_a, run_id + 1) - jnp.take(head_a, run_id)
        dest_b = jnp.where(is_a, nb, idx_s - na)
        mb = jnp.zeros(nb, jnp.int32).at[dest_b].set(m_b_at, mode="drop")
        # dead b rows in the shared all-ones run are unmatched by fiat
        un_mask = bemit & (jnp.where(live_b, mb, 0) == 0)
        n_un = un_mask.sum(dtype=cdt)
    else:
        un_mask = jnp.zeros(nb, bool)
        n_un = jnp.zeros((), cdt)
    counts2 = jnp.stack([n_primary, n_un])
    return counts2, lo, m, bperm, un_mask


def join_plan_gids(gl, gr, lemit, remit, join_type: JoinType):
    """Plan from precomputed shared dense key ids (compat wrapper over
    `join_plan_keys`): negative gids are null sentinels that never match."""
    sb = jnp.uint32(1 << 31)
    return join_plan_keys(
        (gl.astype(jnp.uint32) ^ sb,), gl >= 0, lemit,
        (gr.astype(jnp.uint32) ^ sb,), gr >= 0, remit, join_type)


def _expand_from_match(lo, m, aemit, bperm, out_size: int,
                       emit_unmatched_a: bool
                       ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Emit (a_idx, b_idx) pairs from precomputed match info, padded to
    ``out_size`` with (-1, -1).

    B rows of a key occupy a contiguous run of the key-sorted b permutation
    starting at lo; a row i's j-th output picks run slot j − first_output_i.
    The j→i map: scatter a 1 at each emitting run's start (unique slots),
    cumsum ranks positions into ordinal runs, and a gather through the
    compacted emitting-row list recovers i — no cumulative max (215 s
    COMPILE at 2M) and no binary search.

    Per-row plan values (a-row index, packed lo − starts & has-match) are
    compacted into one (na, 2) matrix so the output-sized re-gather is ONE
    packed row gather, not three scalar gathers — gathers cost ~10-15
    ns/element on TPU regardless of width and dominate this kernel."""
    na, nb = lo.shape[0], bperm.shape[0]
    if na == 0:
        e = jnp.full(out_size, -1, jnp.int32)
        return e, e
    mm = jnp.where(aemit & emit_unmatched_a, jnp.maximum(m, 1), m)
    off = jnp.cumsum(mm)
    total = off[-1]
    starts = off - mm
    # bpos = lo[i] + (j - starts[i]) = j + delta[i]; two's-complement
    # arithmetic keeps (x*2+bit)>>1 == x for negative deltas. The *2
    # packing halves the int32 range, so past 2^30 output rows fall back
    # to separate (delta, has) gathers instead of silently wrapping.
    pack_ok = out_size < (1 << 30) and nb < (1 << 30)

    aiota = jnp.arange(na, dtype=jnp.int32)
    erank = jnp.cumsum((mm > 0).astype(jnp.int32))  # inclusive
    slot = jnp.where(mm > 0, erank - 1, na)
    emit_list = jnp.zeros(na, jnp.int32).at[slot].set(aiota, mode="drop")
    z = jnp.zeros(out_size, jnp.int32)
    z = z.at[jnp.where(mm > 0, starts, out_size)].set(1, mode="drop")
    c = jnp.cumsum(z)  # 1-based ordinal of the run covering position j
    ord_safe = jnp.maximum(c - 1, 0)

    j = jnp.arange(out_size, dtype=jnp.int32)
    if pack_ok:
        delta2 = (lo - starts) * 2 + (m > 0)
        # compact delta2 alongside emit_list (two unique-slot scatters —
        # a packed 2-column scatter is slow on TPU, packed GATHER is fast)
        delc = jnp.zeros(na, jnp.int32).at[slot].set(delta2, mode="drop")
        pair = jnp.stack([emit_list, delc], axis=1)  # (na, 2)
        g = jnp.take(pair, ord_safe, axis=0, mode="clip")
        i, d2 = g[:, 0], g[:, 1]
        has = (d2 & 1) == 1
        d = d2 >> 1
    else:
        delta = lo - starts
        has_m = m > 0
        i = jnp.take(emit_list, ord_safe, mode="clip")
        d = jnp.take(delta, i)
        has = jnp.take(has_m, i)
    if nb == 0:
        bidx = jnp.full(out_size, -1, jnp.int32)
    else:
        bpos = j + d
        bidx = jnp.take(bperm, bpos, mode="fill", fill_value=0)
        bidx = jnp.where(has, bidx, -1)
    valid = j < total
    aidx = jnp.where(valid, i, -1)
    bidx = jnp.where(valid, bidx, -1)
    return aidx, bidx


def join_materialize_gids(lo, m, bperm, un_mask, aemit,
                          join_type: JoinType, cap_p: int, cap_u: int
                          ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Traceable (lidx, ridx, emit) at static capacity from a plan's
    arrays. emit marks live output rows; padding carries (-1, -1, False)."""
    aidx, bidx = _expand_from_match(lo, m, aemit, bperm, cap_p,
                                    join_type != JoinType.INNER)
    if join_type == JoinType.FULL_OUTER:
        un = _masked_indices(un_mask, cap_u)
        aidx = jnp.concatenate([aidx, jnp.full(cap_u, -1, jnp.int32)])
        bidx = jnp.concatenate([bidx, un])
    if join_type == JoinType.RIGHT:
        lidx, ridx = bidx, aidx
    else:
        lidx, ridx = aidx, bidx
    return lidx, ridx, (lidx >= 0) | (ridx >= 0)


# ---------------------------------------------------------------------------
# Pallas streaming plan path. The XLA plan above spends ~2 s at 33M rows in
# latency-bound scatter/gather passes (head broadcast + a/b-space
# scatter-backs); the streaming kernel (ops/tpu_kernels.join_plan_stream)
# fuses everything after the key sort into ONE sequential HBM pass and
# emits the expansion plan directly in compacted form. Applicability:
# single u32 key, INNER/LEFT/RIGHT (FULL_OUTER needs a backward pass —
# falls back to the XLA plan), per-shard rows < 2^29.
# ---------------------------------------------------------------------------

# None = auto (TPU backend, or interpreter off-TPU when forced True);
# False disables; True forces (tests force it with the interpreter).
STREAM_PLAN: Optional[bool] = None


def stream_plan_applicable(lkeys, rkeys, str_flags,
                           join_type: JoinType) -> bool:
    """Host-side check over key arrays (pre-ordered-bits): single 4-byte
    (or dictionary-string) key, or a single 8-byte key held as word
    planes on both sides (data/column.py: two exact key lanes, no hash);
    INNER/LEFT/RIGHT/SEMI/ANTI, both sides non-empty."""
    if STREAM_PLAN is False or join_type == JoinType.FULL_OUTER:
        return False
    if len(lkeys) != 1:
        return False

    def width(x, is_str):
        if is_word_planes(x):
            return 8
        return 4 if is_str else np.dtype(x.dtype).itemsize

    wl, wr = width(lkeys[0], str_flags[0]), width(rkeys[0], str_flags[0])
    planes = is_word_planes(lkeys[0]) and is_word_planes(rkeys[0])
    if not (planes or (wl == 4 and wr == 4)) \
            or (not str_flags[0] and lkeys[0].dtype == jnp.bool_):
        return False
    na, nb = lkeys[0].shape[-1], rkeys[0].shape[-1]
    if na == 0 or nb == 0 or na + nb >= (1 << 29):
        return False
    if STREAM_PLAN:
        return True
    return jax.default_backend() == "tpu"


# sort-operand budget for the hash path: 2 hash keys + tag + key-verify
# lanes + shared payload lanes
MAX_HASH_KEY_LANES = 6


def _key_lane_count(x, is_str) -> int:
    if is_str:
        return 1
    if x.dtype == jnp.bool_:
        return 1
    return 2 if is_word_planes(x) or np.dtype(x.dtype).itemsize == 8 else 1


def hash_stream_applicable(lkeys, rkeys, str_flags,
                           join_type: JoinType) -> bool:
    """The hash-join stream path covers what the single-key path can't:
    multi-column and wide keys. Rows sort by a 2x32-bit row hash (2
    operands however many key columns), true key bits ride as verify
    lanes, and the plan kernel counts within-run key mismatches — a
    nonzero count means a 64-bit hash collision and the caller recomputes
    via the exact XLA plan (reference hash join: arrow_hash_kernels.hpp
    :48-225, where the multimap probe re-checks true keys the same way).
    """
    if STREAM_PLAN is False or join_type == JoinType.FULL_OUTER:
        return False
    if any(is_word_planes(x) for x in tuple(lkeys) + tuple(rkeys)):
        return False  # word planes: the sort path (one key) or the XLA plan
    na, nb = lkeys[0].shape[0], rkeys[0].shape[0]
    if na == 0 or nb == 0 or na + nb >= (1 << 29):
        return False
    kl = sum(_key_lane_count(x, s) for x, s in zip(lkeys, str_flags))
    if kl > MAX_HASH_KEY_LANES:
        return False
    if STREAM_PLAN:
        return True
    return jax.default_backend() == "tpu"


# Shared sort-payload slot budget: each slot adds one u32 operand to the
# fused plan sort, and the sort's time goes with the NUMBER of operands
# (PERF.md section 6: ~136 ms a 4-byte operand at 1e8 rows on v5e).
# Columns beyond the budget fall back to aidx/bidx gathers. The join key
# takes no slot where it rides as "k" (plan_lane_descs).
MAX_SHARED_LANES = 8


def plan_lane_descs(ldat, lval, rdat, rval, join_type: JoinType,
                    lkey: Optional[int] = None, rkey: Optional[int] = None,
                    wide_key: Optional[str] = None):
    """Static lane packing for the stream path: which columns ride the
    plan sort as u32 payload lanes. Slot s carries the probe side's lane
    s at probe rows and the build side's lane s at build rows, so the
    operand count is max(a, b) lanes, not the sum.

    Returns hashable (a_desc, b_desc): tuples of (col_idx, kind) with
    kind "d" (data, bit-exact u32 reinterpret), "v" (validity widened to
    u32) or "k" (THE join key: no operand of its own; the sorted key bits
    stand in its slot and the column is read back off them with
    order.from_ordered_bits_raw). A 64-bit column held as word planes
    (``uint32[2, n]``, data/column.py) rides as its two planes, "dh" and
    "dl", and as the key "kh" and "kl": the two sorted key lanes.
    4-byte 1-D non-bool columns and word planes qualify; the rest (a
    native 8-byte array under x64, bool) use the index-gather fallback in
    materialize. A column whose validity is None adds no "v" lane: the
    output's validity is then the side's hit mask.

    ``lkey`` / ``rkey``: the column of each side that is the join's ONE
    key and whose values the sort path's key bits were made from (the
    caller sees that: not a promoted copy, not dictionary codes, not the
    hash path). The key rides once only where the input allows it, on
    BOTH sides (slot 0 is shared, so it is the sorted bits for both or
    for neither): a signed or unsigned integer, 4 bytes wide or held as
    word planes of the logical dtype ``wide_key`` (its numpy name, else
    None; a float keeps its lane: its ordered bits fold -0.0 into +0.0 and
    the output must not)
    with validity None (then no emitted row had its bits forced to
    all-ones as a dead row). Otherwise the key rides as "d", as any
    other column.

    SEMI / ANTI: the build (right) side rides the sort with its key and
    tag alone, so its descriptor is () and no slot is shared: the key
    rides once where the LEFT side alone allows it."""
    if join_type == JoinType.RIGHT:
        adat, aval, akey, bdat, bval, bkey = rdat, rval, rkey, ldat, lval, lkey
    else:
        adat, aval, akey, bdat, bval, bkey = ldat, lval, lkey, rdat, rval, rkey

    def key_once(dat, val, key):
        if key is None or val[key] is not None:
            return False
        k = dat[key]
        if is_word_planes(k):
            return np.dtype(wide_key).kind in "iu"
        return k.dtype.itemsize == 4 and jnp.issubdtype(k.dtype, jnp.integer)

    semi = is_semi(join_type)
    once = key_once(adat, aval, akey) \
        and (semi or key_once(bdat, bval, bkey))

    def side(dat, val, key):
        head = ()
        if once:
            head = ((key, "kh"), (key, "kl")) if is_word_planes(dat[key]) \
                else ((key, "k"),)
        desc = []
        for ci, (d, v) in enumerate(zip(dat, val)):
            if once and ci == key:
                continue
            wide = is_word_planes(d)
            kinds = ("dh", "dl") if wide else ("d",)
            need = len(kinds) + (1 if v is not None else 0)
            if ((wide or (d.ndim == 1 and d.dtype.itemsize == 4))
                    and d.dtype != jnp.bool_
                    and len(desc) + need <= MAX_SHARED_LANES):
                desc.extend((ci, kind) for kind in kinds)
                if v is not None:
                    desc.append((ci, "v"))
        return head + tuple(desc)

    return side(adat, aval, akey), (() if semi else side(bdat, bval, bkey))


def plan_key_lane_count(keys, str_flags) -> int:
    """32-bit key lanes the join's plan sort compares for one side's key
    arrays (counted as ``cylon_join_key_lanes_total``): one for a 4-byte
    key, two for an 8-byte one, native or held as word planes."""
    return sum(_key_lane_count(x, s) for x, s in zip(keys, str_flags))


def plan_sort_operand_count(keys, str_flags, a_desc=None, b_desc=None,
                            hash_mode: bool = False) -> int:
    """How many operands the join's plan sort is handed for these
    arguments: a pure function of what the host sees before it
    dispatches (counted there as ``cylon_join_sort_operands_total``).
    ``keys``: one side's key arrays, raw or as ordered bits. With the
    lane descriptors it is the stream path's sort: key bits (two lanes
    for a key held as word planes) + tag + one operand a payload slot
    (the hash path: two hashes + tag + the key's verify lanes + the
    slots); without them the XLA plan's (`join_plan_keys`: the key bit
    arrays + tag). It counts what the program hands over: the stream
    path's sorts are not stable, so the compiler adds no index to them."""
    planes = sum(map(is_word_planes, keys))  # each is two bit arrays
    if a_desc is None:
        return len(keys) + planes + 1
    # a payload slot is the wider side's lane; the key as "k" takes none
    slots = max(sum(kind[0] != "k" for _, kind in desc)
                for desc in (a_desc, b_desc))
    if not hash_mode:
        return 2 + planes + slots
    return 3 + plan_key_lane_count(keys, str_flags) + slots


def plan_gathered_column_count(n_cols: int, a_desc=None, b_desc=None) -> int:
    """Output columns of a join over ``n_cols`` input columns (both
    sides) that are materialised through the index-gather fallback and
    not off a sort lane (counted as
    ``cylon_join_gathered_columns_total``): on the stream path those no
    lane descriptor names, on the XLA plan all of them. A SEMI or ANTI
    join's ``n_cols`` are the left side's alone (``b_desc`` is ()), and
    its XLA plan gathers none: the caller passes 0."""
    if a_desc is None:
        return n_cols
    return n_cols - sum(len({ci for ci, _ in desc})
                        for desc in (a_desc, b_desc))


def expand_sweep_rows(block_rows: Optional[int] = None) -> int:
    """Window rows ONE output vreg's a-side sweep visits in the expand
    kernel, on the path taken (counted as
    ``cylon_join_expand_sweep_rows_total``): the slab's sub-window on
    the stream path, whatever the block (before PR 33 the block's whole
    ``block_rows + 8`` window: 72 at 64); 0 on the XLA plan
    (``block_rows`` None), which materialises by gathers, and for a SEMI
    or ANTI join, which launches no expand kernel (its callers pass
    None)."""
    if block_rows is None:
        return 0
    from . import tpu_kernels as tk

    return tk.EXPAND_SWEEP_ROWS  # every block's window holds them


def stream_block_rows(na: int, nb: int) -> int:
    """ONE Pallas block-rows choice for plan AND expand (the expansion
    window slack requires expand block_rows <= plan block_rows): small
    inputs use small blocks — the kernel graphs (log-shift compaction,
    window sweeps) scale with the block span, and small-block variants
    trace/compile ~3x faster, which dominates interpreter-mode tests."""
    return 8 if (na + nb) < (1 << 20) else 64


def stream_expand_capacity(n: int, block_rows: int):
    """cap_e for join_expand_stream: the pow2-bucketed capacity lifted
    to a whole number of expansion blocks. cap_e is a jit cache-key
    parameter on both the local and the distributed stream path, so it
    routes through util.bucket_cap (1 bucket per octave) rather
    than the 16-per-octave mantissa rounding — the specialization
    analysis recognizes this helper as bucketing."""
    blk = block_rows * 128
    return -(-_bucket_cap(n) // blk) * blk


def _side_lanes(dat, val, desc):
    lanes = []
    for ci, kind in desc:
        if kind[0] == "k":
            continue  # rides as the sort's key bits, not as a lane
        if kind == "d":
            d = dat[ci]
            lanes.append(d if d.dtype == jnp.uint32 else d.view(jnp.uint32))
        elif kind == "v":
            lanes.append(val[ci].astype(jnp.uint32))
        else:  # "dh" / "dl": a plane of a 64-bit column
            hi, lo = split_planes(dat[ci])
            lanes.append(hi if kind == "dh" else lo)
    return lanes


def _plan_program_stream_impl(lkeys, lkvalid, lemit, rkeys, rkvalid, remit,
                              ldat, lval, rdat, rval,
                              str_flags, join_type: JoinType,
                              a_desc=(), b_desc=(), block_rows: int = 64,
                              hash_mode: bool = False,
                              interpret: bool = False,
                              wide_key: Optional[str] = None):
    """Phase 1 (stream path): raw key columns → sorted stream (payload
    lanes riding along) → Pallas plan pass that compacts the plan AND the
    payload into groups A/B. Only counts[4] crosses to the host.

    ``wide_key``: the logical 64-bit dtype name of THE key where it is
    held as word planes (`stream_plan_applicable` admits one such key),
    else None. It sorts as its two exact ordered lanes ``(hi, lo, tag)``,
    and the plan kernel finds its runs over both.

    hash_mode (the honest JoinAlgorithm.HASH): rows sort by a 2x32-bit
    row hash instead of raw key bits, so ANY key shape costs two sort
    operands; the true key bits ride as verify lanes and counts[3]
    reports within-run mismatches (64-bit hash collisions) for the
    caller's exact fallback.

    SEMI / ANTI: this ONE program is the whole join. The build side
    rides the sort with its key and tag alone (``b_desc`` is ()), the
    plan pass keeps each probe row at most once (its multiplicity is
    ``min(m, 1)`` or ``m == 0``) and compacts no build group, and the
    kept rows' columns are read off the compacted lanes here: returns
    ``(counts, lod, lov, emit, lidx)`` at the PROBE side's capacity, the
    row mask ``emit`` a prefix decided on the device. Nothing is
    expanded and no count has to reach the host."""
    from . import tpu_kernels as tk
    from .hash import hash2_streams

    lbits, lkv, rbits, rkv = _keys_to_bits(lkeys, lkvalid, rkeys, rkvalid,
                                           str_flags,
                                           (wide_key,) if wide_key else ())
    lemit = _vm(lemit, lkv.shape[0])
    remit = _vm(remit, rkv.shape[0])
    if join_type == JoinType.RIGHT:
        abits, akv, aemit = rbits, rkv, remit
        bbits, bkv, bemit = lbits, lkv, lemit
        adat, aval, bdat, bval = rdat, rval, ldat, lval
    else:
        abits, akv, aemit = lbits, lkv, lemit
        bbits, bkv, bemit = rbits, rkv, remit
        adat, aval, bdat, bval = ldat, lval, rdat, rval
    na, nb = aemit.shape[0], bemit.shape[0]
    n = na + nb

    live = jnp.concatenate([aemit & akv, bemit & bkv])
    emit = jnp.concatenate([aemit, bemit])
    iota = jnp.arange(n, dtype=jnp.uint32)
    tag = (jnp.concatenate([jnp.full(na, jnp.uint32(1 << 31)),
                            jnp.zeros(nb, jnp.uint32)])
           | (emit.astype(jnp.uint32) << 30)
           | (live.astype(jnp.uint32) << 29) | iota)

    # the join key rides once (plan_lane_descs): no lane of its own here,
    # the sorted key bits stand in slot 0 for both sides after the sort
    semi = is_semi(join_type)
    keep = {JoinType.SEMI: "semi", JoinType.ANTI: "anti"}.get(join_type)
    key_once = bool(a_desc) and a_desc[0][1][0] == "k"
    assert semi or key_once == (bool(b_desc) and b_desc[0][1][0] == "k")
    assert not (semi and b_desc), "a semi join reads no build column"
    assert not (key_once and hash_mode), "the hash path sorts by hashes"
    a_lanes = _side_lanes(adat, aval, a_desc)
    b_lanes = _side_lanes(bdat, bval, b_desc)
    lanes = []
    for s in range(max(len(a_lanes), len(b_lanes))):
        al = a_lanes[s] if s < len(a_lanes) else jnp.zeros(na, jnp.uint32)
        bl = b_lanes[s] if s < len(b_lanes) else jnp.zeros(nb, jnp.uint32)
        lanes.append(jnp.concatenate([al, bl]))

    allones = jnp.uint32(0xFFFFFFFF)
    if hash_mode:
        # flatten every key column into u32 lanes (8-byte bits split
        # hi/lo) and hash them into two independent 32-bit streams.
        # KNOWN trade-off, here only (the sort path's key rides once, as
        # the key bits: plan_lane_descs' "k"): a 4-byte key column rides
        # this sort twice, as a verify lane and as a payload lane. The
        # way back exists (order.from_ordered_bits_raw); what is missing
        # is the key→column map for several key columns, left until the
        # hash path shows up in a profile
        kb_lanes = []
        for a, b in zip(abits, bbits):
            cat = jnp.concatenate([a, b])
            if cat.dtype.itemsize == 8:
                kb_lanes.append((cat >> 32).astype(jnp.uint32))
                kb_lanes.append(cat.astype(jnp.uint32))
            else:
                kb_lanes.append(cat.astype(jnp.uint32))
        h1, h2 = hash2_streams(kb_lanes, live)
        # not stable, as the sort path's below: tag ends the keys and
        # holds the row's iota
        res = jax.lax.sort((h1, h2, tag) + tuple(kb_lanes) + tuple(lanes),
                           num_keys=3, is_stable=False)
        nk = len(kb_lanes)
        planned = tk.join_plan_stream(
            res[0], res[2], na, nb,
            emit_unmatched_a=join_type != JoinType.INNER,
            lanes=res[3 + nk:], n_a_lanes=len(a_lanes),
            n_b_lanes=len(b_lanes), bits2_s=res[1],
            verify_lanes=res[3:3 + nk],
            block_rows=block_rows, interpret=interpret, keep=keep)
        if semi:
            return _semi_result(planned, adat, aval, a_desc, na, wide_key)
        return planned

    # one bit array for a 4-byte key, (hi, lo) for one held as word planes
    assert len(abits) in (1, 2)
    bits = tuple(jnp.where(live, jnp.concatenate([a, b]), allones)
                 for a, b in zip(abits, bbits))
    nk = len(bits)
    # (bits, tag) is a total order (tag's low 29 bits are the row's iota),
    # so a sort that is not stable gives the same arrays bit for bit, and
    # XLA does not append an index operand of its own as it does for a
    # stable sort (PERF.md section 6: one 4-byte operand's time)
    res = jax.lax.sort(bits + (tag,) + tuple(lanes), num_keys=nk + 1,
                       is_stable=False)
    bits_s, tag_s, lanes_s = res[:nk], res[nk], res[nk + 1:]
    if key_once:
        lanes_s = tuple(bits_s) + tuple(lanes_s)
    planned = tk.join_plan_stream(
        bits_s[0], tag_s, na, nb,
        emit_unmatched_a=join_type != JoinType.INNER,
        lanes=lanes_s, n_a_lanes=len(a_desc), n_b_lanes=len(b_desc),
        bits2_s=bits_s[1] if nk == 2 else None,
        block_rows=block_rows, interpret=interpret, keep=keep)
    if semi:
        return _semi_result(planned, adat, aval, a_desc, na, wide_key)
    return planned


def _semi_result(planned, adat, aval, a_desc, na: int,
                 wide_key: Optional[str]):
    """The kept probe rows of a SEMI / ANTI plan pass as columns: group A
    of `tpu_kernels.join_plan_stream(keep=...)` holds, compacted in key
    order, each kept row's index and payload lanes; cut to the probe
    side's ``na`` slots they ARE the result, under the prefix row mask
    ``iota < n_kept``. Columns no lane carries gather by the index."""
    counts, a_streams, _ = planned
    emit = jnp.arange(na, dtype=jnp.int32) < counts[1]
    flat = [x.reshape(-1)[:na] for x in a_streams]
    lidx = jnp.where(emit, flat[0].astype(jnp.int32), -1)
    zero = jnp.uint32(0)   # the slots past the kept rows hold no row
    lanes = [jnp.where(emit, x, zero) for x in flat[1:]]
    lod, lov = _unpack_side(adat, aval, a_desc, lanes, emit, lidx, wide_key)
    return counts, lod, lov, emit, lidx


_plan_program_stream_jit = partial(
    jax.jit, static_argnames=("str_flags", "join_type", "a_desc", "b_desc",
                              "block_rows", "hash_mode", "interpret",
                              "wide_key"))(_plan_program_stream_impl)


def plan_program_stream(*args, interpret: bool = False, **kw):
    """Dispatch: compiled on TPU; EAGER under the interpreter (tests) —
    jitting the interpreted Pallas graph costs ~70 s of XLA CPU compile
    per shape variant, while eager execution of test-sized inputs is
    milliseconds."""
    if interpret:
        return _plan_program_stream_impl(*args, interpret=True, **kw)
    return _plan_program_stream_jit(*args, interpret=False, **kw)


def _unpack_side(dat, val, desc, lane_outs, hit, idx,
                 wide_key: Optional[str] = None):
    """One side's output columns from its lanes (``desc`` says which
    column and kind each lane is; `plan_lane_descs`), ``hit`` marking
    the rows that hold a row of this side and ``idx`` their source
    indices (-1 elsewhere), for the columns no lane carries."""
    from .order import from_ordered_bits_planes, from_ordered_bits_raw

    od: list = [None] * len(dat)
    ov: list = [None] * len(dat)
    planes: dict = {}  # column -> its "kh"/"kl" or "dh"/"dl" lanes
    for (ci, kind), lane in zip(desc, lane_outs):
        if kind in ("kh", "kl", "dh", "dl"):
            planes.setdefault(ci, {})[kind] = lane
        elif kind == "k":
            # the lane is the key's ordered bits; rows without a hit
            # read 0 as every lane does (the kernel zeroes them)
            od[ci] = jnp.where(
                hit, from_ordered_bits_raw(lane, dat[ci].dtype),
                jnp.zeros((), dat[ci].dtype))
            ov[ci] = hit
        elif kind == "d":
            od[ci] = lane if dat[ci].dtype == jnp.uint32 \
                else lane.view(dat[ci].dtype)
            if val[ci] is None:
                ov[ci] = hit
        else:
            ov[ci] = (lane != 0) & hit
    for ci, got in planes.items():
        if "kh" in got:  # the key's ordered lanes; see "k" above
            zero = jnp.uint32(0)
            od[ci] = join_planes(*(jnp.where(hit, w, zero) for w in
                                   from_ordered_bits_planes(
                                       got["kh"], got["kl"], wide_key)))
            ov[ci] = hit
        else:
            od[ci] = join_planes(got["dh"], got["dl"])
            if val[ci] is None:
                ov[ci] = hit
    fb = [ci for ci in range(len(dat)) if od[ci] is None]
    if fb:
        fbd, fbv = gather_columns(
            tuple(dat[ci] for ci in fb), tuple(val[ci] for ci in fb),
            idx)
        for k, ci in enumerate(fb):
            od[ci], ov[ci] = fbd[k], fbv[k]
    return tuple(od), tuple(ov)


def _materialize_program_stream_impl(counts, a_streams, b_streams,
                                     ldat, lval, rdat, rval,
                                     join_type: JoinType, cap_e: int,
                                     a_desc=(), b_desc=(),
                                     block_rows: int = 64,
                                     interpret: bool = False,
                                     wide_key: Optional[str] = None):
    """Phase 2 (stream path): compacted plan + payload lanes → output
    rows via the streaming expansion kernel. Returns (ldat', lval',
    rdat', rval', emit). Columns that rode sort lanes are unpacked from
    the kernel's lane outputs (zero output-sized XLA gathers; a 64-bit
    column's two lanes go back to ``uint32[2, cap_e]`` word planes, the
    key's through ``wide_key``, its logical dtype); the rest gather
    by the materialized aidx/bidx."""
    from . import tpu_kernels as tk

    aidx, bidx, a_lane_outs, b_lane_outs = tk.join_expand_stream(
        counts, a_streams, b_streams, cap_e, block_rows=block_rows,
        interpret=interpret)
    valid = aidx >= 0
    bhit = bidx >= 0
    lidx, ridx = (bidx, aidx) if join_type == JoinType.RIGHT else (aidx, bidx)

    if join_type == JoinType.RIGHT:
        adat, aval, bdat, bval = rdat, rval, ldat, lval
    else:
        adat, aval, bdat, bval = ldat, lval, rdat, rval

    aod, aov = _unpack_side(adat, aval, a_desc, a_lane_outs, valid, aidx,
                            wide_key)
    bod, bov = _unpack_side(bdat, bval, b_desc, b_lane_outs, bhit, bidx,
                            wide_key)
    if join_type == JoinType.RIGHT:
        lod, lov, rod, rov = bod, bov, aod, aov
    else:
        lod, lov, rod, rov = aod, aov, bod, bov
    return lod, lov, rod, rov, valid, lidx, ridx


_materialize_program_stream_jit = partial(
    jax.jit, static_argnames=("join_type", "cap_e", "a_desc", "b_desc",
                              "block_rows", "interpret",
                              "wide_key"))(_materialize_program_stream_impl)


def materialize_program_stream(*args, interpret: bool = False, **kw):
    """Dispatch twin of plan_program_stream: compiled on TPU, eager under
    the interpreter."""
    if interpret:
        return _materialize_program_stream_impl(*args, interpret=True, **kw)
    return _materialize_program_stream_jit(*args, interpret=False, **kw)


def _vm(v, n):
    """validity-or-None → mask (None means all-valid; stays device-side)."""
    return jnp.ones(n, dtype=bool) if v is None else v


def _keys_to_bits(lkeys, lkvalid, rkeys, rkvalid, str_flags, key_wide=()):
    """Ordered bit arrays of both sides' keys. ``key_wide[i]`` names the
    logical 64-bit dtype of key array i where it is held as word planes
    (else None; () for none at all): such a key gives TWO bit arrays,
    its ordered high and low lanes."""
    from .order import ordered_bits_planes, ordered_bits_raw

    wide = tuple(key_wide) or (None,) * len(str_flags)

    def bits(keys):
        out = []
        for x, s, w in zip(keys, str_flags, wide):
            if w is None:
                out.append(ordered_bits_raw(x, s))
            else:
                out.extend(ordered_bits_planes(x, w))
        return tuple(out)

    n_l, n_r = lkeys[0].shape[-1], rkeys[0].shape[-1]
    lbits, rbits = bits(lkeys), bits(rkeys)
    lkv = jnp.ones(n_l, bool)
    for v in lkvalid:
        if v is not None:
            lkv = lkv & v
    rkv = jnp.ones(n_r, bool)
    for v in rkvalid:
        if v is not None:
            rkv = rkv & v
    return lbits, lkv, rbits, rkv


@partial(jax.jit, static_argnames=("str_flags", "join_type", "key_wide"))
def plan_program(lkeys, lkvalid, lemit, rkeys, rkvalid, remit, str_flags,
                 join_type: JoinType, key_wide=()):
    """Phase 1: raw key columns → plan (counts + match arrays), one
    compiled program. Only counts2 crosses to the host; the match arrays
    stay on device for phase 2."""
    lbits, lkv, rbits, rkv = _keys_to_bits(lkeys, lkvalid, rkeys, rkvalid,
                                           str_flags, key_wide)
    return join_plan_keys(lbits, lkv, _vm(lemit, lkv.shape[0]),
                          rbits, rkv, _vm(remit, rkv.shape[0]), join_type)


def semi_keep_mask(lbits, lkv, lemit, rbits, rkv, remit,
                   join_type: JoinType):
    """Traceable: the left rows a SEMI / ANTI join keeps, as a mask in
    the left's own row order (`join_plan_keys` holds each probe row's
    match count ``m`` there: SEMI is ``lemit & (m > 0)``, ANTI ``lemit &
    (m == 0)``; a dead or null-key row has ``m == 0``). No gather."""
    _, _, m, _, _ = join_plan_keys(lbits, lkv, lemit, rbits, rkv, remit,
                                   join_type)
    return lemit & ((m > 0) if join_type == JoinType.SEMI else (m == 0))


@partial(jax.jit, static_argnames=("str_flags", "join_type", "key_wide"))
def semi_plan_program(lkeys, lkvalid, lemit, rkeys, rkvalid, remit,
                      str_flags, join_type: JoinType, key_wide=()):
    """The XLA path's whole SEMI / ANTI join, one compiled program: raw
    key columns → the left table's new row mask. The result is the left
    table under that mask; nothing reaches the host."""
    lbits, lkv, rbits, rkv = _keys_to_bits(lkeys, lkvalid, rkeys, rkvalid,
                                           str_flags, key_wide)
    return semi_keep_mask(lbits, lkv, _vm(lemit, lkv.shape[0]),
                          rbits, rkv, _vm(remit, rkv.shape[0]), join_type)


@partial(jax.jit, static_argnames=("join_type", "cap_p", "cap_u"))
def materialize_program(lo, m, bperm, un_mask, aemit,
                        ldat, lval, rdat, rval,
                        join_type: JoinType, cap_p: int, cap_u: int):
    """Phase 2: plan arrays → index pairs → gather every payload column,
    one compiled program. Returns (ldat', lval', rdat', rval', emit)."""
    lidx, ridx, emit = join_materialize_gids(
        lo, m, bperm, un_mask, _vm(aemit, lo.shape[0]), join_type,
        cap_p, cap_u)
    lod, lov = gather_columns(ldat, lval, lidx)
    rod, rov = gather_columns(rdat, rval, ridx)
    return lod, lov, rod, rov, emit, lidx, ridx


def gather_columns(dat, val, idx):
    """Batch −1→null gather (traceable): new validity = src validity at the
    gathered row AND a real (non-negative) index. Empty sources produce
    all-null outputs (idx is guaranteed all −1 then).

    Columns are gathered individually: XLA fuses same-index gathers into
    one HBM pass on its own, so manual (n, C) bit-packing only adds stack
    copies (measured +200 ms at 17M rows — packing pays ONLY for gathers
    with *independent* index vectors, as in _expand_from_match)."""
    safe = jnp.maximum(idx, 0)
    hit = idx >= 0
    out_d, out_v = [], []
    for d, v in zip(dat, val):
        if d.shape[-1] == 0:  # rows are the last axis (word planes: [2, n])
            out_d.append(jnp.zeros(d.shape[:-1] + idx.shape, d.dtype))
            out_v.append(jnp.zeros(idx.shape, bool))
        else:
            out_d.append(jnp.take(d, safe, axis=-1))
            out_v.append(hit if v is None else (jnp.take(v, safe) & hit))
    return tuple(out_d), tuple(out_v)

