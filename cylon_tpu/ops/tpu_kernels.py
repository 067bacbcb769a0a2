"""Pallas TPU kernel library — streaming relational primitives.

XLA's gather/scatter on TPU costs ~15-30 ns/element regardless of index
locality (measured on v5e: 33M-element random gather 509 ms, *sorted*
gather 963 ms, scatter 250 ms — vs 96-192 ms for a full multi-operand
sort, ~17 ms for a cumsum and ~10 ms for an elementwise pass). The
relational hot paths are therefore rebuilt as streaming Pallas kernels
that touch HBM sequentially and resolve indirection on-chip:

- ``sweep_gather``  — in-kernel VMEM window gather out[i] = win[o[i]]:
  sublane sweep of native (rows,128) lane gathers (`take_along_axis`
  along lanes is a Mosaic primitive; wider windows sweep row-by-row
  with compare+select). One gather a (window row, output vreg) pair:
  hand it the rows the outputs can reach and no more
  (`join_expand_stream` DMAs a block's window and sweeps, a vreg of
  outputs at a time, the 16 rows of it that vreg reaches).
- ``block_cumsum``  — in-kernel flat inclusive scan of a (R,128) block
  (`jnp.cumsum` has no Mosaic lowering).
- ``inverse_monotone`` — o[q] = #{j : P[j] <= q} for a non-decreasing
  block P: a count of the rows wholly <= q off their last elements,
  then a binary search over the one row left, 7 sweep_gather probes,
  each a sweep of all of P for all of q (so it is called a slab at a
  time on 16 rows, not on a block's 72).
- ``stream_compact`` — compact masked elements of K parallel u32 streams
  into dense prefixes, writing element-exact output via row-aligned DMA
  with a write pointer and partial-row tail carried in SMEM/VMEM across
  the (sequential) TPU grid.
- ``groupby_run_reduce`` — the groupby's reduce step over presorted
  rows: running reductions that restart at every run start, the value
  at each run's end compacted to the run's ordinal; no scatter.

Storage convention: 1-D streams are reshaped (n/128, 128) so windows can
be DMA'd at dynamic *row* offsets (Mosaic rejects arbitrary-offset 1-D
HBM slices; row-granular 2-D slices work).

These replace the reference's builder-append materialization (reference:
cpp/src/cylon/join/join_utils.cpp:131-196 `build_final_table`,
cpp/src/cylon/util/copy_arrray.cpp `copy_array_by_indices`) with
TPU-streaming equivalents. Off-TPU every wrapper accepts
``interpret=True`` and runs under the Pallas interpreter (used by the
CPU test suite; the XLA kernels in ops/join.py remain the portable
default path).
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import wideint as W
from .wideint import two_sum as _two_sum

LANES = 128
_I32MAX = jnp.iinfo(jnp.int32).max
# window rows ONE (8, 128) vreg of join_expand_stream's outputs can reach:
# 1,024 outputs span at most 1,025 runs, 9 rows, 16 from an 8-aligned row
EXPAND_SWEEP_ROWS = 16


def _x32_trace():
    """Context: trace kernel bodies with x64 disabled. Under
    jax_enable_x64, jnp.take_along_axis promotes its indices to int64 and
    Mosaic's int64 convert_element_type rule recurses forever; every
    kernel here is 32-bit by construction, so the promotion is never
    wanted."""
    return jax.enable_x64(False)


def _roll(x, k, axis, interpret=False):
    # pltpu.roll is Mosaic-only; the interpreter needs jnp.roll
    if interpret:
        return jnp.roll(x, k, axis)
    return pltpu.roll(x, k, axis)


def rows_for(n: int) -> int:
    return max(-(-n // LANES), 1)


def pad_rows(x: jnp.ndarray, rows: int, fill=0) -> jnp.ndarray:
    """1-D (n,) -> (rows, 128), zero/fill-padded."""
    n = x.shape[0]
    pad = rows * LANES - n
    if pad:
        x = jnp.concatenate([x, jnp.full(pad, fill, x.dtype)])
    return x.reshape(rows, LANES)


# ---------------------------------------------------------------------------
# in-kernel building blocks (pure functions of VMEM values)
# ---------------------------------------------------------------------------


# np scalar, not a bare python int: weak literals in kernel jaxprs are
# re-canonicalized (i64 under jax_enable_x64) when the interpret
# lowering discharges inside an enclosing jit — see block_cumsum
_L32 = np.int32(LANES)


def flat_iota(shape) -> jnp.ndarray:
    return (jax.lax.broadcasted_iota(jnp.int32, shape, 0) * _L32
            + jax.lax.broadcasted_iota(jnp.int32, shape, 1))


def block_cumsum(x: jnp.ndarray, interpret: bool = False) -> jnp.ndarray:
    """Inclusive scan of a (R,128) int32 block in flat row-major order.

    Scalar where-branches carry STRONG dtypes (``x.dtype.type(0)``, not
    a bare ``0``): a weak python literal in the kernel jaxpr is
    re-canonicalized when the interpret lowering discharges inside an
    enclosing jit — under jax_enable_x64 it comes back i64 and fails
    select_n's strict dtype check. Same rule for every kernel helper
    below."""
    R = x.shape[0]
    zero = x.dtype.type(0)
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    v = x
    k = 1
    while k < LANES:
        v = v + jnp.where(lane >= k, _roll(v, k, 1, interpret), zero)
        k <<= 1
    if R == 1:
        return v
    tot = jnp.broadcast_to(v[:, LANES - 1:LANES], (R, LANES))
    riota = jax.lax.broadcasted_iota(jnp.int32, (R, LANES), 0)
    inc = tot
    k = 1
    while k < R:
        inc = inc + jnp.where(riota >= k, _roll(inc, k, 0, interpret),
                              zero)
        k <<= 1
    return v + (inc - tot)


def block_cummax(x: jnp.ndarray, interpret: bool = False) -> jnp.ndarray:
    """Inclusive running MAX of a (R,128) int32 block in flat row-major
    order (same log-shift structure as block_cumsum)."""
    R = x.shape[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    neg = x.dtype.type(jnp.iinfo(x.dtype).min)  # strong: see block_cumsum
    v = x
    k = 1
    while k < LANES:
        v = jnp.maximum(v, jnp.where(lane >= k, _roll(v, k, 1, interpret),
                                     neg))
        k <<= 1
    if R == 1:
        return v
    tot = jnp.broadcast_to(v[:, LANES - 1:LANES], (R, LANES))
    riota = jax.lax.broadcasted_iota(jnp.int32, (R, LANES), 0)
    inc = tot
    k = 1
    while k < R:
        inc = jnp.maximum(inc, jnp.where(riota >= k,
                                         _roll(inc, k, 0, interpret), neg))
        k <<= 1
    prev_rows = jnp.where(riota > 0, _roll(inc, 1, 0, interpret), neg)
    return jnp.maximum(v, prev_rows)


def flat_shift(x: jnp.ndarray, s, fill=0, interpret: bool = False
               ) -> jnp.ndarray:
    """Shift a (R,128) block DOWN by s (dynamic, 0 <= s < 128) in flat
    order; vacated head gets `fill`. Elements pushed past the end are
    dropped (callers append a spill row first if they need them)."""
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    ra = _dyn_roll_lanes(x, s)
    rb = _roll(ra, 1, 0, interpret)  # rows down by one
    shifted = jnp.where(lane >= s, ra, rb)
    fi = flat_iota(x.shape)
    return jnp.where(fi >= s, shifted,
                     jnp.asarray(fill, x.dtype))  # strong: block_cumsum


def _dyn_roll_lanes(x, s):
    """Roll lanes by dynamic s using take_along_axis (Mosaic-native)."""
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    src = (lane - s) % _L32
    return jnp.take_along_axis(x, src, axis=1)


def flat_shift_up(x: jnp.ndarray, k: int, fill=0, interpret: bool = False
                  ) -> jnp.ndarray:
    """Shift a (R,128) block UP (toward index 0) by static k in flat
    order; vacated tail gets `fill`."""
    R = x.shape[0]
    span = R * LANES
    rows_k, q = k // LANES, k % LANES
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    a = _roll(x, (R - rows_k) % R, 0, interpret)  # pltpu.roll: shift >= 0
    if q == 0:
        shifted = a
    else:
        b = _roll(x, (R - rows_k - 1) % R, 0, interpret)
        ra = _roll(a, LANES - q, 1, interpret)
        rb = _roll(b, LANES - q, 1, interpret)
        shifted = jnp.where(lane < np.int32(LANES - q), ra, rb)
    fi = flat_iota(x.shape)
    return jnp.where(fi < np.int32(span - k), shifted,
                     jnp.asarray(fill, x.dtype))  # strong: block_cumsum


def sweep_gather(win: jnp.ndarray, o: jnp.ndarray, fill=0) -> jnp.ndarray:
    """out[i] = win.flat[o[i]] for window (W,128) and flat offsets o
    (B,128); offsets outside [0, W*128) yield `fill`. Cost O(W) vops."""
    W = win.shape[0]
    orow = o // LANES
    olane = jnp.where((o >= 0) & (orow < W), o % LANES, 0)
    out = jnp.full(o.shape, fill, win.dtype)
    for r in range(W):
        bc = jnp.broadcast_to(win[r:r + 1, :], o.shape)
        g = jnp.take_along_axis(bc, olane, axis=1)
        out = jnp.where(orow == r, g, out)
    return out


def inverse_monotone(P: jnp.ndarray, q: jnp.ndarray) -> jnp.ndarray:
    """o[·] = #{j : P.flat[j] <= q[·]} for non-decreasing (R,128) P; q
    any int32 block shape. Two levels: the rows wholly <= q are counted
    off each row's LAST element (one lane gather and one compare a row,
    where a binary search's probes at steps of 128 and up would each
    sweep all R rows), then a binary search over the 127 lane positions
    of the one row left: 7 `sweep_gather` probes whatever R."""
    R = P.shape[0]
    span = R * LANES
    last = jnp.full(q.shape, LANES - 1, jnp.int32)
    rows = jnp.zeros(q.shape, jnp.int32)
    for r in range(R):
        bc = jnp.broadcast_to(P[r:r + 1, :], q.shape)
        rows = rows + (jnp.take_along_axis(bc, last, axis=1)
                       <= q).astype(jnp.int32)
    lo = rows * _L32
    step = LANES // 2
    while step:
        mid = lo + step
        pv = sweep_gather(P, jnp.minimum(mid, span) - 1, fill=_I32MAX)
        pv = jnp.where(mid <= span, pv, _I32MAX)
        lo = jnp.where(pv <= q, mid, lo)
        step >>= 1
    return lo


# ---------------------------------------------------------------------------
# stream_compact
# ---------------------------------------------------------------------------


def stream_compact(mask: jnp.ndarray, streams: Sequence[jnp.ndarray],
                   block_rows: int = 32, interpret: bool = False,
                   out_elems: Optional[int] = None
                   ) -> Tuple[Tuple[jnp.ndarray, ...], jnp.ndarray]:
    """Compact ``streams[k][mask]`` into dense zero-padded prefixes.

    mask: (n,) bool/int; streams: 1-D 32-bit arrays of length n.
    Returns (tuple of compacted (n_pad,) arrays, count int32). n_pad =
    n rounded up to a block multiple (tail beyond `count` is zeros).

    ``out_elems``: the outputs hold that many elements and no more (the
    caller knows the count is at most that: `data/table.compact_live`
    fetched it), so a mask that keeps a hundredth of its rows writes a
    hundredth of the input, not all of it and a tail of zeros. A count
    beyond it is not written past the end (the window's row is held to
    the array) and comes back as it is, for the caller to see.
    """
    nstreams = len(streams)
    n = mask.shape[0]
    BR = block_rows
    # DMA windows must cover whole (8,128) sublane tiles — a copy of a
    # non-multiple-of-8 row count hard-faults the chip (observed on v5e)
    assert BR % 8 == 0 and BR >= 8
    blocks = max(-(-n // (BR * LANES)), 1)
    rows = blocks * BR
    m2 = pad_rows(mask.astype(jnp.int32), rows)
    # BITCAST (not value-cast) to u32: the outputs are bit-reinterpreted
    # back via .view(s.dtype), so the round trip must be bit-exact
    for s in streams:
        assert s.dtype.itemsize == 4, \
            f"stream_compact takes 32-bit streams, got {s.dtype}"
    s2 = [pad_rows(s if s.dtype == jnp.uint32 else s.view(jnp.uint32),
                   rows) for s in streams]

    # dynamic write window may extend past the last row written
    keep_rows = rows if out_elems is None \
        else -(-rows_for(out_elems) // 8) * 8
    out_rows = keep_rows + BR + 8

    scratch = ([pltpu.SMEM((1,), jnp.int32),
                pltpu.VMEM((nstreams, LANES), jnp.uint32)]
               + [pltpu.VMEM((BR + 8, LANES), jnp.uint32)
                  for _ in range(nstreams)]
               + [pltpu.SemaphoreType.DMA((nstreams,))])

    out_shapes = ([jax.ShapeDtypeStruct((out_rows, LANES), jnp.uint32)
                   for _ in range(nstreams)]
                  + [jax.ShapeDtypeStruct((1,), jnp.int32)])

    def kernel(mask_ref, *rest):
        srefs = rest[:nstreams]
        outs = rest[nstreams:2 * nstreams]
        cnt_ref = rest[2 * nstreams]
        wptr = rest[2 * nstreams + 1]
        tails = rest[2 * nstreams + 2]
        bufs = list(rest[2 * nstreams + 3:2 * nstreams + 3 + nstreams])
        sems = rest[2 * nstreams + 3 + nstreams]
        _compact_streams(nstreams, BR, mask_ref, srefs, outs, cnt_ref,
                         wptr, tails, bufs, sems, interpret,
                         None if out_elems is None else out_rows)

    res = pl.pallas_call(
        kernel,
        name="stream_compact",
        out_shape=out_shapes,
        grid=(blocks,),
        in_specs=([pl.BlockSpec((BR, LANES), lambda i: (i, 0),
                                memory_space=pltpu.VMEM)] * (1 + nstreams)),
        out_specs=([pl.BlockSpec(memory_space=pl.ANY)] * nstreams
                   + [pl.BlockSpec(memory_space=pltpu.SMEM)]),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(has_side_effects=True),
        interpret=interpret,
    )
    with _x32_trace():
        res = res(m2, *s2)
    outs, count = res[:nstreams], res[nstreams][0]
    keep = rows * LANES if out_elems is None else out_elems
    flat = tuple(
        o.reshape(-1)[:keep].view(s.dtype)
        if s.dtype != jnp.uint32 else o.reshape(-1)[:keep]
        for o, s in zip(outs, streams))
    return flat, count


# ---------------------------------------------------------------------------
# join_plan_stream — the streaming join planner
# ---------------------------------------------------------------------------


def join_plan_stream(bits_s: jnp.ndarray, tag_s: jnp.ndarray, na: int,
                     nb: int, emit_unmatched_a: bool,
                     lanes: Sequence[jnp.ndarray] = (),
                     n_a_lanes: Optional[int] = None,
                     n_b_lanes: Optional[int] = None,
                     bits2_s: Optional[jnp.ndarray] = None,
                     verify_lanes: Sequence[jnp.ndarray] = (),
                     block_rows: int = 64, interpret: bool = False,
                     keep: Optional[str] = None):
    """ONE sequential pass over the key-sorted row stream that computes the
    whole join plan — the Pallas replacement for the XLA scatter/gather
    chain in ops/join.join_plan_keys (profiled ~2 s of latency-bound
    random HBM passes at 33M rows; this pass is bandwidth-bound streaming).

    Inputs (key-sorted together, see ops/join.plan_program_stream):
      bits_s: u32 order-normalized key bits; dead rows forced to ~0.
      tag_s:  u32 ``side<<31 | emit<<30 | live<<29 | iota`` — probe (a)
              rows carry side=1 and sort after build (b) rows within a run.
      lanes:  u32 payload streams that rode the SAME sort (slot s holds
              a-side column s at a rows, b-side column s at b rows) —
              they are compacted into both groups so the expansion kernel
              never has to random-gather payload from HBM.
      bits2_s: optional SECOND run-boundary stream — the hash-join path
              sorts on a 2x32-bit row hash, so runs are (bits, bits2)
              equality classes.
      verify_lanes: u32 key-bit streams checked for equality WITHIN each
              run; any difference between adjacent live rows bumps the
              collision counter (counts[3]) — the hash-join path treats
              a nonzero count as "hash collision, recompute exactly".

    Per element the pass derives, with SMEM carries across the sequential
    grid: the live-b prefix count (block_cumsum), run boundaries (shifted
    compare), the run-head live-b prefix via a running MAX broadcast
    (head values are non-decreasing in key order, so cummax IS the
    broadcast — no scatter), match count m, output offsets (cumsum of
    per-row multiplicity), and stream-compacts two groups:
      group A (emitting probe rows): {orig index, packed delta2,
              output start, payload lanes…} — the expansion plan;
      group B (live build rows):     {orig index, payload lanes…} — the
              key-ordered build permutation (bperm analog).

    Returns (counts i32[4] = [n_out, n_emit, n_blive, n_collisions],
    a_streams, b_streams) where a_streams = (elist, delc, startsc,
    a_lane…) and b_streams = (blist, b_lane…), each a PADDED (rows,
    LANES) u32 block array; entries beyond their count are garbage —
    consumers mask by the counts (join_expand_stream).

    ``keep``: None, or "semi" / "anti", the pass of a semi / anti join:
    a probe row's multiplicity is ``min(m, 1)`` ("semi": a live row with
    a match) or ``m == 0`` ("anti": an emitted row with none, a null key
    included) instead of ``m``, so each probe row is kept at most once
    and nothing is left to expand. Group A is then (elist, a_lane…): the
    kept rows' indices and lanes, compacted in key order (no delta, no
    output start); group B is not compacted at all, because nothing
    reads the build side past its match count: b_streams is (). counts =
    [n_kept, n_kept, 0, n_collisions].
    """
    assert keep in (None, "semi", "anti")
    n = bits_s.shape[0]
    BR = block_rows
    L = len(lanes)
    # lane slot s holds a-side column s at a rows and b-side column s at b
    # rows; when the sides pack unequal lane counts, the narrow side's
    # group only compacts ITS lanes (the tail slots are the other side's)
    La = L if n_a_lanes is None else n_a_lanes
    Lb = L if n_b_lanes is None else n_b_lanes
    nA, nB = (3 + La, 1 + Lb) if keep is None else (1 + La, 0)
    has_b2 = bits2_s is not None
    nv = len(verify_lanes)
    assert BR % 8 == 0 and BR >= 8
    assert n < (1 << 29)
    blocks = max(-(-n // (BR * LANES)), 1)
    rows = blocks * BR
    allones = jnp.uint32(0xFFFFFFFF)
    b2 = pad_rows(bits_s, rows, fill=allones)
    t2 = pad_rows(tag_s, rows, fill=0)  # side=0, live=0 → inert
    b2b = pad_rows(bits2_s, rows, fill=allones) if has_b2 else None
    v2 = [pad_rows(x, rows, fill=0) for x in verify_lanes]
    l2 = [pad_rows(x, rows, fill=0) for x in lanes]

    rows_a = rows_for(max(na, 1))
    rows_b = rows_for(max(nb, 1))
    out_rows_a = rows_a + BR + 8
    out_rows_b = rows_b + BR + 8

    out_shapes = (
        [jax.ShapeDtypeStruct((out_rows_a, LANES), jnp.uint32)] * nA
        + [jax.ShapeDtypeStruct((out_rows_b, LANES), jnp.uint32)] * nB
        + [jax.ShapeDtypeStruct((4,), jnp.int32)])

    # tails rows: [0,nA) A partial-row carries, [nA,nA+nB) B carries,
    # then prev-element carries: bits, tag, bits2?, verify lanes…
    t_prev = nA + nB
    n_tails = t_prev + 2 + (1 if has_b2 else 0) + nv
    scratch = ([pltpu.SMEM((8,), jnp.int32),
                pltpu.VMEM((n_tails, LANES), jnp.uint32)]
               + [pltpu.VMEM((BR + 8, LANES), jnp.uint32)
                  for _ in range(nA + nB)]
               + [pltpu.SemaphoreType.DMA((nA + nB,))])

    def kernel(bits_ref, tag_ref, *rest):
        k = 0
        bits2_ref = rest[k] if has_b2 else None
        k += 1 if has_b2 else 0
        vrefs = rest[k:k + nv]
        k += nv
        lane_refs = rest[k:k + L]
        k += L
        outsA = rest[k:k + nA]
        outsB = rest[k + nA:k + nA + nB]
        cnt_ref = rest[k + nA + nB]
        carr = rest[k + nA + nB + 1]
        tails = rest[k + nA + nB + 2]
        bufsA = list(rest[k + nA + nB + 3:k + nA + nB + 3 + nA])
        bufsB = list(rest[k + nA + nB + 3 + nA:k + nA + nB + 3 + nA + nB])
        sems = rest[k + nA + nB + 3 + nA + nB]
        i = pl.program_id(0)
        bits = bits_ref[:]
        tag = tag_ref[:]
        lane_vals = [r[:] for r in lane_refs]

        @pl.when(i == 0)
        def _():
            carr[0] = 0  # inclusive live-b count so far
            carr[1] = 0  # inclusive output offset so far
            carr[2] = 0  # running max of head b_before (monotone ≥ 0)
            carr[4] = 0  # group A write pointer (n_emit)
            carr[5] = 0  # group B write pointer (n_blive)
            carr[6] = 0  # within-run key-mismatch (hash collision) count
            tails[:] = jnp.zeros((n_tails, LANES), jnp.uint32)

        def prev_of(x, trow, fill0):
            """x shifted down by one in flat order, the vacated head
            filled from the carried last element of the previous block
            (prev-element carries live in tails rows — Mosaic has no
            scalar bitcast, so an SMEM i32 slot can't hold a u32)."""
            pf = jnp.where(i == 0, fill0, tails[trow, LANES - 1])
            return flat_shift(x, jnp.int32(1), fill=pf,
                              interpret=interpret)

        # at i==0 any value ≠ bits[0,0] forces the first run head
        pb = prev_of(bits, t_prev, bits[0, 0] + jnp.uint32(1))
        neq = bits != pb
        if has_b2:
            bits2 = bits2_ref[:]
            neq = neq | (bits2 != prev_of(bits2, t_prev + 2,
                                          bits2[0, 0] + jnp.uint32(1)))
        side = (tag >> 31) == 1
        emit = ((tag >> 30) & 1) == 1
        live = ((tag >> 29) & 1) == 1
        idx_u = tag & jnp.uint32((1 << 29) - 1)

        if nv:
            # hash-collision audit: adjacent LIVE rows inside one run
            # must agree on every true-key lane (prev tag carried for the
            # cross-block boundary; tag fill 0 → prev dead → no flag)
            ptag = prev_of(tag, t_prev + 1, jnp.uint32(0))
            prev_live = ((ptag >> 29) & 1) == 1
            coll = jnp.zeros(bits.shape, bool)
            vbase = t_prev + 2 + (1 if has_b2 else 0)
            for vi in range(nv):
                vl = vrefs[vi][:]
                coll = coll | (vl != prev_of(vl, vbase + vi, jnp.uint32(0)))
            # a live row BELOW a dead row in one run means a live key
            # hashed to the dead rows' forced all-ones slot — its verify
            # chain is interrupted, so that also counts as a collision
            coll = (coll | ~prev_live) & (~neq) & live
            carr[6] = carr[6] + jnp.sum(coll.astype(jnp.int32))

        ib = ((~side) & live).astype(jnp.int32)
        cumb = block_cumsum(ib, interpret) + carr[0]
        bb_at = cumb - ib
        # run-head b_before values are non-decreasing in key order, so a
        # running max of (head ? value : 0) IS the per-run broadcast
        headv = jnp.where(neq, bb_at, 0)
        bb = jnp.maximum(block_cummax(headv, interpret), carr[2])
        m_at = cumb - bb
        eff_m = jnp.where(live, m_at, 0)
        if keep == "semi":
            mm = (side & live & (eff_m > 0)).astype(jnp.int32)
        elif keep == "anti":
            mm = (side & emit & (eff_m == 0)).astype(jnp.int32)
        elif emit_unmatched_a:
            mm = jnp.where(side & emit, jnp.maximum(eff_m, 1), 0)
        else:
            mm = jnp.where(side & live, eff_m, 0)
        if keep is None:
            offv = block_cumsum(mm, interpret) + carr[1]
            start = offv - mm
            delta2 = (bb - start) * 2 + (eff_m > 0).astype(jnp.int32)

        # carries must update before the compaction writes bump wptrs
        carr[0] = cumb[BR - 1, LANES - 1]
        if keep is None:
            carr[1] = offv[BR - 1, LANES - 1]
        carr[2] = bb[BR - 1, LANES - 1]
        tails[t_prev:t_prev + 1, :] = bits[BR - 1:BR, :]
        tails[t_prev + 1:t_prev + 2, :] = tag[BR - 1:BR, :]
        if has_b2:
            tails[t_prev + 2:t_prev + 3, :] = bits2[BR - 1:BR, :]
        for vi in range(nv):
            vb = t_prev + 2 + (1 if has_b2 else 0) + vi
            tails[vb:vb + 1, :] = vrefs[vi][BR - 1:BR, :]

        mA = (mm > 0).astype(jnp.int32)
        if keep is None:
            valsA = [idx_u,
                     jax.lax.bitcast_convert_type(delta2, jnp.uint32),
                     jax.lax.bitcast_convert_type(start, jnp.uint32)] \
                + lane_vals[:La]
        else:
            valsA = [idx_u] + lane_vals[:La]
        _compact_write(BR, mA, valsA, list(outsA), carr, 4, tails, 0,
                       bufsA, sems, 0, interpret)
        if keep is None:
            valsB = [idx_u - jnp.uint32(na)] + lane_vals[:Lb]
            _compact_write(BR, ib, valsB, list(outsB), carr, 5, tails, nA,
                           bufsB, sems, nA, interpret)

        @pl.when(i == pl.num_programs(0) - 1)
        def _():
            # n_out: a kept row is one output row
            cnt_ref[0] = offv[BR - 1, LANES - 1] if keep is None \
                else carr[4]
            cnt_ref[1] = carr[4]                  # n_emit
            cnt_ref[2] = carr[5]                  # n_blive
            cnt_ref[3] = carr[6]                  # hash collisions

    extra_in = ([b2b] if has_b2 else []) + v2 + l2
    res = pl.pallas_call(
        kernel,
        name="join_stream_plan",
        out_shape=out_shapes,
        grid=(blocks,),
        in_specs=[pl.BlockSpec((BR, LANES), lambda i: (i, 0),
                               memory_space=pltpu.VMEM)] * (2 + len(extra_in)),
        out_specs=([pl.BlockSpec(memory_space=pl.ANY)] * (nA + nB)
                   + [pl.BlockSpec(memory_space=pltpu.SMEM)]),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(has_side_effects=True),
        interpret=interpret,
    )
    with _x32_trace():
        res = res(b2, t2, *extra_in)
    return res[nA + nB], tuple(res[:nA]), tuple(res[nA:nA + nB])


# ---------------------------------------------------------------------------
# setop_stream — streaming set operations (union/subtract/intersect)
# ---------------------------------------------------------------------------


def setop_stream(bits_s: jnp.ndarray, bits2_s: jnp.ndarray,
                 tag_s: jnp.ndarray, lanes: Sequence[jnp.ndarray],
                 op: int, block_rows: int = 64, interpret: bool = False):
    """ONE sequential pass over the full-row-hash-sorted stream that
    computes a distinct set operation and compacts its output rows —
    replacing the XLA path's ~8 full sorts + scatters (dense ranks,
    first-occurrence, membership, masked-indices; reference semantics:
    table.cpp:729-942 hash-set union/subtract/intersect).

    Inputs sorted together by (bits, bits2, tag): bits/bits2 = 2x32-bit
    full-row hash (dead rows forced all-ones), tag = ``side<<31 |
    live<<29 | iota`` with side=1 for the LEFT table — so within a run
    all right rows precede all left rows, and at any left element the
    inclusive right-prefix count IS the run's right total. lanes carry
    the canonicalized row payload; they double as hash-verify lanes
    (within-run mismatch => counts[1] collision, caller recomputes
    exactly) and as the compacted output.

    op: 0=UNION (first live element of each run, either side),
    1=SUBTRACT (first live left of runs with no live right),
    2=INTERSECT (first live left of runs with at least one live right).

    Returns (counts i32[2] = [n_out, n_collisions], out_streams) with
    out_streams = (idx, lane…) compacted at emitted rows; idx addresses
    the concatenated [left; right] row space.
    """
    n = bits_s.shape[0]
    BR = block_rows
    L = len(lanes)
    nO = 1 + L
    assert BR % 8 == 0 and BR >= 8
    assert n < (1 << 29)
    blocks = max(-(-n // (BR * LANES)), 1)
    rows = blocks * BR
    allones = jnp.uint32(0xFFFFFFFF)
    b1 = pad_rows(bits_s, rows, fill=allones)
    b2 = pad_rows(bits2_s, rows, fill=allones)
    t2 = pad_rows(tag_s, rows, fill=0)
    l2 = [pad_rows(x, rows, fill=0) for x in lanes]

    out_rows = rows_for(n) + BR + 8
    out_shapes = ([jax.ShapeDtypeStruct((out_rows, LANES), jnp.uint32)] * nO
                  + [jax.ShapeDtypeStruct((2,), jnp.int32)])

    # tails: [0,nO) output-group partial rows, then prev carries:
    # bits, bits2, tag, lanes…
    t_prev = nO
    n_tails = t_prev + 3 + L
    scratch = ([pltpu.SMEM((8,), jnp.int32),
                pltpu.VMEM((n_tails, LANES), jnp.uint32)]
               + [pltpu.VMEM((BR + 8, LANES), jnp.uint32)
                  for _ in range(nO)]
               + [pltpu.SemaphoreType.DMA((nO,))])

    def kernel(b1_ref, b2_ref, tag_ref, *rest):
        lane_refs = rest[:L]
        outs = rest[L:L + nO]
        cnt_ref = rest[L + nO]
        carr = rest[L + nO + 1]
        tails = rest[L + nO + 2]
        bufs = list(rest[L + nO + 3:L + nO + 3 + nO])
        sems = rest[L + nO + 3 + nO]
        i = pl.program_id(0)
        bits = b1_ref[:]
        bits2 = b2_ref[:]
        tag = tag_ref[:]
        lane_vals = [r[:] for r in lane_refs]

        @pl.when(i == 0)
        def _():
            carr[0] = 0  # inclusive live-left count
            carr[1] = 0  # inclusive live-right count
            carr[2] = 0  # running max of head left-before
            carr[3] = 0  # running max of head right-before
            carr[4] = 0  # output write pointer
            carr[6] = 0  # collision count
            tails[:] = jnp.zeros((n_tails, LANES), jnp.uint32)

        def prev_of(x, trow, fill0):
            pf = jnp.where(i == 0, fill0, tails[trow, LANES - 1])
            return flat_shift(x, jnp.int32(1), fill=pf,
                              interpret=interpret)

        neq = (bits != prev_of(bits, t_prev, bits[0, 0] + jnp.uint32(1))) \
            | (bits2 != prev_of(bits2, t_prev + 1,
                                bits2[0, 0] + jnp.uint32(1)))
        side = (tag >> 31) == 1
        live = ((tag >> 29) & 1) == 1
        idx_u = tag & jnp.uint32((1 << 29) - 1)

        ptag = prev_of(tag, t_prev + 2, jnp.uint32(0))
        prev_live = ((ptag >> 29) & 1) == 1
        coll = jnp.zeros(bits.shape, bool)
        for vi in range(L):
            coll = coll | (lane_vals[vi] != prev_of(
                lane_vals[vi], t_prev + 3 + vi, jnp.uint32(0)))
        coll = (coll | ~prev_live) & (~neq) & live
        carr[6] = carr[6] + jnp.sum(coll.astype(jnp.int32))

        ill = (side & live).astype(jnp.int32)
        ibr = ((~side) & live).astype(jnp.int32)
        cum_l = block_cumsum(ill, interpret) + carr[0]
        cum_r = block_cumsum(ibr, interpret) + carr[1]
        # run-head prefix broadcast via running max (heads non-decreasing)
        l_before = jnp.maximum(
            block_cummax(jnp.where(neq, cum_l - ill, 0), interpret),
            carr[2])
        r_before = jnp.maximum(
            block_cummax(jnp.where(neq, cum_r - ibr, 0), interpret),
            carr[3])
        l_at = cum_l - l_before  # inclusive live-left count within run
        r_at = cum_r - r_before  # inclusive live-right count within run

        if op == 0:      # UNION: first live element of the run
            emitm = live & ((l_at + r_at) == 1)
        elif op == 1:    # SUBTRACT: first live left, no live right in run
            emitm = (ill == 1) & (l_at == 1) & (r_at == 0)
        else:            # INTERSECT: first live left, some live right
            emitm = (ill == 1) & (l_at == 1) & (r_at > 0)

        carr[0] = cum_l[BR - 1, LANES - 1]
        carr[1] = cum_r[BR - 1, LANES - 1]
        carr[2] = l_before[BR - 1, LANES - 1]
        carr[3] = r_before[BR - 1, LANES - 1]
        tails[t_prev:t_prev + 1, :] = bits[BR - 1:BR, :]
        tails[t_prev + 1:t_prev + 2, :] = bits2[BR - 1:BR, :]
        tails[t_prev + 2:t_prev + 3, :] = tag[BR - 1:BR, :]
        for vi in range(L):
            tails[t_prev + 3 + vi:t_prev + 4 + vi, :] = \
                lane_vals[vi][BR - 1:BR, :]

        _compact_write(BR, emitm.astype(jnp.int32), [idx_u] + lane_vals,
                       list(outs), carr, 4, tails, 0, bufs, sems, 0,
                       interpret)

        @pl.when(i == pl.num_programs(0) - 1)
        def _():
            cnt_ref[0] = carr[4]
            cnt_ref[1] = carr[6]

    res = pl.pallas_call(
        kernel,
        name="setop_stream",
        out_shape=out_shapes,
        grid=(blocks,),
        in_specs=[pl.BlockSpec((BR, LANES), lambda i: (i, 0),
                               memory_space=pltpu.VMEM)] * (3 + L),
        out_specs=([pl.BlockSpec(memory_space=pl.ANY)] * nO
                   + [pl.BlockSpec(memory_space=pltpu.SMEM)]),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(has_side_effects=True),
        interpret=interpret,
    )
    with _x32_trace():
        res = res(b1, b2, t2, *l2)
    return res[nO], tuple(res[:nO])


# ---------------------------------------------------------------------------
# join_expand_stream — the streaming join materializer
# ---------------------------------------------------------------------------


def join_expand_stream(counts: jnp.ndarray,
                       a_streams: Sequence[jnp.ndarray],
                       b_streams: Sequence[jnp.ndarray],
                       cap_e: int, block_rows: int = 64,
                       interpret: bool = False):
    """Expand a compacted join plan into the output rows — the streaming
    replacement for the XLA scatter+cumsum+row-gather chain that dominated
    the join at ~30 ns/row (profiled: ordx 228 ms + two output-sized row
    gathers ~1.1 s at 17M output rows on v5e).

    DMA goes by the BLOCK (BR rows of outputs, chosen large to amortise
    the round trips), vector work by the SLAB (one (8, 128) vreg of
    outputs): a sweep costs (window rows) x (output vregs), so each slab
    searches and sweeps only the rows its own 1,024 outputs can reach.

    The key structural facts the kernel exploits:
      * group A's output starts are STRICTLY increasing over emitting
        runs, so the covering-run ordinal of output j is monotone: a
        block needs only a (BR+8)-row window of group A at the carried
        run pointer (ONE DMA a stream a block), and a slab only the
        8-aligned 16 rows of it at the pointer carried from the slab
        before (1,024 outputs span at most 1,025 runs: 9 rows, 16 when
        aligned), searched with `inverse_monotone` and swept for the
        plan and the lanes;
      * within a run, b positions are CONSECUTIVE (bpos = j + delta), and
        run lo's are non-decreasing, so each block's b reads live in a
        short span walked with a windowed loop whose TOTAL work across
        blocks is bounded by one streaming pass over group B (plus one
        window per duplicate-key reset); inside a DMA'd window a slab
        sweeps the 16-row sub-windows its own min..max bpos spans (one,
        mostly), so its cost goes with its span;
      * a slab whose first output is past n_out reaches no row: it is
        filled (-1 / 0) without a search, and a block of such slabs
        without a DMA.

    counts: i32[4] from join_plan_stream. a_streams: (elist, delc,
    startsc, a_lane…); b_streams: (blist, b_lane…) — padded (rows, LANES)
    u32 blocks as returned by join_plan_stream. cap_e: static output
    capacity, must be a multiple of block_rows*LANES.

    Returns (aidx, bidx, a_lane_outs, b_lane_outs): i32/u32 (cap_e,)
    arrays; aidx = −1 beyond n_out, bidx = −1 where the row has no build
    match; lanes are zeroed where their side's index is −1.
    """
    BR = block_rows
    assert BR % 8 == 0 and BR >= 8
    assert cap_e % (BR * LANES) == 0 and cap_e > 0
    nA, nB = len(a_streams), len(b_streams)
    La, Lb = nA - 3, nB - 1
    nblocks = cap_e // (BR * LANES)
    W = BR + 8  # window rows; DMA row counts must be multiples of 8
    SR = 8  # slab rows: one vreg of outputs, the tile sub-windows align to
    SW = EXPAND_SWEEP_ROWS  # sub-window rows a slab sweeps
    nslabs = BR // SR
    tot_a = a_streams[0].shape[0]
    tot_b = b_streams[0].shape[0]
    assert tot_a >= W and tot_b >= W, "plan streams carry BR+8 slack rows"

    out_shapes = ([jax.ShapeDtypeStruct((nblocks * BR, LANES), jnp.int32)] * 2
                  + [jax.ShapeDtypeStruct((nblocks * BR, LANES), jnp.uint32)]
                  * (La + Lb))

    # carr: [0] run pointer, then (min, max) of bpos a slab
    scratch = ([pltpu.SMEM((1 + 2 * nslabs,), jnp.int32),
                pltpu.VMEM((BR, LANES), jnp.int32)]
               + [pltpu.VMEM((W, LANES), jnp.uint32)
                  for _ in range(nA + nB)]
               + [pltpu.SemaphoreType.DMA((nA + nB,))])

    def as_i32(x):
        return jax.lax.bitcast_convert_type(x, jnp.int32)

    def kernel(cnt_ref, *rest):
        a_refs = rest[:nA]
        b_refs = rest[nA:nA + nB]
        o_aidx = rest[nA + nB]
        o_bidx = rest[nA + nB + 1]
        o_alane = rest[nA + nB + 2:nA + nB + 2 + La]
        o_blane = rest[nA + nB + 2 + La:nA + nB + 2 + La + Lb]
        carr = rest[nA + nB + 2 + La + Lb]
        bpos_buf = rest[nA + nB + 3 + La + Lb]
        bufsA = list(rest[nA + nB + 4 + La + Lb:
                          nA + nB + 4 + La + Lb + nA])
        bufsB = list(rest[nA + nB + 4 + La + Lb + nA:
                          nA + nB + 4 + La + Lb + nA + nB])
        sems = rest[nA + nB + 4 + La + Lb + nA + nB]
        i = pl.program_id(0)

        @pl.when(i == 0)
        def _():
            carr[0] = 0  # run pointer: ordinal of prev slab's last output

        n_out = cnt_ref[0]
        n_emit = cnt_ref[1]
        j0 = i * (BR * LANES)
        # slabs with an output under n_out; the rest reach no row
        nlive = jnp.clip((n_out - j0 + (SR * LANES - 1)) // (SR * LANES),
                         0, nslabs)

        def slab(ref, s):
            return ref.at[pl.ds(pl.multiple_of(s * SR, SR), SR), :]

        def sub(ref, r):
            return ref[pl.ds(pl.multiple_of(r, SR), SW), :]

        # the fills: every b output (a slab's hits are merged in below,
        # one sub-window at a time) and the a outputs of dead slabs
        o_bidx[:] = jnp.full((BR, LANES), -1, jnp.int32)
        for k in range(Lb):
            o_blane[k][:] = jnp.zeros((BR, LANES), jnp.uint32)

        @pl.when(nlive < nslabs)
        def _():
            o_aidx[:] = jnp.full((BR, LANES), -1, jnp.int32)
            for k in range(La):
                o_alane[k][:] = jnp.zeros((BR, LANES), jnp.uint32)

        # --- group A window at the carried run pointer ---
        arow0 = jnp.minimum(carr[0] // LANES, tot_a - W)

        @pl.when(nlive > 0)
        def _():
            for k in range(nA):
                pltpu.make_async_copy(a_refs[k].at[pl.ds(arow0, W)],
                                      bufsA[k], sems.at[k]).start()
            for k in range(nA):
                pltpu.make_async_copy(a_refs[k].at[pl.ds(arow0, W)],
                                      bufsA[k], sems.at[k]).wait()

        def a_slab(s, carry):
            ptr, minb, maxb = carry
            # the 16 window rows this slab's outputs can reach: every
            # run before them starts at/before the pointer's covered
            # output, every run after them past the slab's last
            r0 = jnp.minimum((ptr // LANES - arow0) // SR * SR, W - SW)
            base_e = (arow0 + r0) * LANES
            ge = base_e + flat_iota((SW, LANES))
            s_win = jnp.where(ge < n_emit, as_i32(sub(bufsA[2], r0)),
                              _I32MAX)
            j = j0 + s * (SR * LANES) + flat_iota((SR, LANES))
            # ordinal = #{r global : start[r] <= j} − 1
            cnt_le = inverse_monotone(s_win, j)
            ordinal = base_e + cnt_le - 1
            woff = jnp.maximum(cnt_le - 1, 0)
            d2 = sweep_gather(as_i32(sub(bufsA[1], r0)), woff)
            aidx = sweep_gather(as_i32(sub(bufsA[0], r0)), woff)
            valid = j < n_out
            slab(o_aidx, s)[:] = jnp.where(valid, aidx, -1)
            for k in range(La):
                slab(o_alane[k], s)[:] = jnp.where(
                    valid, sweep_gather(sub(bufsA[3 + k], r0), woff),
                    jnp.uint32(0))
            has = ((d2 & 1) == 1) & valid
            bpos = j + (d2 >> 1)  # arithmetic shift: delta may be negative
            slab(bpos_buf, s)[:] = jnp.where(has, bpos, -1)
            lo = jnp.min(jnp.where(has, bpos, _I32MAX))
            hi = jnp.max(jnp.where(has, bpos, -1))
            carr[1 + 2 * s] = lo
            carr[2 + 2 * s] = hi
            return (jnp.maximum(ordinal[SR - 1, LANES - 1], 0),
                    jnp.minimum(minb, lo), jnp.maximum(maxb, hi))

        carr[0], minb, maxb = jax.lax.fori_loop(
            0, nlive, a_slab,
            (carr[0], jnp.int32(_I32MAX), jnp.int32(-1)))

        # --- group B windowed walk over the block's bpos span ---
        brow0 = jnp.clip(minb // LANES, 0, tot_b - W)
        nw = jnp.where(maxb >= 0,
                       (jnp.minimum(maxb // LANES, tot_b - 1) - brow0) // W
                       + 1, 0)
        b_outs = [o_bidx] + list(o_blane)

        def b_window(w, _):
            brow = jnp.minimum(brow0 + w * W, tot_b - W)
            for k in range(nB):
                pltpu.make_async_copy(b_refs[k].at[pl.ds(brow, W)],
                                      bufsB[k], sems.at[nA + k]).start()
            for k in range(nB):
                pltpu.make_async_copy(b_refs[k].at[pl.ds(brow, W)],
                                      bufsB[k], sems.at[nA + k]).wait()

            def b_slab(s, _):
                # the slab's own span, cut to this window: its rows
                lo = jnp.maximum(carr[1 + 2 * s] // LANES - brow, 0)
                hi = jnp.minimum(carr[2 + 2 * s] // LANES - brow, W - 1)
                r0 = jnp.minimum(lo // SR * SR, W - SW)
                nsub = jnp.where(hi >= lo, (hi - r0) // SW + 1, 0)
                off = slab(bpos_buf, s)[:] - brow * LANES

                def b_sub(t, _):
                    r = jnp.minimum(r0 + t * SW, W - SW)
                    o = off - r * LANES
                    hit = (o >= 0) & (o < SW * LANES)  # a miss's off is < 0
                    o = jnp.where(hit, o, -1)
                    for k in range(nB):
                        out = slab(b_outs[k], s)
                        g = sweep_gather(sub(bufsB[k], r), o)
                        if k == 0:
                            g = as_i32(g)
                        out[:] = jnp.where(hit, g, out[:])
                    return _

                return jax.lax.fori_loop(0, nsub, b_sub, _)

            return jax.lax.fori_loop(0, nlive, b_slab, _)

        jax.lax.fori_loop(0, nw, b_window, jnp.int32(0))

    res = pl.pallas_call(
        kernel,
        name="join_stream_expand",
        out_shape=out_shapes,
        grid=(nblocks,),
        in_specs=([pl.BlockSpec(memory_space=pltpu.SMEM)]
                  + [pl.BlockSpec(memory_space=pl.ANY)] * (nA + nB)),
        out_specs=[pl.BlockSpec((BR, LANES), lambda i: (i, 0))] * len(
            out_shapes),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(has_side_effects=True),
        interpret=interpret,
    )
    with _x32_trace():
        res = res(counts, *a_streams, *b_streams)
    flat = [r.reshape(-1) for r in res]
    return (flat[0], flat[1], tuple(flat[2:2 + La]),
            tuple(flat[2 + La:2 + La + Lb]))


def _compact_write(BR, m, vals, out_refs, wptr, wslot, tails, trow0,
                   bufs, sems, srow0, interpret, last_row0=None):
    """Compact the masked elements of `vals` (VMEM (BR,128) u32 values,
    mask m int32 0/1) onto `out_refs` at the running write pointer
    ``wptr[wslot]``, carrying the partial-row tail in rows trow0.. of
    `tails` and using semaphores srow0.. of `sems`. ``last_row0``: the
    last row a write window may start at (outputs smaller than the
    input: `stream_compact`'s ``out_elems``).

    Staged-shift compaction: selected element at j must move UP by
    d[j] = #unselected before j (monotone non-decreasing). Moving by
    d's bits low-to-high is collision-free: for j1<j2 (both selected),
    (d2 mod 2^b) - (d1 mod 2^b) <= d2-d1 < j2-j1, so partial positions
    j - (d mod 2^b) stay strictly ordered. O(log span) cheap vector
    passes — no in-VMEM scatter, no O(rows) sweeps."""
    nstreams = len(vals)
    P = block_cumsum(m, interpret)
    cnt = P[BR - 1, LANES - 1]
    base = wptr[wslot]
    s = base % _L32
    row0 = base // _L32 if last_row0 is None \
        else jnp.minimum(base // _L32, np.int32(last_row0))

    one_u = np.uint32(1)
    q = flat_iota((BR, LANES))
    d = q + np.int32(1) - P  # unselected before j (exclusive, j selected)
    pack = ((d.astype(jnp.uint32) << one_u) | m.astype(jnp.uint32))
    vals = list(vals)
    span = BR * LANES
    k = 1
    b = 0
    while k < span:
        pa = flat_shift_up(pack, k, 0, interpret)
        bshift = np.uint32(b)
        take = ((pa & one_u) == one_u) \
            & (((pa >> one_u) >> bshift) & one_u == one_u)
        keep = ((pack & one_u) == one_u) \
            & (((pack >> one_u) >> bshift) & one_u == np.uint32(0))
        pack = jnp.where(take, pa, jnp.where(keep, pack, jnp.uint32(0)))
        vals = [jnp.where(take, flat_shift_up(v, k, 0, interpret),
                          jnp.where(keep, v, jnp.uint32(0)))
                for v in vals]
        k <<= 1
        b += 1

    valid = q < cnt
    lane1 = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    for k in range(nstreams):
        v = jnp.where(valid, vals[k], jnp.uint32(0))
        ext = jnp.concatenate([v, jnp.zeros((8, LANES), v.dtype)])
        shifted = flat_shift(ext, s, 0, interpret)
        first = jnp.where(lane1 < s, tails[trow0 + k:trow0 + k + 1, :],
                          shifted[0:1, :])
        blk = jnp.concatenate([first, shifted[1:]])
        bufs[k][:] = blk
        pltpu.make_async_copy(
            bufs[k], out_refs[k].at[pl.ds(row0, BR + 8)],
            sems.at[srow0 + k]).start()
    newp = base + cnt
    rel = newp // _L32 - base // _L32
    for k in range(nstreams):
        pltpu.make_async_copy(
            bufs[k], out_refs[k].at[pl.ds(row0, BR + 8)],
            sems.at[srow0 + k]).wait()
        tails[trow0 + k:trow0 + k + 1, :] = bufs[k][pl.ds(rel, 1), :]
    wptr[wslot] = newp
    return newp


def _compact_streams(nstreams, BR, mask_ref, streams, out_refs, cnt_ref,
                     wptr, tails, bufs, sems, interpret=False,
                     out_rows=None):
    """``out_rows``: the rows of the output arrays where they are fewer
    than the input's blocks and one window."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        wptr[0] = 0
        for k in range(nstreams):
            tails[k:k + 1, :] = jnp.zeros((1, LANES), jnp.uint32)

    m = (mask_ref[:] != 0).astype(jnp.int32)
    vals = [st[:] for st in streams]
    base = wptr[0]  # write pointer before this block's compaction
    newp = _compact_write(BR, m, vals, out_refs, wptr, 0, tails, 0,
                          bufs, sems, 0, interpret,
                          None if out_rows is None else out_rows - (BR + 8))

    @pl.when(i == pl.num_programs(0) - 1)
    def _():
        cnt_ref[0] = newp
        # The documented contract zero-pads the tail; real HBM outputs are
        # not zero-initialized, so sweep zero windows over whatever lies
        # beyond the final write window.
        total_rows = pl.num_programs(0) * BR + BR + 8 \
            if out_rows is None else out_rows
        start = base // LANES + BR + 8
        nwin = (total_rows - start + (BR + 8) - 1) // (BR + 8)
        for k in range(nstreams):
            bufs[k][:] = jnp.zeros((BR + 8, LANES), jnp.uint32)

        def zero_one(w, _):
            for k in range(nstreams):
                pltpu.make_async_copy(
                    bufs[k],
                    out_refs[k].at[pl.ds(jnp.minimum(
                        start + w * (BR + 8),
                        total_rows - (BR + 8)), BR + 8)],
                    sems.at[k]).start()
            for k in range(nstreams):
                pltpu.make_async_copy(
                    bufs[k],
                    out_refs[k].at[pl.ds(jnp.minimum(
                        start + w * (BR + 8),
                        total_rows - (BR + 8)), BR + 8)],
                    sems.at[k]).wait()
            return _

        jax.lax.fori_loop(0, nwin, zero_one, 0)


# ---------------------------------------------------------------------------
# partition_hist / partition_scatter — the fused shuffle partitioner
# ---------------------------------------------------------------------------
# The counted padded exchange (parallel/shuffle._padded_partition) needs a
# STABLE partition of every payload leaf into <= W+1 contiguous buckets
# (W live targets + the dead-row tail). The XLA route is a full stable
# multi-operand `jax.lax.sort` by target — a comparison network priced
# O(n log n) (96-192 ms for a 33M-row multi-operand sort on v5e) where
# the problem only needs a counting sort. These two kernels replace it
# with the SURVEY §7 shape: one histogram pass and one scatter pass,
# both sequential HBM streams.
#
# * ``partition_hist``   — pass 1: streams the target-id blocks once and
#   emits the per-block × per-bucket histogram. Summed over blocks it is
#   the counts vector (replacing W compare-sum passes of
#   shuffle._target_counts); exclusively scanned it is the bucket start
#   offsets. Zero extra passes over payload.
# * ``partition_scatter`` — pass 2: a (nbuckets, blocks) grid, bucket-
#   major. TPU grid order is sequential, so appending each block's
#   bucket-w rows (staged-shift compaction, `_compact_write`'s
#   partial-row-tail discipline, ONE global write pointer) IS a stable
#   counting sort: bucket 0's rows land first in block order, then
#   bucket 1's, … — bit-for-bit the permutation `jax.lax.sort(…,
#   is_stable=True)` by target produces. Every payload leaf rides the
#   same pass as a u32 leg, so one kernel materializes the whole
#   partition (varbytes word legs included).
#
# Cost: pass 2 visits every block once a bucket, and its time goes with
# those visits. A visit that finds rows of its bucket pays a fixed
# ~1.6 us of latency (one serial chain: scan, count to a scalar, the
# pointer through SMEM, the staged passes each waiting for the roll
# before, a DMA started and waited for) whatever the block's height,
# so the block is as TALL as VMEM allows (`partition_block_rows`: 256
# rows up to 10 legs, fewer for wider payloads): the passes of its 32
# vregs an array overlap and the step runs at what its XLU and VALU
# slots carry, 0.165 ns a row and bucket at 2 legs (PERF.md section 6,
# PR 47; at 32 rows a block, before, the fixed part was 53 of the 64 ms
# the kernel took a join-w4 query). The input is re-streamed once a
# bucket, W + 1 reads for one write: a win over the sort up to W≈16
# (shuffle routes by world size; a visit that finds no row of its
# bucket skips compaction and DMA, so clustered inputs pay less).
# ---------------------------------------------------------------------------

# VMEM the partition kernel's blocks may take: the id block and a block
# a leg, each double-buffered by the pipeline, and a write window a leg
# (the compaction's own temporaries come to as much again, inside
# Mosaic's 16 MiB)
_PARTITION_VMEM_BYTES = 4 << 20
# taller buys nothing: 13.23 ms at 256 and at 512 rows for join-w4's 16M
# rows, 2 legs (the passes go from 15 to 16, the compile from 2 to 4 s)
_PARTITION_MAX_BLOCK_ROWS = 256


def partition_block_rows(legs: int) -> int:
    """Rows of a `partition_scatter` block for a payload of ``legs`` u32
    legs: the tallest power of two from 32 to 256 whose blocks fit the
    VMEM budget (256 up to 10 legs, 128 to 20, 64 to 42, then 32)."""
    per_row = (2 * (1 + legs) + legs) * LANES * 4
    fit = max(_PARTITION_VMEM_BYTES // per_row, 32)
    return min(1 << (fit.bit_length() - 1), _PARTITION_MAX_BLOCK_ROWS)


def partition_scatter_steps(n: int, nbuckets: int, legs: int) -> int:
    """Grid steps `partition_scatter` takes over n rows: every block
    once a bucket (what the host adds to
    ``cylon_partition_steps_total`` a dispatch)."""
    span = partition_block_rows(legs) * LANES
    return nbuckets * max(-(-n // span), 1)


def partition_hist(t_s: jnp.ndarray, nbuckets: int, block_rows: int = 32,
                   interpret: bool = False) -> jnp.ndarray:
    """Per-block bucket histogram of a target-id stream.

    t_s: (n,) int32 bucket ids in [0, nbuckets); out-of-range ids are
    never counted (padding uses id nbuckets). Returns (blocks,
    nbuckets) int32 with blocks = ceil(n / (block_rows*128)):
    ``out[b, w]`` = #rows of block b with id w. ``out.sum(0)`` is the
    counts vector; an exclusive scan of it the bucket starts.
    Requires nbuckets <= 128 (one lane row carries a block's histogram).
    """
    n = t_s.shape[0]
    BR = block_rows
    assert BR % 8 == 0 and BR >= 8
    assert 1 <= nbuckets <= LANES
    blocks = max(-(-n // (BR * LANES)), 1)
    rows = blocks * BR
    t2 = pad_rows(t_s.astype(jnp.int32), rows, fill=nbuckets)

    def kernel(t_ref, hist_ref):
        tv = t_ref[:]
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
        row = jnp.zeros((1, LANES), jnp.int32)
        for w in range(nbuckets):
            c = jnp.sum((tv == w).astype(jnp.int32))
            row = jnp.where(lane == w, c, row)
        # Mosaic wants output blocks of (8k, 128): each grid step owns
        # one full (8, 128) tile holding its histogram row 8 times
        hist_ref[:] = jnp.broadcast_to(row, (8, LANES))

    res = pl.pallas_call(
        kernel,
        name="partition_hist",
        out_shape=jax.ShapeDtypeStruct((blocks * 8, LANES), jnp.int32),
        grid=(blocks,),
        in_specs=[pl.BlockSpec((BR, LANES), lambda b: (b, 0),
                               memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec((8, LANES), lambda b: (b, 0),
                               memory_space=pltpu.VMEM),
        interpret=interpret,
    )
    with _x32_trace():
        res = res(t2)
    return res[::8, :nbuckets]


def partition_scatter(t_s: jnp.ndarray, streams: Sequence[jnp.ndarray],
                      nbuckets: int, block_rows: Optional[int] = None,
                      interpret: bool = False
                      ) -> Tuple[jnp.ndarray, ...]:
    """Stable counting scatter of u32 streams into bucket-contiguous
    layout — the partition permutation applied to every leg at once.

    t_s: (n,) int32 bucket ids in [0, nbuckets); streams: (n,) u32 legs
    (callers bitcast/split wider dtypes). Returns one (n,) u32 array
    per leg holding ``leg[perm]`` where perm is the stable sort by
    bucket id — identical to ``jax.lax.sort((t,)+legs, num_keys=1,
    is_stable=True)`` including rows of the last (dead) bucket.

    Grid is (nbuckets, blocks), bucket-major; grid order on TPU is
    sequential, so the single carried write pointer makes the appends a
    stable counting sort. A (bucket, block) pair with no matching rows
    skips its compaction and DMA entirely. ``block_rows`` None: as tall
    as the legs allow (`partition_block_rows`); a test passes a height.
    """
    n = t_s.shape[0]
    L = len(streams)
    BR = partition_block_rows(L) if block_rows is None else block_rows
    assert BR % 8 == 0 and BR >= 8
    assert 1 <= nbuckets <= LANES
    assert L >= 1
    for s in streams:
        assert s.dtype == jnp.uint32, \
            f"partition_scatter takes u32 legs, got {s.dtype}"
        assert s.shape == (n,)
    blocks = max(-(-n // (BR * LANES)), 1)
    rows = blocks * BR
    # pad id nbuckets: matches NO grid bucket, so padding is never
    # scattered and the write pointer ends exactly at n
    t2 = pad_rows(t_s.astype(jnp.int32), rows, fill=nbuckets)
    s2 = [pad_rows(s, rows) for s in streams]

    out_rows = rows + BR + 8  # append windows may extend past rows

    scratch = ([pltpu.SMEM((1,), jnp.int32),
                pltpu.VMEM((L, LANES), jnp.uint32)]
               + [pltpu.VMEM((BR + 8, LANES), jnp.uint32)
                  for _ in range(L)]
               + [pltpu.SemaphoreType.DMA((L,))])

    out_shapes = [jax.ShapeDtypeStruct((out_rows, LANES), jnp.uint32)
                  for _ in range(L)]

    def kernel(t_ref, *rest):
        srefs = rest[:L]
        outs = list(rest[L:2 * L])
        wptr = rest[2 * L]
        tails = rest[2 * L + 1]
        bufs = list(rest[2 * L + 2:2 * L + 2 + L])
        sems = rest[2 * L + 2 + L]
        w = pl.program_id(0)
        b = pl.program_id(1)

        @pl.when((w == 0) & (b == 0))
        def _():
            # jnp.int32, not a bare 0: a weak python literal survives
            # into the kernel jaxpr and is re-canonicalized to int64
            # when the interpret lowering runs under jax_enable_x64 —
            # the store then fails the dynamic_update_slice dtype check
            wptr[0] = jnp.int32(0)
            tails[:] = jnp.zeros((L, LANES), jnp.uint32)

        m = (t_ref[:] == w).astype(jnp.int32)

        @pl.when(jnp.sum(m) > 0)
        def _():
            _compact_write(BR, m, [r[:] for r in srefs], outs, wptr, 0,
                           tails, 0, bufs, sems, 0, interpret)

    res = pl.pallas_call(
        kernel,
        name="partition_scatter",
        out_shape=out_shapes,
        grid=(nbuckets, blocks),
        in_specs=[pl.BlockSpec((BR, LANES), lambda w, b: (b, 0),
                               memory_space=pltpu.VMEM)] * (1 + L),
        out_specs=[pl.BlockSpec(memory_space=pl.ANY)] * L,
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(has_side_effects=True),
        interpret=interpret,
    )
    with _x32_trace():
        res = res(t2, *s2)
    return tuple(o.reshape(-1)[:n] for o in res)


# ---------------------------------------------------------------------------
# groupby_run_reduce — the groupby's reduce step over sorted runs
# ---------------------------------------------------------------------------
# After ops/groupby.presort_groups the rows are sorted by group, dead
# rows last, and group g is the g-th run. A group's aggregate is then a
# reduction over a CONTIGUOUS run and its slot is the run's ordinal —
# what a stream compaction writes — so the reduce step needs no scatter:
# one sequential pass keeps, per stream, a running reduction that
# restarts at every run start, and compacts the value standing at each
# run's last live row to slot g. (A segmented-scan groupby kernel lived
# here through rounds 2-3 and was removed at 10-11M rows/s on a retired
# set-up; this one is measured on the benchmark: PERF.md section 6,
# PR 26.)
# ---------------------------------------------------------------------------

_RUN_OPS = {
    "add": jnp.add,
    "min": jnp.minimum,
    "max": jnp.maximum,
    "first": lambda head, later: head,
}


def _shift_down_one(x, head, interpret):
    """(R,128) block shifted DOWN by one in flat order; element 0 takes
    ``head`` (an (R,128) block: the value carried from the block
    before)."""
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    ra = _roll(x, 1, 1, interpret)
    out = jnp.where(lane >= 1, ra, _roll(ra, 1, 0, interpret))
    return jnp.where(flat_iota(x.shape) == 0, head, out)


def _run_scan(start, vals, kinds, carries, interpret):
    """Inclusive running reductions of (R,128) blocks in flat order that
    RESTART where ``start`` (int32 0/1) holds: out[j] = vals[s..j]
    reduced, s the last run start at or before j. Where the block holds
    no start at or before j the run is still open from the block
    before and its accumulator ``carries[k]`` (an (R,128) broadcast)
    joins in.

    Every combination is SELECTED, never masked by arithmetic: an
    element only ever meets values and partials of its own run, so a
    float sum is formed from the run's own float32 additions alone
    (no prefix difference — the bound on a group's error is relative
    to ITS Σ|x|), and no identity element is needed. Log-shift
    structure of block_cumsum: lanes, then row totals."""
    R = start.shape[0]
    ops = [_RUN_OPS[k] for k in kinds]
    zero = np.int32(0)
    lane = jax.lax.broadcasted_iota(jnp.int32, start.shape, 1)
    row = jax.lax.broadcasted_iota(jnp.int32, start.shape, 0)
    g = start   # a run start lies in the window already reduced
    vs = list(vals)
    k = 1
    while k < LANES:
        ext = (lane >= k) & (g == zero)
        vs = [jnp.where(ext, op(_roll(v, k, 1, interpret), v), v)
              for op, v in zip(ops, vs)]
        g = g | jnp.where(lane >= k, _roll(g, k, 1, interpret), zero)
        k <<= 1
    # row totals (the open tail of each row), scanned the same way
    tg = jnp.broadcast_to(g[:, LANES - 1:LANES], (R, LANES))
    ts = [jnp.broadcast_to(v[:, LANES - 1:LANES], (R, LANES)) for v in vs]
    k = 1
    while k < R:
        ext = (row >= k) & (tg == zero)
        ts = [jnp.where(ext, op(_roll(t, k, 0, interpret), t), t)
              for op, t in zip(ops, ts)]
        tg = tg | jnp.where(row >= k, _roll(tg, k, 0, interpret), zero)
        k <<= 1
    # what enters each row: the rows before it back to their last
    # start, or — no start yet in this block — the carry joined on
    has_prev = row >= 1
    started = jnp.where(has_prev, _roll(tg, 1, 0, interpret), zero) != zero
    outs = []
    for op, v, t, c in zip(ops, vs, ts, carries):
        pt = _roll(t, 1, 0, interpret)
        cin = jnp.where(started, pt, jnp.where(has_prev, op(c, pt), c))
        outs.append(jnp.where(g != zero, v, op(cin, v)))
    return outs


def groupby_run_reduce(new_grp: jnp.ndarray, emit: jnp.ndarray,
                       streams: Sequence[jnp.ndarray],
                       kinds: Sequence[str], num_segments: int,
                       block_rows: int = 128, interpret: bool = False
                       ) -> Tuple[Tuple[jnp.ndarray, ...], jnp.ndarray]:
    """Reduce every run of the presorted rows into its slot — the
    scatter-free replacement of the O(n) ``segment_*`` scatters of
    ops/groupby.sorted_segment_aggregate (8.8 ns a row EACH on v5e
    whatever the locality).

    new_grp, emit: (n,) bool, rows sorted by group with live rows
    (``emit``) a PREFIX and ``new_grp`` marking each live run's first
    row. streams: (n,) int32/float32 arrays; kinds[k] in "add" / "min"
    / "max" / "first" (the run's first element). What dead rows hold
    is never read into a result.

    ONE sequential pass: the running reductions restart at each run
    start (_run_scan; a run longer than a block carries its open
    accumulator to the next grid step), and the value standing at a
    run's LAST live row is emitted one element later — at the next
    run's start, or at the first dead row — which needs no look-ahead
    across a block edge. The emitted elements are compacted onto slot
    g = the run's ordinal (_compact_write). Elements at and past n
    (the ragged last block; n itself is padded only to a multiple of
    128, so a 128-multiple n is read in place) are made dead in the
    kernel; a run still open at the very last element of the grid is
    handed back as it stands and placed by one select here.

    block_rows 128: at 1e8 rows in 1e6 runs, 4 streams, a v5e takes
    73.1 / ~44 / 37.6 / 35.8 ms at 32 / 64 / 128 / 256 rows a block
    (16.3 ms when no block ends a run: the scans and the reads), and
    Mosaic compiles the body in 1.0 / 1.6 / 2.8 s at 64 / 128 / 256
    (PERF.md section 6, PR 26).

    Returns (outs, count): outs[k] is (num_segments,) in streams[k]'s
    dtype, slot g < count holding run g's reduction; slots at and past
    ``count`` (the number of runs, int32) are NOT defined — callers
    mask by count. More runs than ``num_segments`` is a caller error:
    the excess is dropped, never written out of bounds.
    """
    n = new_grp.shape[0]
    K = len(streams)
    BR = block_rows
    assert BR % 8 == 0 and BR >= 8
    assert K >= 1 and len(kinds) == K
    for s in streams:
        assert s.shape == (n,) and s.dtype in (jnp.int32, jnp.float32), \
            f"groupby_run_reduce takes int32/float32 streams, got {s.dtype}"
    span = BR * LANES
    rows = rows_for(n)
    blocks = -(-rows // BR)   # the last block may be ragged
    fl2 = pad_rows(new_grp.astype(jnp.int32)
                   | (emit.astype(jnp.int32) << np.int32(1)), rows)
    s2 = [pad_rows(s, rows) for s in streams]
    dtypes = [s.dtype for s in streams]

    out_rows = rows_for(num_segments) + BR + 8  # the last write window
    # tails rows: [0,K) the compaction's partial-row carries, [K,2K) each
    # stream's open accumulator (last row of its scan), 2K the live flags
    t_acc, t_live = K, 2 * K
    fin_rows = -(-(K + 1) // 8) * 8
    out_shapes = ([jax.ShapeDtypeStruct((out_rows, LANES), jnp.uint32)] * K
                  + [jax.ShapeDtypeStruct((1,), jnp.int32),
                     jax.ShapeDtypeStruct((fin_rows, LANES), jnp.uint32)])
    scratch = ([pltpu.SMEM((1,), jnp.int32),
                pltpu.VMEM((2 * K + 1, LANES), jnp.uint32)]
               + [pltpu.VMEM((BR + 8, LANES), jnp.uint32) for _ in range(K)]
               + [pltpu.SemaphoreType.DMA((K,))])

    def kernel(fl_ref, *rest):
        srefs = rest[:K]
        outs = list(rest[K:2 * K])
        cnt_ref, fin_ref = rest[2 * K], rest[2 * K + 1]
        wptr = rest[2 * K + 2]
        tails = rest[2 * K + 3]
        bufs = list(rest[2 * K + 4:3 * K + 4])
        sems = rest[3 * K + 4]
        i = pl.program_id(0)

        @pl.when(i == 0)
        def _():
            wptr[0] = jnp.int32(0)  # strong: see partition_scatter
            tails[:] = jnp.zeros((2 * K + 1, LANES), jnp.uint32)

        def carried(trow, dtype):
            """Last element of the block before, broadcast (zeros at
            i == 0, where nothing reads it: row 0 is a run start or
            dead)."""
            c = jnp.full((BR, LANES), tails[trow, LANES - 1], jnp.uint32)
            return jax.lax.bitcast_convert_type(c, dtype)

        one = np.int32(1)
        inside = i * np.int32(span) + flat_iota((BR, LANES)) < np.int32(n)
        fl = jnp.where(inside, fl_ref[:], np.int32(0))
        start = fl & one
        live = (fl >> one) & one
        carries = [carried(t_acc + k, dtypes[k]) for k in range(K)]
        acc = _run_scan(start, [r[:] for r in srefs], kinds, carries,
                        interpret)
        prev_live = _shift_down_one(live, carried(t_live, jnp.int32),
                                    interpret)
        # a run ended one element back: a new run starts here, or the
        # live prefix is over
        m = (start | (live ^ one)) & prev_live
        vals = [jax.lax.bitcast_convert_type(
            _shift_down_one(a, c, interpret), jnp.uint32)
            for a, c in zip(acc, carries)]
        for k in range(K):
            tails[t_acc + k:t_acc + k + 1, :] = \
                jax.lax.bitcast_convert_type(acc[k][BR - 1:BR, :],
                                             jnp.uint32)
        tails[t_live:t_live + 1, :] = jax.lax.bitcast_convert_type(
            live[BR - 1:BR, :], jnp.uint32)

        # a block inside one long run ends no run: no compaction, no DMA.
        # The bound keeps every write window inside the outputs.
        @pl.when((jnp.sum(m) > 0) & (wptr[0] <= np.int32(num_segments)))
        def _():
            _compact_write(BR, m, vals, outs, wptr, 0, tails, 0, bufs,
                           sems, 0, interpret)

        @pl.when(i == pl.num_programs(0) - 1)
        def _():
            cnt_ref[0] = wptr[0]
            fin_ref[0:K + 1, :] = tails[t_acc:t_acc + K + 1, :]

    res = pl.pallas_call(
        kernel,
        name="groupby_run_reduce",
        out_shape=out_shapes,
        grid=(blocks,),
        in_specs=[pl.BlockSpec((BR, LANES), lambda i: (i, 0),
                               memory_space=pltpu.VMEM)] * (1 + K),
        out_specs=([pl.BlockSpec(memory_space=pl.ANY)] * K
                   + [pl.BlockSpec(memory_space=pltpu.SMEM),
                      pl.BlockSpec((fin_rows, LANES), lambda i: (0, 0),
                                   memory_space=pltpu.VMEM)]),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(has_side_effects=True),
        interpret=interpret,
    )
    with _x32_trace():
        res = res(fl2, *s2)
    # the run still open at the grid's very last element (n fills the
    # blocks exactly and row n-1 is live) was not flushed: it is run
    # `count`, and its reductions are the accumulators as they stand
    count, fin = res[K][0], res[K + 1][:, LANES - 1]
    open_end = fin[K] != 0
    slot = jnp.arange(num_segments, dtype=jnp.int32)
    outs = tuple(
        jnp.where(open_end & (slot == count), fin[k].view(s.dtype),
                  o.reshape(-1)[:num_segments].view(s.dtype))
        for k, (o, s) in enumerate(zip(res[:K], streams)))
    return outs, count + open_end.astype(jnp.int32)


# ---------------------------------------------------------------------------
# groupby_dense_reduce — a groupby over few groups, with no sort
# ---------------------------------------------------------------------------
# When the key's observed range is small (ops/groupby.group_path) a row's
# slot is ``key - lo`` and nothing has to be sorted: ONE pass over the key
# and the value columns keeps, per slot, a count and one accumulator a
# stream in VMEM and writes the table once at the end. A row reaches its
# slot by compare-and-select on the VPU: the block's rows are swept once
# a CHUNK of 8 slots (whose accumulators, a vreg a slot a stream, stay in
# vector registers), so the time goes with slots x rows / 1,024, not with
# bytes.
#
# The table holds one (1, 128) row a slot a stream: 128 independent
# partial sums by lane. At the end of a block each slot's accumulator
# vreg is folded over its 8 sublanes and the chunk's 8 rows join the
# table as ONE aligned (8, 128) tile. An int32 stream adds exactly
# (wrapping, as XLA's own int32 sum). A float stream's block partial is
# formed by plain float32 additions (at most block_rows / 8 + 3 a lane)
# and joins a float32 (hi, lo) pair by a two-sum, so that across blocks
# nothing is lost: the table holds each lane's sum to about 2^-48
# relative, and the wrapper folds the 128 pairs pairwise with two-sums
# and rounds ONCE. (Adding the blocks' partials one after another into a
# plain float32 slot is a random walk of half-ulps: ~120 units of 2^-24
# at 1e5 blocks.) A "planes" stream is an int64 column as its two word
# planes: each block is cut, in the kernel, into three limbs of 22, 22
# and 20 bits (the last signed, biased to be non-negative), so that a
# block's partial of a limb (at most 512 of them a lane: such a pass runs
# blocks of 512 rows) is an int32 that cannot wrap; a limb's partial joins
# an int32 (hi, lo) pair, lo kept under 2^31 and its carry counted in hi,
# so the table holds each lane's sums exactly whatever the rows, and the
# wrapper adds lanes and limbs in 96 bits (`wideint`): the EXACT sum of
# the int64s, wider than an int64, for the caller to check and cut.
# ---------------------------------------------------------------------------

DENSE_CHUNK = 8   # slots swept together: the rows of one table tile
# vregs of rows a step of a chunk's sweep: an inner loop unrolled whole
# (Mosaic takes a fori_loop's `unroll` only whole or not at all). In
# groupby-q4's shape the pass takes 35.9 ms at 1, 20.5 at 8, 19.5 at 16,
# 18.8 at 32, 18.2 with the block's 128 (PERF.md section 6, PR 34)
DENSE_UNROLL = 16
# a "planes" stream's limbs: bits of the two low ones (the third takes the
# 20 that are left, sign included), and the rows of a block of such a
# pass: 512 limbs of 22 bits a lane stay under 2^31
DENSE_LIMB_BITS = 22
DENSE_PLANES_BLOCK_ROWS = 512


def _fold_pairs(hi, lo):
    """(..., W) float32 (hi, lo) pairs -> (...,) float32: the W pairs
    added pairwise, every addition of two ``hi`` a two-sum whose error
    joins ``lo``; one rounding at the end."""
    w = hi.shape[-1]
    assert w & (w - 1) == 0
    while w > 1:
        w //= 2
        hi, e = _two_sum(hi[..., :w], hi[..., w:2 * w])
        lo = lo[..., :w] + lo[..., w:2 * w] + e
    return hi[..., 0] + lo[..., 0]


def groupby_dense_reduce(keys: jnp.ndarray, lo, live_slots,
                         streams: Sequence, kinds: Sequence[str], slots: int,
                         block_rows: int = 1024, interpret: bool = False
                         ) -> Tuple[jnp.ndarray, Tuple]:
    """Per-slot count and per-slot sums of unsorted rows: row r belongs
    to slot ``keys[r] - lo`` (int32, wrapping) and to none when that lies
    outside [0, live_slots) — how a caller keeps a dead row out is to
    hand it the key ``lo - 1``.

    keys: (n,) int32. lo, live_slots: int32 scalars (traced: neither is
    in the program's shape; live_slots <= slots, and only that many
    slots are swept). streams: (n,) int32 /
    float32; kinds[k] is ``"int"`` (an int32 stream, its exact wrapping
    int32 sum) or ``"float"`` (a float32 sum, compensated as described
    above; an int32 stream is converted to float32 element by element
    first, once a block) or ``"planes"`` (the stream is an int64
    column's ``uint32[2, n]`` word planes: its exact sum, in 96 bits; the
    kernel reads the array as (rows, 2, 128) blocks, which is how its
    (2, 128) tiles lie in HBM already, so all it costs before the kernel
    is the padding to whole rows). What a row outside every slot holds is
    never read into a result.

    Returns (count, sums): count (slots,) int32 rows a slot, sums[k]
    (slots,) int32 or float32, for a "planes" stream the triple (w2, w1,
    w0) of (slots,) uint32 words of the sum as a 96-bit two's-complement
    integer, most significant first; slots at and past live_slots read 0.
    """
    n = keys.shape[0]
    K = len(streams)
    U = DENSE_CHUNK
    assert keys.dtype == jnp.int32 and len(kinds) == K
    assert 1 <= n < (1 << 30) and slots >= 1
    # the kernel's inputs ((n,) arrays and (2, n) planes), and the LANES
    # it accumulates: (how it joins the table, where a block's values
    # are: an input or a scratch)
    ins, lanes, lanes_of = [], [], []
    for s, kind in zip(streams, kinds):
        assert kind in ("int", "float", "planes")
        lanes_of.append(len(lanes))
        if kind == "planes":
            assert s.shape == (2, n) and s.dtype == jnp.uint32
            lanes += [("wide", ("limb", len(ins), j)) for j in range(3)]
            ins.append(s)
            continue
        assert s.shape == (n,) and (
            s.dtype == jnp.int32 or (kind == "float"
                                     and s.dtype == jnp.float32)), \
            f"groupby_dense_reduce: a {kind} stream of {s.dtype}"
        # an int32 column summed as float is converted in the kernel
        conv = kind == "float" and s.dtype == jnp.int32
        lanes.append((kind, ("conv" if conv else "in", len(ins))))
        ins.append(s)
    if "planes" in kinds:
        block_rows = min(block_rows, DENSE_PLANES_BLOCK_ROWS)
    step_rows = 8 * DENSE_UNROLL
    BR = min(block_rows, -(-rows_for(n) // step_rows) * step_rows)
    assert BR % step_rows == 0
    rows = max(rows_for(n), BR)      # a small input: one whole block
    span = BR * LANES
    blocks = -(-rows // BR)          # the last block may be ragged
    SP = -(-slots // U) * U          # table rows: whole chunks
    k2 = pad_rows(keys, rows)
    stream_spec = pl.BlockSpec((BR, LANES), lambda i: (i, 0),
                               memory_space=pltpu.VMEM)
    s2, in_specs = [], []
    for s in ins:
        if s.ndim == 1:
            s2.append(pad_rows(s, rows))
            in_specs.append(stream_spec)
            continue
        # (2, n) -> (rows, 2, 128): the array's own tiles, padded
        s2.append(jnp.swapaxes(jax.lax.bitcast_convert_type(jnp.pad(
            s, ((0, 0), (0, rows * LANES - n))), jnp.int32).reshape(
                2, rows, LANES), 0, 1))
        in_specs.append(pl.BlockSpec((BR, 2, LANES), lambda i: (i, 0, 0),
                                     memory_space=pltpu.VMEM))
    # scratch: one array a lane whose values the kernel makes
    made = [src for _, src in lanes if src[0] != "in"]
    # table arrays: the count, then one an "int" lane and (hi, lo) a
    # "float" or a "wide" one
    tab_dtypes = [jnp.int32]
    tab_of = []
    for how, _ in lanes:
        tab_of.append(len(tab_dtypes))
        tab_dtypes += {"int": [jnp.int32], "float": [jnp.float32] * 2,
                       "wide": [jnp.int32] * 2}[how]
    T, L, I = len(tab_dtypes), len(lanes), len(ins)
    acc_dtypes = [jnp.int32] + [jnp.float32 if how == "float" else jnp.int32
                                for how, _ in lanes]
    low = np.int32((1 << DENSE_LIMB_BITS) - 1)
    top_shift = 2 * DENSE_LIMB_BITS - 32         # the hi word's low bits
    bias = np.int32(1 << (63 - 2 * DENSE_LIMB_BITS))

    def limb(hi, lo_, j):
        """Limb j of the int64 (hi, lo_): value = l0 + l1 * 2^22 + t *
        2^44, t the signed top 20 bits (biased by 2^19 here: every limb
        is a non-negative int32)."""
        if j == 0:
            return lo_ & low
        if j == 1:
            return jax.lax.shift_right_logical(
                lo_, np.int32(DENSE_LIMB_BITS)) | (
                    (hi & np.int32((1 << top_shift) - 1))
                    << np.int32(32 - DENSE_LIMB_BITS))
        return (hi >> np.int32(top_shift)) + bias

    def kernel(par_ref, k_ref, *rest):
        irefs = list(rest[:I])
        tabs = rest[I:I + T]
        rel_ref = rest[I + T]
        made_refs = dict(zip(made, rest[I + T + 1:]))
        i = pl.program_id(0)

        @pl.when(i == 0)
        def _():
            for t, d in zip(tabs, tab_dtypes):
                t[...] = jnp.zeros((SP, LANES), d)

        # the block's slot ids, once: key - lo, -1 past the live slots
        # (the last chunk is swept whole) and past the last row
        rel = k_ref[:] - par_ref[0]
        rel = jnp.where(rel < par_ref[1], rel, np.int32(-1))
        if blocks * span != n:
            inside = (i * np.int32(span) + flat_iota((BR, LANES))
                      < np.int32(n))
            rel = jnp.where(inside, rel, np.int32(-1))
        rel_ref[:] = rel
        for src, ref in made_refs.items():
            if src[0] == "conv":
                ref[:] = irefs[src[1]][:].astype(jnp.float32)
        for at in sorted({src[1] for src in made if src[0] == "limb"}):
            # each plane is read once (a strided read: one sublane a tile)
            hi, lo_ = irefs[at][:, 0, :], irefs[at][:, 1, :]
            for j in range(3):
                made_refs[("limb", at, j)][:] = limb(hi, lo_, j)
        vrefs = [irefs[src[1]] if src[0] == "in" else made_refs[src]
                 for _, src in lanes]
        sub = jax.lax.broadcasted_iota(jnp.int32, (8, LANES), 0)

        def sweep(s0):
            """One step of a chunk's pass over the block: DENSE_UNROLL
            vregs of rows against the 8 slots from ``s0``."""
            def vreg(r0, accs):
                r0 = pl.multiple_of(r0, 8)
                d = rel_ref[pl.ds(r0, 8), :] - s0
                vals = [v[pl.ds(r0, 8), :] for v in vrefs]
                out = []
                for u in range(U):
                    m = d == np.int32(u)
                    a = accs[u]
                    out.append(tuple(
                        [a[0] + jnp.where(m, np.int32(1), np.int32(0))]
                        + [ak + jnp.where(m, v, jnp.zeros_like(v))
                           for ak, v in zip(a[1:], vals)]))
                return tuple(out)

            def step(g, accs):
                return jax.lax.fori_loop(
                    np.int32(0), np.int32(DENSE_UNROLL),
                    lambda h, a: vreg(g * np.int32(step_rows)
                                      + h * np.int32(8), a),
                    accs, unroll=True)
            return step

        def tile(accs, j):
            """Row u: slot u's accumulator j folded over its sublanes."""
            out = jnp.zeros((8, LANES), acc_dtypes[j])
            for u in range(U):
                r = jnp.sum(accs[u][j], axis=0, keepdims=True)
                out = jnp.where(sub == np.int32(u),
                                jnp.broadcast_to(r, (8, LANES)), out)
            return out

        def chunk(c, carry):
            s0 = c * np.int32(U)
            zero = tuple(tuple(jnp.zeros((8, LANES), d) for d in acc_dtypes)
                         for _ in range(U))
            accs = jax.lax.fori_loop(np.int32(0), np.int32(BR // step_rows),
                                     sweep(s0), zero)
            at = pl.ds(pl.multiple_of(s0, 8), 8)
            tabs[0][at, :] = tabs[0][at, :] + tile(accs, 0)
            for k, (how, _) in enumerate(lanes):
                t, p = tab_of[k], tile(accs, 1 + k)
                if how == "int":
                    tabs[t][at, :] = tabs[t][at, :] + p
                elif how == "wide":
                    # lo and p are both under 2^31: a sum past it shows
                    # as the sign bit, which moves to hi
                    lo_ = tabs[t + 1][at, :] + p
                    tabs[t + 1][at, :] = lo_ & np.int32(0x7FFFFFFF)
                    tabs[t][at, :] = tabs[t][at, :] + jnp.where(
                        lo_ < 0, np.int32(1), np.int32(0))
                else:
                    hi, e = _two_sum(tabs[t][at, :], p)
                    tabs[t][at, :] = hi
                    tabs[t + 1][at, :] = tabs[t + 1][at, :] + e
            return carry

        chunks = (par_ref[1] + np.int32(U - 1)) // np.int32(U)
        jax.lax.fori_loop(np.int32(0), chunks, chunk, np.int32(0))

    tab_spec = pl.BlockSpec((SP, LANES), lambda i: (0, 0),
                            memory_space=pltpu.VMEM)
    # the table and every input block are held twice (the pipeline's
    # buffers), the scratch once
    vmem = (2 * T * SP * LANES * 4
            + (2 * (1 + sum(s.ndim for s in ins)) + 1 + len(made)) * span * 4
            + (16 << 20))
    res = pl.pallas_call(
        kernel,
        name="groupby_dense_reduce",
        out_shape=[jax.ShapeDtypeStruct((SP, LANES), d) for d in tab_dtypes],
        grid=(blocks,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), stream_spec]
        + in_specs,
        out_specs=[tab_spec] * T,
        scratch_shapes=[pltpu.VMEM((BR, LANES), jnp.int32)]
        + [pltpu.VMEM((BR, LANES), jnp.float32 if src[0] == "conv"
                      else jnp.int32) for src in made],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem),
        interpret=interpret,
    )
    with _x32_trace():
        par = jnp.stack([jnp.asarray(lo, jnp.int32),
                         jnp.asarray(live_slots, jnp.int32)])
        tabs = [t[:slots] for t in res(par, k2, *s2)]
        count = tabs[0].sum(axis=1, dtype=jnp.int32)

        def lane_sum(t):
            """A slot's 128 lanes of hi * 2^31 + lo as ONE integer of
            three words: every partial sum below fits an int32."""
            hi, lo_ = tabs[t], tabs[t + 1]
            parts = ((hi, 31), (lo_ >> 16, 16), (lo_ & np.int32(0xFFFF), 0))
            total = W.wide_const(0, 3)
            for x, k in parts:
                total = W.wide_add(total, W.wide_shl(W.wide_from_int32(
                    x.sum(axis=1, dtype=jnp.int32), 3), k))
            return total

        def planes_sum(k):
            """l0 + l1 * 2^22 + (t' - rows * 2^19) * 2^44, exactly."""
            t = tab_of[lanes_of[k]]
            s0, s1, s2 = (lane_sum(t + 2 * j) for j in range(3))
            unbias = W.wide_neg(W.wide_shl(W.wide_from_int32(count, 3),
                                           63 - 2 * DENSE_LIMB_BITS))
            top = W.wide_shl(W.wide_add(s2, unbias), 2 * DENSE_LIMB_BITS)
            return W.wide_add(W.wide_add(s0, W.wide_shl(
                s1, DENSE_LIMB_BITS)), top)

        sums = tuple(
            planes_sum(k) if kind == "planes"
            else tabs[tab_of[lanes_of[k]]].sum(axis=1, dtype=jnp.int32)
            if kind == "int"
            else _fold_pairs(tabs[tab_of[lanes_of[k]]],
                             tabs[tab_of[lanes_of[k]] + 1])
            for k, kind in enumerate(kinds))
    return count, sums
