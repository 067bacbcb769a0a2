"""Distributed relational operators: shuffle-composed, per-shard kernels.

The reference composes every distributed op as *local partition + all-to-all
+ local op* (reference: docs/docs/arch.md:48-52; DistributedJoin
table.cpp:656-696; set ops table.cpp:948-992; GroupBy
groupby/groupby.cpp:96-139). The same composition here, but each stage is a
compiled SPMD program over the mesh instead of per-rank C++:

  1. key prep runs on the GLOBAL sharded arrays (elementwise → no comms):
     dtype promotion / dictionary unification, order-preserving key bits,
     murmur-style partition targets;
  2. the shuffle is the two-phase count+exchange from parallel/shuffle.py;
  3. the local stage runs per shard inside `shard_map` — matching keys are
     co-located after the hash shuffle, so per-shard dense ranks + the same
     vectorized kernels as the local path produce the distributed result.

Data-dependent output sizes follow the framework-wide eager discipline:
a count kernel returns per-shard totals, the host picks a pow2 capacity
(bounding recompilation), a materialize kernel fills static-shape outputs
whose padding rows carry emit=False. Results stay sharded; nothing is
gathered to the host.
"""
from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from jax import shard_map

from .. import dtypes
from ..context import CylonContext
from ..data import table as table_mod
from ..data.column import Column, refuse_planes, unify_dictionaries
from ..data.strings import EXACT_KEY_WORDS, pair_k_words as _pair_k
from ..data.table import Table
from ..ops import groupby as _groupby
from ..ops import hash as _hash
from ..ops import join as _join
from ..ops import order as _order
from ..ops import setops as _setops
from ..status import Code, CylonError, CylonPlanError
from ..telemetry import annotate as _annotate, counted_cache, \
    counter as _counter, host_fetch as _host_fetch, ledger as _ledger, \
    span as _span
from . import shard
from ..util import bucket_cap as _bucket_cap, pow2_floor as _pow2_floor
from .shuffle import count_pair, exchange, exchange_pair, \
    replicated_gather


# ---------------------------------------------------------------------------
# payload plumbing
# ---------------------------------------------------------------------------

def _table_payload(t: Table) -> dict:
    p = {}
    for i, c in enumerate(t._columns):
        p[f"d{i}"] = c.data
        p[f"v{i}"] = c.valid_mask()
    return p


# ---------------------------------------------------------------------------
# varbytes (device-native strings) distributed plumbing. A sharded
# varbytes column is a SELF-CONTAINED per-shard layout (shard-relative
# starts), so all content kernels run per shard; moving rows moves their
# words through a SECOND exchange whose "rows" are words — the byte-count
# matrix the reference's ArrowAllToAll length headers carry
# (arrow_all_to_all.cpp:96-107) is exactly this word exchange's count
# phase.
# ---------------------------------------------------------------------------


@counted_cache
def _string_hash_fn(mesh, max_words: int):
    """Per-shard content hashes (h1, h2, h3, len-as-u32) for a sharded
    varbytes column — strings._hash_rows under shard_map (shard-relative
    starts make the per-shard call exact)."""
    from ..data import strings as _strings

    spec = P(mesh.axis_names[0])

    def kernel(words, starts, lengths):
        h1, h2, h3 = _strings._hash_rows(words, starts, lengths, max_words)
        return h1, h2, h3, lengths.astype(jnp.uint32)

    return jax.jit(shard_map(kernel, mesh=mesh, in_specs=(spec,) * 3,
                             out_specs=spec))


def _dist_string_keys(ctx: CylonContext, col: Column):
    """(h1, h2, h3, len) sharded key arrays for one varbytes column."""
    vb = col.varbytes
    return _string_hash_fn(ctx.mesh, vb.max_words)(
        shard.pin(vb.words, ctx), shard.pin(vb.starts, ctx),
        shard.pin(vb.lengths, ctx))


@counted_cache
def _word_lanes_fn(mesh, k_lim: int):
    """Per-shard word-lane lift of a sharded varbytes column
    (shard-relative starts make each shard's gather self-contained —
    no cross-shard indexing escapes the shard_map)."""
    spec = P(mesh.axis_names[0])

    def kernel(words, starts, lengths):
        nw = (lengths + 3) >> 2
        wcap = words.shape[0]
        outs = []
        for k in range(k_lim):
            pos = jnp.clip(starts + k, 0, wcap - 1)
            outs.append(jnp.where(k < nw, jnp.take(words, pos),
                                  jnp.uint32(0)))
        return tuple(outs)

    return jax.jit(shard_map(kernel, mesh=mesh, in_specs=(spec,) * 3,
                             out_specs=(spec,) * k_lim))


def _dist_word_lanes(ctx: CylonContext, col: Column, k_lim: int) -> list:
    vb = col.varbytes
    return list(_word_lanes_fn(ctx.mesh, k_lim)(
        shard.pin(vb.words, ctx), shard.pin(vb.starts, ctx),
        shard.pin(vb.lengths, ctx)))


def _lanes_hash(lanes: Sequence[jnp.ndarray], ln_u32) -> jnp.ndarray:
    """Elementwise partition hash of word lanes + length — the exact-key
    analog of the content-hash h1 (both sides of a join call this with
    the SAME lane count, so equal bytes land on equal shards)."""
    h = ln_u32 * np.uint32(0x9E3779B1)
    for l in lanes:
        h = h * np.uint32(31) + _hash.fmix32(l)
    return _hash.fmix32(h)


# ---------------------------------------------------------------------------
# key programs. What a distributed operator computes elementwise from its
# key columns on the host's side of the exchange is ONE named program a
# call: the partition targets (and the emit mask of a table without a row
# mask) before it, the key bits and the combined key validity after it.
# An eager jnp operation on a sharded column is a program of its own that
# every chip waits for, ~0.84 ms of host each on four chips (PERF.md
# section 6, PR 39). A program is compiled for what the host reads off
# the key columns (`_key_form`), never for a cell or a knob.
# ---------------------------------------------------------------------------

_DICTIONARY = np.empty(0, object)  # marks a traced column as dictionary codes


def _key_form(c: Column, k_words: int = None) -> tuple:
    """What a key program is compiled for, read off one key column:
    ``("plain", storage dtype, dictionary codes?, masked?)``, ``("lanes",
    k, masked?)`` for short varbytes (≤ EXACT_KEY_WORDS words, the pair
    max when ``k_words`` is passed: raw word lanes + length, byte-exact)
    or ``("quad", masked?)`` for longer rows (the content-hash quad)."""
    masked = c.validity is not None
    if not c.is_varbytes:
        return ("plain", str(c.data.dtype), c.is_string, masked)
    k = c.varbytes.max_words if k_words is None \
        else max(int(k_words), c.varbytes.max_words)
    return ("lanes", k, masked) if k <= EXACT_KEY_WORDS \
        else ("quad", masked)


def _key_operands(ctx: CylonContext, c: Column, form: tuple) -> tuple:
    """One key column's arrays as its key program takes them, each on
    the row sharding: the data, or the word lanes and the byte lengths,
    or the content-hash quad (both from named per-shard programs), and
    the validity mask where there is one."""
    if form[0] == "plain":
        ops = [shard.pin(c.data, ctx)]
    elif form[0] == "lanes":
        ops = _dist_word_lanes(ctx, c, form[1]) \
            + [shard.pin(c.varbytes.lengths, ctx)]
    else:
        ops = list(_dist_string_keys(ctx, c))
    if form[-1]:
        ops.append(shard.pin(c.validity, ctx))
    return tuple(ops)


def _key_inputs(ctx: CylonContext, cols: Sequence[Column],
                paired: Sequence[Column] = None):
    """(forms, operands) of the key columns. ``paired``: the other
    side's aligned key columns, so both sides take matching lane
    counts."""
    refuse_planes(cols, "a key across chips")
    forms = tuple(
        _key_form(c, _pair_k(c, paired[j]) if paired is not None else None)
        for j, c in enumerate(cols))
    return forms, tuple(_key_operands(ctx, c, f)
                        for c, f in zip(cols, forms))


def _bits_as_given(form: tuple) -> int:
    """How many of a form's leading operands ARE key bit arrays as they
    stand (the word lanes, the quad): a key-bits program does not copy
    them through."""
    if form[0] == "lanes":
        return form[1]
    return 4 if form[0] == "quad" else 0


def _traced_col_keys(form: tuple, ops: Sequence):
    """One column's (key bit arrays it has to compute, partition hash,
    validity or None), traced inside a key program. Plain columns use
    ordered bits and ops/hash.hash_column as they are, so the host-side
    twins (shard.host_partition_arrays, partition_signature) keep
    agreeing with the device on every row's shard."""
    valid = ops[-1] if form[-1] else None
    if form[0] == "plain":
        c = Column(ops[0], None, valid, _DICTIONARY if form[2] else None)
        return [_order.sort_keys([c])[0]], _hash.hash_column(c), valid
    if form[0] == "lanes":
        k = form[1]
        ln = ops[k].astype(jnp.uint32)
        bits, h1 = [ln], _lanes_hash(ops[:k], ln)
    else:
        bits, h1 = [], ops[0]
    if valid is not None:
        h1 = jnp.where(valid, h1, jnp.uint32(0x9E3779B9))
    return bits, h1, valid


@counted_cache
def _partition_targets_program_fn(mesh, forms: tuple, with_emit: bool):
    """Per-row int32 shard targets of key columns of the given forms, on
    the row sharding: every column's partition hash, the ops/hash.
    hash_columns combine scheme, the modulo. ``with_emit``: also the
    all-ones emit mask of a table that has no row mask."""
    spec = P(mesh.axis_names[0])
    world = mesh.devices.size

    def kernel(cols):
        h = None
        for form, ops in zip(forms, cols):
            hc = _traced_col_keys(form, ops)[1]
            h = hc if h is None else h * np.uint32(31) + hc
        targets = (_hash.fmix32(h) % np.uint32(world)).astype(jnp.int32)
        if with_emit:
            return targets, jnp.ones(targets.shape, bool)
        return (targets,)

    return jax.jit(shard_map(kernel, mesh=mesh, in_specs=(spec,),
                             out_specs=spec))


@counted_cache
def _key_bits_program_fn(mesh, forms: tuple, null_lanes: tuple):
    """The key bit arrays a per-shard join / group / set kernel compares
    and the combined key validity, on the row sharding. Only what has to
    be computed comes out (`_bits_as_given`); no partition hash does:
    no caller past the exchange uses one. ``null_lanes[j]``: column j's
    validity rides as a uint8 key lane after its bits (set operations:
    nulls compare equal)."""
    spec = P(mesh.axis_names[0])

    def kernel(cols):
        out, kv = [], None
        for form, ops, lane in zip(forms, cols, null_lanes):
            bits, _h1, valid = _traced_col_keys(form, ops)
            if valid is None:
                valid = jnp.ones(ops[0].shape, bool)
            out.append(tuple(bits)
                       + ((valid.astype(jnp.uint8),) if lane else ()))
            kv = valid if kv is None else (kv & valid)
        return tuple(out), kv

    return jax.jit(shard_map(kernel, mesh=mesh, in_specs=(spec,),
                             out_specs=spec))


def _dist_key_bits(ctx: CylonContext, cols: Sequence[Column],
                   paired: Sequence[Column] = None,
                   null_lanes: Sequence[bool] = None):
    """Key bit arrays and combined key-validity for per-shard join /
    group / set kernels, from ONE program. ``paired``: the other side's
    aligned key columns (joins, set operations) so both sides emit
    matching lane counts. ``null_lanes``: see `_key_bits_program_fn`."""
    forms, operands = _key_inputs(ctx, cols, paired)
    lanes = tuple(bool(x) for x in null_lanes) if null_lanes is not None \
        else (False,) * len(forms)
    _counter("cylon_key_programs_total", {"stage": "keybits"}).inc()
    computed, kv = _key_bits_program_fn(ctx.mesh, forms, lanes)(operands)
    bits: list = []
    for form, ops, made in zip(forms, operands, computed):
        bits.extend(ops[:_bits_as_given(form)])
        bits.extend(made)
    return tuple(bits), kv


def _dispatch_targets(ctx: CylonContext, cols: Sequence[Column],
                      paired: Sequence[Column], with_emit: bool) -> tuple:
    """ONE dispatch of the targets program: ``(targets,)`` or ``(targets,
    all-ones emit mask)``, on the row sharding."""
    forms, operands = _key_inputs(ctx, cols, paired)
    _counter("cylon_key_programs_total", {"stage": "targets"}).inc()
    return _partition_targets_program_fn(ctx.mesh, forms, with_emit)(
        operands)


def _partition_targets_dist(ctx: CylonContext, cols: Sequence[Column],
                            paired: Sequence[Column] = None
                            ) -> jnp.ndarray:
    """Per-row target shard for mixed plain/varbytes key columns (plain
    columns hash elementwise, varbytes per shard). ``paired``: the other
    side's aligned key columns so both sides hash with matching lane
    counts."""
    return _dispatch_targets(ctx, cols, paired, False)[0]


def _targets_and_emit(ctx: CylonContext, t: Table, cols: Sequence[Column],
                      paired: Sequence[Column] = None):
    """(targets, emit) of an exchange of ``t`` by the key columns
    ``cols``, both on the row sharding: a table without a row mask gets
    its all-ones emit mask out of the targets' own program."""
    if t.row_mask is not None:
        return (_partition_targets_dist(ctx, cols, paired),
                shard.pin(t.row_mask, ctx))
    return _dispatch_targets(ctx, cols, paired, True)


@counted_cache
def _word_targets_fn(mesh):
    """Word-level (targets, emit) from row-level (targets, emit): every
    word inherits its row's shuffle target; words of dead rows and slack
    slots are dropped."""
    from ..data import strings as _strings

    spec = P(mesh.axis_names[0])

    def kernel(words, starts, lengths, targets, emit):
        W = words.shape[0]
        nw = (lengths + 3) >> 2
        row, p = _strings._word_row_map(starts, nw, W)
        wt = jnp.take(targets, row)
        wemit = jnp.take(emit, row) & (p >= 0) & (p < jnp.take(nw, row))
        return wt.astype(jnp.int32), wemit

    return jax.jit(shard_map(kernel, mesh=mesh, in_specs=(spec,) * 5,
                             out_specs=spec))


@counted_cache
def _starts_reconcile_fn(mesh, row_block: int, word_block: int):
    """Rebuild shard-relative varbytes starts after a row+word exchange
    pair, for ANY combination of padded/compact layouts (block=0 means
    compact). Both exchanges keep each source's items contiguous and in
    matching order, so row (source s, j)'s words sit at that source's
    word-segment offset plus the within-source word prefix."""
    axis = mesh.axis_names[0]
    world = mesh.devices.size
    spec = P(axis)

    def kernel(lengths, row_ci, word_ci):
        n = lengths.shape[0]
        nw = (lengths + 3) >> 2
        cs = jnp.cumsum(nw)
        if row_block:
            row_off = jnp.arange(world, dtype=jnp.int32) * row_block
        else:
            row_off = jnp.cumsum(row_ci) - row_ci
        if word_block:
            word_off = jnp.arange(world, dtype=jnp.int32) * word_block
        else:
            word_off = jnp.cumsum(word_ci) - word_ci
        pos = jnp.arange(n, dtype=jnp.int32)
        sid = jnp.zeros(n, jnp.int32)
        for s in range(1, world):
            sid = sid + (pos >= row_off[s]).astype(jnp.int32)
        head = jnp.where(row_off > 0,
                         jnp.take(cs, jnp.maximum(row_off - 1, 0)), 0)
        return jnp.take(word_off, sid) + (cs - nw) - jnp.take(head, sid)

    return jax.jit(shard_map(kernel, mesh=mesh, in_specs=(spec,) * 3,
                             out_specs=spec))


def _exchange_varbytes_words(ctx: CylonContext, vb, targets, emit,
                             new_lengths, row_meta: dict):
    """The word-leg of a varbytes shuffle: words ride their own exchange
    (stability of the bucket sort keeps word order == row order), then
    shard-relative starts reconcile the two layouts."""
    from ..data.strings import VarBytes

    world = ctx.get_world_size()
    wt, wemit = _word_targets_fn(ctx.mesh)(
        shard.pin(vb.words, ctx), shard.pin(vb.starts, ctx),
        shard.pin(vb.lengths, ctx), targets, emit)
    wout, _wemit2, _wcap, wmeta = exchange(
        {"w": shard.pin(vb.words, ctx)}, wt, wemit, ctx)
    new_starts = _starts_reconcile_fn(
        ctx.mesh, row_meta["block"], wmeta["block"])(
        new_lengths, row_meta["counts_in"], wmeta["counts_in"])
    return VarBytes(wout["w"], new_starts, new_lengths, vb.max_words,
                    int(wout["w"].shape[0]),
                    shard_geom=(int(new_lengths.shape[0]) // world,
                                int(wout["w"].shape[0]) // world))


@counted_cache
def _lanes_interleave_fn(mesh, K: int):
    """Per-shard (lengths, lanes…) → (interleaved words, shard-relative
    starts): the strided-layout assembly stays local to each shard (a
    global reshape over the sharded row axis would re-layout)."""
    spec = P(mesh.axis_names[0])

    def kernel(lengths, *lanes):
        n = lengths.shape[0]
        nw = (lengths + 3) >> 2
        masked = [jnp.where(k < nw, l, jnp.uint32(0))
                  for k, l in enumerate(lanes)]
        flat = jnp.stack(masked, axis=1).reshape(-1)
        starts = jnp.arange(n, dtype=jnp.int32) * jnp.int32(K)
        return flat, starts

    return jax.jit(shard_map(kernel, mesh=mesh, in_specs=(spec,) * (1 + K),
                             out_specs=(spec, spec)))


def _from_lanes_sharded(ctx: CylonContext, lanes, lengths):
    """Strided sharded VarBytes from exchanged word lanes: each shard's
    rows occupy [r_local*K, r_local*K + nw) of its own word segment —
    shard-relative starts, shard_geom rows*K word stride."""
    from ..data.strings import VarBytes

    K = max(len(lanes), 1)
    n = int(lengths.shape[0])
    world = ctx.get_world_size()
    rows = n // world
    flat, starts = _lanes_interleave_fn(ctx.mesh, K)(lengths, *lanes)
    return VarBytes(flat, starts, lengths, K, n * K,
                    shard_geom=(rows, rows * K), stride=K)


def _build_exchange_payload(t: Table, ctx: CylonContext,
                            extra: Optional[dict]):
    """Payload leaves for a table shuffle. Short varbytes columns
    (≤ LANE_WORDS_MAX words) ride the ROW exchange as fixed word lanes —
    no second word-level exchange, no extra count sync, no starts
    reconcile. All-valid columns skip the mask leaf entirely (validity
    None round-trips as None — one less sort operand per column, here
    and in the per-shard join's plan sort: distributed_join hands the
    None on as it is)."""
    from ..data.strings import LANE_WORDS_MAX

    with _span("shuffle.payload") as sp:
        payload = dict(extra or {})
        lane_cols = {}
        for i, c in enumerate(t._columns):
            payload[f"d{i}"] = c.data  # byte lengths for varbytes columns
            if c.validity is not None:
                payload[f"v{i}"] = c.valid_mask()
            if c.is_varbytes and c.varbytes.max_words <= LANE_WORDS_MAX:
                vb = c.varbytes
                lanes = _word_lanes_fn(ctx.mesh, vb.max_words)(
                    shard.pin(vb.words, ctx), shard.pin(vb.starts, ctx),
                    shard.pin(vb.lengths, ctx))
                lane_cols[i] = vb.max_words
                for k, l in enumerate(lanes):
                    payload[f"d{i}w{k}"] = l
        payload = {k: shard.pin(v, ctx) for k, v in payload.items()}
        sp.set(lanes=len(payload))
    return payload, lane_cols


def _finish_exchange_table(t: Table, ctx: CylonContext, targets, emit,
                           out, new_emit, meta, lane_cols,
                           extra: Optional[dict]):
    """Columns back out of the exchanged payload's leaves (a long
    varbytes column's words ride their own exchange from here)."""
    with _span("shuffle.unpack"):
        cols = []
        for i, c in enumerate(t._columns):
            d, v = out[f"d{i}"], out.get(f"v{i}")
            if c.is_varbytes:
                # the padded-mode exchange over-reads neighbor rows into
                # dead slots, so dead rows can carry live rows' byte
                # lengths; the lane masking and every later _word_row_map
                # pass need dead rows at nw=0 to keep the monotone-starts
                # invariant (strings.py _word_row_map), so zero them first
                d = jnp.where(new_emit, d, jnp.zeros((), d.dtype))
                if i in lane_cols:
                    vb = _from_lanes_sharded(
                        ctx,
                        [out[f"d{i}w{k}"] for k in range(lane_cols[i])], d)
                else:
                    vb = _exchange_varbytes_words(ctx, c.varbytes, targets,
                                                  emit, d, meta)
                cols.append(Column(vb.lengths, c.dtype, v, None, c.name,
                                   varbytes=vb))
            else:
                cols.append(Column(d, c.dtype, v, c.dictionary, c.name))
        extra_out = {k: out[k] for k in (extra or {})}
    return cols, new_emit, extra_out


def _exchange_table(t: Table, targets, emit, ctx: CylonContext,
                    extra: Optional[dict] = None, counts=None):
    """Shuffle a whole table's columns (fixed-width AND varbytes) plus
    optional extra per-row arrays. Returns (columns, new_emit,
    extra_out)."""
    payload, lane_cols = _build_exchange_payload(t, ctx, extra)
    out, new_emit, _cap, meta = exchange(payload, targets, emit, ctx,
                                         counts=counts)
    return _finish_exchange_table(t, ctx, targets, emit, out, new_emit,
                                  meta, lane_cols, extra)


def _exchange_table_pair(t1: Table, tg1, e1, c1, t2: Table, tg2, e2, c2,
                         ctx: CylonContext):
    """Two-table shuffle in ONE compiled program when both sides route
    padded (exchange_pair) — the distributed join/set-op composition."""
    p1, lc1 = _build_exchange_payload(t1, ctx, None)
    p2, lc2 = _build_exchange_payload(t2, ctx, None)
    r1, r2 = exchange_pair(p1, tg1, e1, c1, p2, tg2, e2, c2, ctx)
    out1, ne1, _cap1, m1 = r1
    out2, ne2, _cap2, m2 = r2
    return (_finish_exchange_table(t1, ctx, tg1, e1, out1, ne1, m1, lc1,
                                   None),
            _finish_exchange_table(t2, ctx, tg2, e2, out2, ne2, m2, lc2,
                                   None))


# -- per-shard varlen gather (count → take at worst-shard capacity) --


@counted_cache
def _varlen_count_fn(mesh, replicated: bool = False):
    """Output-word count for a per-shard varlen gather. ``replicated``:
    the length source is a replicated (vocab) array, idx stays sharded."""
    axis = mesh.axis_names[0]
    spec = P(axis)

    def kernel(lengths, idx):
        safe = jnp.maximum(idx, 0)
        nw = (jnp.take(lengths, safe) + 3) >> 2
        total = jnp.where(idx >= 0, nw, 0).sum().astype(jnp.int32)
        return replicated_gather(total[None], axis, mesh.devices.size)

    src = P() if replicated else spec
    return jax.jit(shard_map(kernel, mesh=mesh, in_specs=(src, spec),
                             out_specs=P()))


@counted_cache
def _varlen_take_fn(mesh, cap_w: int, replicated: bool = False):
    """Per-shard varlen gather (strings._take_program under shard_map).
    ``replicated``: gather FROM a replicated source (dict vocab lift)."""
    from ..data import strings as _strings

    spec = P(mesh.axis_names[0])

    def kernel(words, starts, lengths, idx):
        return _strings._take_program(words, starts, lengths, idx, cap_w)

    src = P() if replicated else spec
    return jax.jit(shard_map(kernel, mesh=mesh,
                             in_specs=(src, src, src, spec),
                             out_specs=spec))


def _varlen_take_sharded(ctx: CylonContext, vb, idx) -> "object":
    """Distributed analog of VarBytes.take: per-shard varlen gather with
    ONE host sync for the worst shard's output word count."""
    from ..data.strings import VarBytes

    words = shard.pin(vb.words, ctx)
    starts = shard.pin(vb.starts, ctx)
    lengths = shard.pin(vb.lengths, ctx)
    idx = shard.pin(idx, ctx)
    counts = np.asarray(_host_fetch(
        "varlen.count", _varlen_count_fn(ctx.mesh)(lengths, idx)))
    cap_w = _bucket_cap(int(counts.max()))
    w, s, ln = _varlen_take_fn(ctx.mesh, cap_w)(words, starts, lengths, idx)
    world = ctx.get_world_size()
    return VarBytes(w, s, ln, vb.max_words, int(w.shape[0]),
                    shard_geom=(int(idx.shape[0]) // world, cap_w))


def _dist_as_varbytes(ctx: CylonContext, col: Column) -> Column:
    """Sharding-aware as_varbytes: dictionary codes stay sharded; the
    (small) vocab VarBytes is replicated and each shard gathers its own
    self-contained layout."""
    from ..data.strings import VarBytes

    if col.is_varbytes:
        return col
    vocab_vb = VarBytes.from_host(col.dictionary)
    max_words = vocab_vb.max_words
    codes = shard.pin(col.data, ctx)
    counts = np.asarray(_host_fetch(
        "varlen.count", _varlen_count_fn(ctx.mesh, replicated=True)(
            jax.device_put(vocab_vb.lengths), codes)))
    cap_w = _bucket_cap(int(counts.max()))
    w, s, ln = _varlen_take_fn(ctx.mesh, cap_w, replicated=True)(
        vocab_vb.words, vocab_vb.starts, vocab_vb.lengths, codes)
    world = ctx.get_world_size()
    vb = VarBytes(w, s, ln, max_words, int(w.shape[0]),
                  shard_geom=(int(codes.shape[0]) // world, cap_w))
    return Column(vb.lengths, col.dtype, col.validity, None, col.name,
                  varbytes=vb)


def _align_key_columns_dist(ctx: CylonContext, left_d: Table,
                            right_d: Table, lidx, ridx):
    """Distribution-aware align_key_columns: mixed string storages lift
    through the replicated-vocab kernel (the eager lift in
    data/column.align_string_columns would collapse per-shard layouts)."""
    lcols, rcols = [], []
    for li, ri in zip(lidx, ridx):
        a, b = left_d._columns[li], right_d._columns[ri]
        if a.is_string != b.is_string:
            raise CylonPlanError(
                f"join key type mismatch: {a.name} vs {b.name}",
                code=Code.TypeError)
        if a.is_string:
            if a.is_varbytes or b.is_varbytes:
                a = _dist_as_varbytes(ctx, a)
                b = _dist_as_varbytes(ctx, b)
            else:
                a, b = unify_dictionaries(a, b)
        elif a.data.dtype != b.data.dtype:
            common = jnp.promote_types(a.data.dtype, b.data.dtype)
            a = Column(a.data.astype(common), a.dtype, a.validity, None,
                       a.name)
            b = Column(b.data.astype(common), b.dtype, b.validity, None,
                       b.name)
        lcols.append(a)
        rcols.append(b)
    return lcols, rcols


def _payload_tuples(p: dict, ncols: int) -> Tuple[Tuple, Tuple]:
    return (tuple(p[f"d{i}"] for i in range(ncols)),
            tuple(p[f"v{i}"] for i in range(ncols)))


def _rebuild_columns(dat: Sequence, val: Sequence, src,
                     names: Sequence[str]) -> List[Column]:
    src_cols = src._columns if isinstance(src, Table) else src
    cols = []
    for d, v, c, name in zip(dat, val, src_cols, names):
        cols.append(Column(d, c.dtype, v, c.dictionary, name))
    return cols


def _all_valid(cols: Sequence[Column]) -> jnp.ndarray:
    v = cols[0].valid_mask()
    for c in cols[1:]:
        v = v & c.valid_mask()
    return v


# ---------------------------------------------------------------------------
# per-shard kernels (cached per mesh/static-shape signature)
# ---------------------------------------------------------------------------

@counted_cache
def _join_plan_fn(mesh, join_type: _join.JoinType):
    """Per-shard join plan: ONE fused sort per shard (join_plan_keys);
    match arrays stay sharded on device for the materialize phase, the
    [world, 2] count matrix is all_gather-REPLICATED so every controller
    process can fetch it (multi-host safe)."""
    axis = mesh.axis_names[0]
    spec = P(axis)

    def kernel(lbits, lkv, lemit, rbits, rkv, remit):
        counts2, lo, m, bperm, un_mask = _join.join_plan_keys(
            lbits, lkv, lemit, rbits, rkv, remit, join_type)
        world = mesh.devices.size
        return (replicated_gather(counts2, axis, world),
                lo, m, bperm, un_mask)

    return jax.jit(shard_map(kernel, mesh=mesh, in_specs=(spec,) * 6,
                             out_specs=(P(), spec, spec, spec, spec)))


_gather_side = _join.gather_columns


@counted_cache
def _join_plan_stream_fn(mesh, join_type: _join.JoinType, nk: int,
                         a_desc, b_desc, block_rows: int, hash_mode: bool):
    """Per-shard Pallas streaming join plan under shard_map — the same
    kernel chain the local join uses (ops/join.plan_program_stream),
    which the XLA per-shard plan was measured ~5x slower than at bench
    scale. TPU-only (the interpreter inside jit is prohibitive)."""
    axis = mesh.axis_names[0]
    world = mesh.devices.size
    spec = P(axis)

    def kernel(lkb, lkv, lemit, rkb, rkv, remit, ldat, lval, rdat, rval):
        counts, a_streams, b_streams = _join._plan_program_stream_impl(
            lkb, tuple([lkv] + [None] * (nk - 1)), lemit,
            rkb, tuple([rkv] + [None] * (nk - 1)), remit,
            ldat, lval, rdat, rval, (False,) * nk, join_type,
            a_desc=a_desc, b_desc=b_desc, block_rows=block_rows,
            hash_mode=hash_mode, interpret=False)
        return (replicated_gather(counts, axis, world), counts,
                a_streams, b_streams)

    # check_vma off: pallas_call outputs carry no varying-mesh-axes
    # annotation for the checker
    return jax.jit(shard_map(kernel, mesh=mesh, in_specs=(spec,) * 10,
                             out_specs=(P(), spec, spec, spec),
                             check_vma=False))


@counted_cache
def _semi_plan_fn(mesh, join_type: _join.JoinType):
    """Per-shard SEMI / ANTI join on the XLA plan: the new row mask of
    the (exchanged) left side in its own row order, nothing else: no
    counts gather, no materialise program."""
    spec = P(mesh.axis_names[0])

    def kernel(lbits, lkv, lemit, rbits, rkv, remit):
        return _join.semi_keep_mask(lbits, lkv, lemit, rbits, rkv, remit,
                                    join_type)

    return jax.jit(shard_map(kernel, mesh=mesh, in_specs=(spec,) * 6,
                             out_specs=spec))


@counted_cache
def _semi_plan_stream_fn(mesh, join_type: _join.JoinType, a_desc,
                         block_rows: int):
    """Per-shard SEMI / ANTI join on the sort-stream path, the plan pass
    in its semi form under shard_map (`ops/join._plan_program_stream_impl`):
    each shard's kept left rows compacted in key order at the shard's
    capacity, under a prefix row mask. ONE program; no count leaves it."""
    spec = P(mesh.axis_names[0])

    def kernel(lkb, lkv, lemit, rkb, rkv, remit, ldat, lval):
        _counts, lod, lov, emit, lidx = _join._plan_program_stream_impl(
            lkb, (lkv,), lemit, rkb, (rkv,), remit, ldat, lval, (), (),
            (False,), join_type, a_desc=a_desc, b_desc=(),
            block_rows=block_rows, hash_mode=False, interpret=False)
        return lod, lov, emit, lidx

    return jax.jit(shard_map(kernel, mesh=mesh, in_specs=(spec,) * 8,
                             out_specs=spec, check_vma=False))


@counted_cache
def _join_mat_stream_fn(mesh, join_type: _join.JoinType, cap_e: int,
                        a_desc, b_desc, block_rows: int):
    spec = P(mesh.axis_names[0])

    def kernel(counts, a_streams, b_streams, ldat, lval, rdat, rval):
        return _join._materialize_program_stream_impl(
            counts, a_streams, b_streams, ldat, lval, rdat, rval,
            join_type, cap_e, a_desc=a_desc, b_desc=b_desc,
            block_rows=block_rows, interpret=False)

    return jax.jit(shard_map(kernel, mesh=mesh, in_specs=(spec,) * 7,
                             out_specs=spec, check_vma=False))


def _dist_stream_mode(lkb, rkb, join_type: _join.JoinType, world: int):
    """None (XLA plan), or (hash_mode, block_rows) when the per-shard
    Pallas stream join applies (same applicability shape as the local
    join's router, on the post-exchange key-bit arrays)."""
    if jax.default_backend() != "tpu" or _join.STREAM_PLAN is False:
        return None
    if join_type == _join.JoinType.FULL_OUTER:
        return None
    na = int(lkb[0].shape[0]) // world
    nb = int(rkb[0].shape[0]) // world
    if na == 0 or nb == 0 or na + nb >= (1 << 29):
        return None
    if len(lkb) == 1 and lkb[0].dtype.itemsize == 4 \
            and lkb[0].dtype != jnp.bool_:
        return (False, _join.stream_block_rows(na, nb))
    lanes = sum(2 if b.dtype.itemsize == 8 else 1 for b in lkb)
    if lanes <= _join.MAX_HASH_KEY_LANES:
        return (True, _join.stream_block_rows(na, nb))
    return None


@counted_cache
def _join_mat_fn(mesh, join_type: _join.JoinType, cap_p: int, cap_u: int):
    spec = P(mesh.axis_names[0])

    def kernel(lo, m, bperm, un_mask, aemit, ldat, lval, rdat, rval):
        lidx, ridx, emit = _join.join_materialize_gids(
            lo, m, bperm, un_mask, aemit, join_type, cap_p, cap_u)
        lod, lov = _gather_side(ldat, lval, lidx)
        rod, rov = _gather_side(rdat, rval, ridx)
        return lod, lov, rod, rov, emit, lidx, ridx

    return jax.jit(shard_map(kernel, mesh=mesh, in_specs=(spec,) * 9,
                             out_specs=spec))


@counted_cache
def _setop_count_fn(mesh):
    spec = P(mesh.axis_names[0])

    def kernel(lbits, lemit, rbits, remit):
        gl, gr = _order.dense_ranks_two(list(lbits), list(rbits))
        c = _setops.setop_counts(gl, gr, lemit, remit)
        counts = jnp.stack([c["n_union"], c["n_subtract"],
                            c["n_intersect"]]).astype(jnp.int32)
        return replicated_gather(counts, mesh.axis_names[0],
                                 mesh.devices.size)

    return jax.jit(shard_map(kernel, mesh=mesh, in_specs=(spec,) * 4,
                             out_specs=P()))


@counted_cache
def _setop_mat_fn(mesh, op: _setops.SetOp, cap: int):
    spec = P(mesh.axis_names[0])

    def kernel(lbits, lemit, rbits, remit, ldat, lval, rdat, rval):
        gl, gr = _order.dense_ranks_two(list(lbits), list(rbits))
        idx = _setops.setop_indices(gl, gr, lemit, remit, op, cap)
        emit = idx >= 0
        # indices address the concatenated [left; right] per-shard table
        dat = tuple(jnp.concatenate([a, b]) for a, b in zip(ldat, rdat))
        val = tuple(jnp.concatenate([a, b]) for a, b in zip(lval, rval))
        od, ov = _gather_side(dat, val, idx)
        return od, ov, emit, idx

    return jax.jit(shard_map(kernel, mesh=mesh, in_specs=(spec,) * 8,
                             out_specs=spec))


@counted_cache
def _varlen_take_concat_count_fn(mesh):
    """Word count for a gather over the per-shard concat [left; right]
    varbytes pair."""
    axis = mesh.axis_names[0]
    spec = P(axis)

    def kernel(ll, lr, idx):
        lens = jnp.concatenate([ll, lr])
        safe = jnp.maximum(idx, 0)
        nw = (jnp.take(lens, safe) + 3) >> 2
        total = jnp.where(idx >= 0, nw, 0).sum().astype(jnp.int32)
        return replicated_gather(total[None], axis, mesh.devices.size)

    return jax.jit(shard_map(kernel, mesh=mesh, in_specs=(spec,) * 3,
                             out_specs=P()))


@counted_cache
def _varlen_take_concat_fn(mesh, cap_w: int):
    """Varlen gather over the per-shard concat of two varbytes columns.
    The source concat needs NO repacking: right starts shift by the
    (static) left word-buffer length — the hash/take range sums are
    gap-immune (data/strings.py)."""
    from ..data import strings as _strings

    spec = P(mesh.axis_names[0])

    def kernel(lw, ls, ll, rw, rs, rl, idx):
        words = jnp.concatenate([lw, rw])
        starts = jnp.concatenate([ls, rs + lw.shape[0]])
        lens = jnp.concatenate([ll, rl])
        return _strings._take_program(words, starts, lens, idx, cap_w)

    return jax.jit(shard_map(kernel, mesh=mesh, in_specs=(spec,) * 7,
                             out_specs=spec))


@counted_cache
def _groupby_fn(mesh, ops: Tuple[_groupby.AggregationOp, ...],
                col_ids: Tuple[int, ...], all_valid: Tuple[bool, ...],
                key_spec: tuple = None, plan: tuple = None,
                elided: Tuple[bool, ...] = None):
    """The per-shard sort + reduce step. Returns (key data, key
    validities, the groups' row mask ``gvalid``, one (array, validity) an
    aggregate, ``safe``). An aggregate's validity is its own ANDed with
    ``gvalid``, or None where ``elided`` (static; `_partial_masks_elided`
    decides, for the step that makes the partial table, of which
    ``gvalid`` is the row mask) says it would only repeat ``gvalid``: the
    AND over the slots is then not computed. ``key_spec`` (static;
    `_group_key_spec` decides) None: the sort carries the row index and
    each group's key is gathered from its first original row (``kdat``,
    ``kval``; ``safe`` goes out for a varbytes key's words). Else
    ``kbits`` are the key lanes as `ops/groupby._key_columns` reads them
    (`_group_key_operands`), no index rides, the sort is not stable, and
    the key columns come out of the reduce pass: no gather over the rows,
    ``kdat`` and ``kval`` empty, a key without nulls comes back with
    validity None and ``safe`` is None. ``emit`` None (a table with no
    row mask): every row is live, no dead flag rides. ``plan`` (static;
    `_sort_pack_probe` and `data/table._sort_pack_plan` decide on the
    host, from the whole table's observed ranges) packs integer columns
    into shared words by ``params``, a replicated operand:
    `ops/groupby.presort_groups`."""
    spec = P(mesh.axis_names[0])

    def kernel(kbits, kdat, kval, emit, vdat, vval, params):
        n = kbits[0].shape[0]
        keys = tuple(kbits) + tuple(v.astype(jnp.uint8) for v in kval)
        vdat_s, vval_s, emit_s, first_s, new_grp, _ng = \
            _groupby.presort_groups(keys, emit, vdat, vval,
                                    index=key_spec is None, plan=plan,
                                    params=params)
        firsts, gvalid, results = _groupby.sorted_segment_aggregate(
            new_grp, emit_s, first_s, vdat_s, vval_s, n, ops, col_ids,
            all_valid, key_spec=key_spec)
        agg = tuple((arr, None if e else av & gvalid) for (arr, av), e
                    in zip(results, elided or (False,) * len(ops)))
        if key_spec is not None:
            kout, kvout = zip(*firsts)
            return kout, kvout, gvalid, agg, None
        safe = jnp.minimum(firsts, n - 1)
        kout = tuple(jnp.take(d, safe, axis=0) for d in kdat)
        kvout = tuple(jnp.take(v, safe) & gvalid for v in kval)
        return kout, kvout, gvalid, agg, safe

    # check_vma off: on a TPU the reduce step is a pallas_call
    # (groupby_run_reduce), whose outputs carry no varying-mesh-axes
    # annotation for the checker; the kernel is purely per-shard
    return jax.jit(shard_map(kernel, mesh=mesh,
                             in_specs=(spec,) * 6 + (P(),),
                             out_specs=spec, check_vma=False))


def _group_key_spec(key_columns, value_dtypes, ops, rows: int):
    """`_groupby_fn`'s ``key_spec``: HOW the per-shard step reads its
    groups' keys, decided from what the host sees before it dispatches
    anything (no knob; `ops/groupby.sort_carries_index`, the function
    `table.groupby_local` decides by; ``rows``: a shard's).

    None, the sort must carry the row index: a varbytes key (hash lanes
    have no way back), a lane wider than 32 bits, a reduce step that is
    not the streaming pass (the CPU, 8-byte accumulators). Else
    `sorted_segment_aggregate`'s ``key_spec``, one (numpy dtype,
    is_string, nullable) a key column."""
    key_spec = None if any(c.is_varbytes for c in key_columns) else tuple(
        (np.dtype(c.data.dtype), c.is_string, c.validity is not None)
        for c in key_columns)
    # a plain column's ordered-bits lane is as wide as its data
    if _groupby.sort_carries_index([c.data for c in key_columns], key_spec,
                                   value_dtypes, ops, rows):
        return None
    return key_spec


def _partial_masks_elided(ops, all_valid) -> Tuple[bool, ...]:
    """Per aggregate of the PARTIAL step (first-phase ``ops``: MEAN is
    already a SUM and a COUNT; ``all_valid``: the source column has
    validity None), whether its validity only repeats the partial table's
    row mask and so goes as None: no leaf in the exchange, no mask
    operand and no any-valid pass in the merge. Static and observed on
    the host before anything is dispatched (no knob): a COUNT is valid
    wherever its group is, whatever the column; a SUM, MIN or MAX over a
    column without nulls too (`ops/groupby.sorted_segment_aggregate`
    hands such an aggregate ``group_valid`` itself). Over a nullable
    column they keep their mask: a group whose values are all null on a
    shard is a live partial row with a null aggregate."""
    return tuple(op == _groupby.AggregationOp.COUNT or av
                 for op, av in zip(ops, all_valid))


def _group_key_operands(ctx: CylonContext, key_columns, key_spec):
    """(kbits, kdat, kval) of `_groupby_fn`, the key operands the way
    ``key_spec`` (`_group_key_spec`) reads the keys takes. None: the key
    bits, the data and every column's mask, to gather from. Else
    ``kbits`` holds a column's ordered-bits lane and then its validity
    lane only if the column is nullable, out of the ONE key-bits program:
    nothing to gather from, no all-ones mask of a key without nulls."""
    if key_spec is not None:
        kbits, _kv = _dist_key_bits(
            ctx, key_columns,
            null_lanes=[nullable for _d, _s, nullable in key_spec])
        return kbits, (), ()
    kbits, _kv = _dist_key_bits(ctx, key_columns)
    return (kbits,
            tuple(shard.pin(c.data, ctx) for c in key_columns),
            tuple(shard.pin(c.valid_mask(), ctx) for c in key_columns))


def _sort_pack_probe(rows: int, key_columns, key_spec, emit, vdat):
    """Dispatch the probe of the per-shard sort's packing over the WHOLE
    sharded table (`data/table._sort_pack_probe`, the function the
    one-chip groupby decides by: the same static test, the same row gate
    on a SHARD's ``rows``; None where no probe is paid). Across chips
    nothing has looked at the key before the sort, so the probe does,
    where the key's lane is the sort's only key (``key_spec`` names ONE
    column without nulls: no validity lane, no gather path's mask lane)
    and may share its word (`ops/groupby.packs`)."""
    key = None
    if key_spec is not None and len(key_spec) == 1 \
            and not key_spec[0][2] and _groupby.packs(key_spec[0][0]):
        key = (key_columns[0], None)
    return table_mod._sort_pack_probe(rows, key, vdat, emit)


def _group_keys(ctx: CylonContext, key_columns, emit, vdat, ops,
                observe: bool):
    """(key_spec, kbits, kdat, kval, packing) of one per-shard step:
    `_group_key_spec`'s decision, the key operands it takes
    (`_group_key_operands`) and `_aggregate_shards`' ``packing``.
    ``observe``: nobody has looked at the columns yet and their ranges
    may pack the sort (`_sort_pack_probe`); what does not depend on the
    ranges, the key-bits program, is dispatched BEFORE the host waits for
    them, so the chips work while the ranges come down."""
    rows = int(key_columns[0].data.shape[0]) // ctx.get_world_size()
    key_spec = _group_key_spec(key_columns, [v.dtype for v in vdat], ops,
                               rows)
    probe = _sort_pack_probe(rows, key_columns, key_spec, emit, vdat) \
        if observe else None
    operands = _group_key_operands(ctx, key_columns, key_spec)
    return (key_spec,) + operands + (table_mod._sort_pack_plan(probe),)


def _aggregate_shards(ctx: CylonContext, phase: str, ops, col_ids,
                      all_valid, key_spec, kbits, kdat, kval, emit, vdat,
                      vval, packing=(None, None), elided=None):
    """One per-shard sort + reduce step (``_groupby_fn``), its sort's
    operands, the columns that ride inside another operand's word, its
    reduce path and the way its groups' keys are read counted here, where
    the host can see them: the same pure functions of masks, lanes,
    packing and accumulator widths that presort_groups and
    sorted_segment_aggregate evaluate inside the program. ``emit`` None
    (a table with no row mask, before any exchange): no dead flag rides;
    after an exchange there is always a row mask. The row index rides, on
    a stable sort, only without a ``key_spec`` (`_group_key_spec`).
    ``packing``: `data/table._sort_pack_plan`'s (plan, params), decided
    on the host from the observed ranges (`_sort_pack_probe`). ``phase``
    names the step in ``cylon_groupby_phase_total``: "partial" (a shard's
    own rows, before the exchange), "merge" (the partials, after it) or
    "single" (the rows themselves after the exchange, or in place: no
    pre-aggregation)."""
    plan, params = packing
    _counter("cylon_groupby_phase_total", {"phase": phase}).inc()
    _counter("cylon_groupby_key_readback_total", {
        "path": "gather" if key_spec is None else "lanes"}).inc()
    _counter("cylon_groupby_sort_operands_total").inc(
        _groupby.sort_operand_count(kbits + kval, emit, vdat, vval,
                                    key_spec is None, plan))
    _counter("cylon_groupby_sort_packed_columns_total").inc(
        _groupby.packed_members(plan))
    _counter("cylon_groupby_reduce_path_total", {
        "path": _groupby.reduce_path(
            [v.dtype for v in vdat], ops,
            int(kbits[0].shape[0]) // ctx.get_world_size())}).inc()
    return _groupby_fn(ctx.mesh, ops, col_ids, all_valid, key_spec, plan,
                       elided)(
        kbits, kdat, kval, emit, vdat, vval, params)


# ---------------------------------------------------------------------------
# shuffle / partition public API
# ---------------------------------------------------------------------------

def shuffle(table: Table, hash_columns: Sequence,
            salted: bool = False) -> Table:
    """Repartition rows by key hash (reference: cylon::Shuffle,
    table.cpp:162-236). Tables already hash-placed on the same keys
    (a previous shuffle, or shard.distribute_by_key host ingest) pass
    through without an exchange.

    ``salted``: the hot-key load-balancing variant (adaptive
    execution): the salted-targets program decides on device which
    destinations are hot (receive total past CYLON_SKEW_WARN_FACTOR x
    the mean, from the true global count matrix) and spreads exactly
    those destinations' rows across CYLON_SALT_FACTOR consecutive
    shards — bounding the max shard under Zipfian keys. The salt is
    routing-only (nothing to strip on the receive side), but the
    output carries NO placement witness: salted placement is
    positional, and every downstream consumer must re-establish
    placement itself. Skew observability records the RAW
    (pre-mitigation) count matrix, so the planner's salting decision
    reads true key skew, never its own mitigation."""
    from .shuffle import salted_exchange_targets
    from ..telemetry import knobs as _knobs
    from ..telemetry import skew as _skew

    ctx = table._ctx
    world = ctx.get_world_size()
    if world == 1:
        return table
    t = shard.distribute(table, ctx)
    idxs = [t._col_index(c) for c in hash_columns]
    sig = shard.partition_signature([t._columns[i] for i in idxs], idxs,
                                    world)
    # pow2_floor: the salt factor keys the compiled salted-targets
    # program (1 per octave, specialization-clean); the effective
    # spread is therefore the pow2 floor of CYLON_SALT_FACTOR
    salt = _pow2_floor(max(int(_knobs.get("CYLON_SALT_FACTOR")), 1)) \
        if salted else 0
    salted = salted and salt >= 2
    if sig is not None and t._hash_partitioned == sig and not salted:
        return t
    targets, emit = _targets_and_emit(
        ctx, t, [t._columns[i] for i in idxs])
    if salted:
        warn = float(_knobs.get("CYLON_SKEW_WARN_FACTOR"))
        targets, counts, raw = salted_exchange_targets(
            targets, emit, ctx, salt, warn)
        targets = shard.pin(targets, ctx)
        _counter("cylon_salted_exchanges_total").inc()
        raw_stats = _skew.SkewStats.from_counts(raw)
        _annotate(salted=True, salt_factor=salt,
                  skew_raw=round(raw_stats.imbalance, 3)
                  if raw_stats is not None else None)
        cols, new_emit, _x = _exchange_table(t, targets, emit, ctx,
                                             counts=counts)
        result = Table(cols, ctx, new_emit)
        # NO witness: hot keys are spread positionally across shards
        table._free_if_unretained()
        return _ledger.track(result, "shuffle")
    cols, new_emit, _x = _exchange_table(t, targets, emit, ctx)
    result = Table(cols, ctx, new_emit)
    result._hash_partitioned = sig
    # reference parity: Shuffle frees non-retained inputs (table.cpp:207)
    table._free_if_unretained()
    return _ledger.track(result, "shuffle")


def hash_partition(table: Table, hash_columns: Sequence,
                   num_partitions: int) -> dict:
    """Split into a {partition_id: Table} map (reference: HashPartition,
    table.hpp:354, table.cpp:102-160). DEVICE-RESIDENT: one fused
    stable sort by target bucket carries every column as an operand
    (the same trick the exchange's bucket sort uses), then each
    partition is a contiguous device slice — rows never leave HBM
    (round-3 verdict: the old host-numpy round trip was wrong for a
    device table mid-pipeline). Long varbytes columns (> LANE_WORDS_MAX
    words) fall back to the native host partitioner."""
    from ..data.column import Column, refuse_planes
    from ..data.strings import LANE_WORDS_MAX, VarBytes

    refuse_planes(table._columns, "hash_partition")
    idxs = [table._col_index(c) for c in hash_columns]
    if any(c.is_varbytes and c.varbytes.max_words > LANE_WORDS_MAX
           for c in table._columns):
        return _hash_partition_host(table, idxs, num_partitions)

    t = table
    ctx = t._ctx
    emit = t.emit_mask()
    targets = _hash.partition_targets(
        [t._columns[i] for i in idxs], num_partitions)
    # varbytes key columns need content hashes, not length hashes —
    # partition_targets handles them via hash_column internally; short
    # varbytes PAYLOADS ride the sort as word lanes below
    tkey = jnp.where(emit, targets, jnp.int32(num_partitions))
    leaves = []
    desc = []  # (col_idx, kind) per leaf, kind in d/v/w
    for ci, c in enumerate(t._columns):
        leaves.append(c.data)
        desc.append((ci, "d"))
        if c.validity is not None:
            leaves.append(c.valid_mask())
            desc.append((ci, "v"))
        if c.is_varbytes:
            for l in c.varbytes.word_lanes():
                leaves.append(l)
                desc.append((ci, "w"))
    res = jax.lax.sort((tkey,) + tuple(leaves), num_keys=1,
                       is_stable=True)
    sorted_leaves = list(res[1:])
    counts = np.asarray(_host_fetch(
        "hash_partition.counts", jax.ops.segment_sum(
            jnp.ones(tkey.shape[0], jnp.int32), tkey,
            num_segments=num_partitions + 1)))[:num_partitions]
    offs = np.concatenate([[0], np.cumsum(counts)]).astype(np.int64)

    out = {}
    for p in range(num_partitions):
        lo, hi = int(offs[p]), int(offs[p + 1])
        cols = []
        by_col = {}
        for (ci, kind), leaf in zip(desc, sorted_leaves):
            by_col.setdefault(ci, {}).setdefault(kind, []).append(
                leaf[lo:hi])
        for ci, c in enumerate(t._columns):
            parts = by_col[ci]
            d = parts["d"][0]
            v = parts.get("v", [None])[0]
            if c.is_varbytes:
                vb = VarBytes.from_lanes(parts["w"], d)
                cols.append(Column(vb.lengths, c.dtype, v, None, c.name,
                                   varbytes=vb))
            else:
                cols.append(Column(d, c.dtype, v, c.dictionary, c.name))
        out[p] = Table(cols, ctx)
    return out


def _hash_partition_host(table: Table, idxs, num_partitions: int) -> dict:
    """Host partitioner (native ct_row_hash) — the long-varbytes path."""
    from ..data.column import Column
    from ..data.strings import VarBytes

    t = table.compact()
    host, valids, counts, order, offs = shard.host_partition_arrays(
        t, idxs, num_partitions)
    out = {}
    for p in range(num_partitions):
        seg = order[offs[p]:offs[p + 1]]
        cols = []
        for ci, c in enumerate(t._columns):
            v = None if valids[ci] is None else jnp.asarray(valids[ci][seg])
            if c.is_varbytes:
                vb = VarBytes.from_host(host[ci][seg])
                cols.append(Column(vb.lengths, c.dtype, v, None, c.name,
                                   varbytes=vb))
            else:
                cols.append(Column(jnp.asarray(host[ci][seg]), c.dtype, v,
                                   c.dictionary, c.name))
        out[p] = Table(cols, t._ctx)
    return out


def repartition(table: Table, ctx: CylonContext) -> Table:
    """Round-robin balance rows across shards (no key)."""
    t = shard.distribute(table, ctx)
    world = ctx.get_world_size()
    if world == 1:
        return t
    n = t.capacity
    targets = shard.pin(
        jnp.arange(n, dtype=jnp.int32) % world, ctx)
    cols, new_emit, _x = _exchange_table(
        t, targets, shard.pin(t.emit_mask(), ctx), ctx)
    return _ledger.track(Table(cols, ctx, new_emit), "repartition")


# ---------------------------------------------------------------------------
# distributed join (reference: DistributedJoin, table.cpp:656-696)
# ---------------------------------------------------------------------------

def distributed_join(left: Table, right: Table,
                     config: _join.JoinConfig) -> Table:
    ctx = left._ctx
    world = ctx.get_world_size()
    if world == 1:
        # reference parity: world==1 short-circuits to the local join
        # (table.cpp:662-669)
        _counter("cylon_join_algorithm_total", {"algo": "local"}).inc()
        return _ledger.track(table_mod.join(left, right, config),
                             "distributed_join")
    # the runtime-honest algorithm census (adaptive execution: which
    # joins actually went broadcast — see broadcast_hash_join)
    _counter("cylon_join_algorithm_total", {"algo": "shuffle"}).inc()
    seq = ctx.get_next_sequence()
    lidx, ridx = config.left_column_idx, config.right_column_idx
    with _span("distributed_join.distribute", seq, world=world) as _sp:
        exact_pairs = []
        if getattr(config, "exact", False):
            from ..data.strings import EXACT_KEY_WORDS

            for li, rj in zip(lidx, ridx):
                a, b = left._columns[li], right._columns[rj]
                kw = _pair_k(a, b)
                if kw is not None and kw > EXACT_KEY_WORDS:
                    # long keys join on the 96-bit content hash; exact=True
                    # byte-verifies AFTER the exchange (both key columns
                    # are row-aligned in the output) — INNER filters false
                    # matches, outer joins redo on dictionary codes
                    exact_pairs.append((li, rj))
        if exact_pairs and _join.is_semi(config.type):
            # no matched pair comes out of a semi join to byte-verify
            raise CylonError(
                Code.NotImplemented,
                f"distributed {config.type.name.lower()} join with "
                f"exact=True on a varbytes key longer than "
                f"{EXACT_KEY_WORDS} words: join locally, or on a "
                f"dictionary-encoded key")

        left_d = shard.distribute(left, ctx)
        right_d = shard.distribute(right, ctx)
        _sp.set(already_distributed=int(left_d is left)
                + int(right_d is right))
        lcols, rcols = _align_key_columns_dist(ctx, left_d, right_d, lidx,
                                               ridx)

    shuffled = []
    with _span("distributed_join.shuffle", seq, world=world,
               rows_in=left_d.capacity + right_d.capacity) as _sp:
        plan = []
        for side, t, kcols, kidx, other in (
                ("left", left_d, lcols, lidx, rcols),
                ("right", right_d, rcols, ridx, lcols)):
            sig = shard.partition_signature(kcols, kidx, world)
            if sig is not None and t._hash_partitioned == sig:
                # co-partitioned (prior shuffle or distribute_by_key host
                # ingest): rows are already hash-placed — skip the exchange
                plan.append(("skip", t, None, None))
                continue
            # targets need only the partition hashes; key BITS are
            # recomputed from the shuffled columns below (elementwise /
            # per-shard work), so the exchange moves ~2/3 fewer lanes —
            # measured 813 ms -> the bare-columns exchange cost at 16M
            with _span("distributed_join.targets", seq, side=side,
                       key_columns=len(kcols)):
                targets, emit = _targets_and_emit(ctx, t, kcols, other)
            plan.append(("exchange", t, targets, emit))
        # both sides exchanging: ONE fused count program + ONE host sync
        # covers both shuffles (the reference pays a header phase per
        # table per peer, mpi_channel.cpp:211-225; here every host
        # round trip stalls dispatch, so fusing halves the fixed cost
        # of the composition)
        ex = [p for p in plan if p[0] == "exchange"]
        _sp.set(sides_exchanged=len(ex), sides_skipped=2 - len(ex))
        results = {}
        if len(ex) == 2:
            cl, cr = count_pair(ex[0][2], ex[0][3], ex[1][2],
                                ex[1][3], ctx)
            r1, r2 = _exchange_table_pair(
                ex[0][1], ex[0][2], ex[0][3], cl,
                ex[1][1], ex[1][2], ex[1][3], cr, ctx)
            results[id(ex[0])] = r1
            results[id(ex[1])] = r2
        for p in plan:
            kind, t, targets, emit = p
            if kind == "skip":
                shuffled.append((t._columns, t.row_mask,
                                 shard.pin(t.emit_mask(), ctx)))
                continue
            if id(p) in results:
                cols, emit_s, _x = results[id(p)]
            else:
                cols, emit_s, _x = _exchange_table(t, targets, emit, ctx)
            shuffled.append((cols, emit_s, emit_s))

    jt = config.type
    with _span("distributed_join.keybits", seq) as _sp:
        # rebuild key bits from the SHUFFLED columns (word lanes reshape
        # out of the strided layout; plain columns are elementwise
        # ordered-bits)
        (lcols_all, lmask, lemit), (rcols_all, rmask, remit) = shuffled
        left_s = Table(list(lcols_all), ctx, lmask)
        right_s = Table(list(rcols_all), ctx, rmask)
        lcols2, rcols2 = _align_key_columns_dist(ctx, left_s, right_s,
                                                 lidx, ridx)
        lkb, lkv = _dist_key_bits(ctx, lcols2, rcols2)
        rkb, rkv = _dist_key_bits(ctx, rcols2, lcols2)
        lcols_s, rcols_s = lcols_all, rcols_all
        lvb = [i for i, c in enumerate(lcols_s) if c.is_varbytes]
        rvb = [i for i, c in enumerate(rcols_s) if c.is_varbytes]
        # a validity that is None stays None, as _build_exchange_payload
        # round-trips it: an all-ones mask would ride the plan sort as a
        # "v" lane that says nothing, and the XLA materialize's gather
        # reads None as all-valid too
        ldat = tuple(shard.pin(c.data, ctx) for c in lcols_s)
        lval = tuple(None if c.validity is None
                     else shard.pin(c.validity, ctx) for c in lcols_s)
        rdat = tuple(shard.pin(c.data, ctx) for c in rcols_s)
        rval = tuple(None if c.validity is None
                     else shard.pin(c.validity, ctx) for c in rcols_s)

        mode = _dist_stream_mode(lkb, rkb, jt, world)
        semi = _join.is_semi(jt)
        sort_rows = lkb[0].shape[-1] + rkb[0].shape[-1]
        if semi and mode is not None and mode[0]:
            # a semi join's hash path would need its collision count on
            # the host: several key columns take the exact XLA plan
            mode = None
        if semi and mode is not None:
            lkey = table_mod.sole_key_index(lcols2, lcols_s, lidx)
            a_desc, b_desc = _join.plan_lane_descs(ldat, lval, (), (), jt,
                                                   lkey, None)
            br = mode[1]
            table_mod.count_plan_sort(lkb, (False,), len(ldat), a_desc,
                                      b_desc, rows=sort_rows)
        elif mode is not None:
            hash_mode, br = mode
            # the sort path's key bits are the shuffled key column's own
            # ordered bits: it rides once
            lkey, rkey = (None, None) if hash_mode else (
                table_mod.sole_key_index(lcols2, lcols_s, lidx),
                table_mod.sole_key_index(rcols2, rcols_s, ridx))
            a_desc, b_desc = _join.plan_lane_descs(ldat, lval, rdat, rval,
                                                   jt, lkey, rkey)
            table_mod.count_plan_sort(lkb, (False,) * len(lkb),
                                      len(ldat) + len(rdat), a_desc,
                                      b_desc, hash_mode, br,
                                      rows=lkb[0].shape[-1]
                                      + rkb[0].shape[-1])
        _sp.set(key_lanes=len(lkb),
                hash_mode=bool(mode is not None and mode[0]))

    if semi:
        _counter("cylon_join_semi_total", {"kind": jt.name.lower()}).inc()
        with _span("distributed_join.plan", seq):
            if mode is not None:
                lod, lov, emit, lidx_o = _semi_plan_stream_fn(
                    ctx.mesh, jt, a_desc, br)(
                    lkb, lkv, lemit, rkb, rkv, remit, ldat, lval)
            else:
                table_mod.count_plan_sort(lkb, (False,) * len(lkb), 0,
                                          rows=sort_rows)
                emit = _semi_plan_fn(ctx.mesh, jt)(
                    lkb, lkv, lemit, rkb, rkv, remit)
        with _span("distributed_join.finish", seq):
            names = [f"lt-{i}" for i in range(left_d.column_count)]
            if mode is not None:
                # a validity that was None stays None: the row mask says
                # which slots hold a row
                cols = _rebuild_columns(
                    lod, [None if c.validity is None else v
                          for v, c in zip(lov, lcols_s)], lcols_s, names)
                for i in lvb:
                    vb = _varlen_take_sharded(ctx, lcols_s[i].varbytes,
                                              lidx_o)
                    cols[i] = Column(vb.lengths, lcols_s[i].dtype,
                                     cols[i].validity, None, names[i],
                                     varbytes=vb)
            else:   # the exchanged left side as it is, under the new mask
                cols = [c.rename(nm) for c, nm in zip(lcols_s, names)]
            result = Table(cols, ctx, emit)
            # the kept rows sit where the exchange put them: the left
            # side's co-partitioning witness, as INNER and LEFT keep it
            result._hash_partitioned = shard.partition_signature(
                lcols2, tuple(lidx), world)
            left._free_if_unretained()
            right._free_if_unretained()
            return _ledger.track(result, "distributed_join")

    res = None
    rows_out = None
    if mode is not None:
        with _span("distributed_join.plan", seq):
            rep_counts, counts_dev, a_streams, b_streams = \
                _join_plan_stream_fn(ctx.mesh, jt, len(lkb), a_desc,
                                     b_desc, br, hash_mode)(
                    lkb, lkv, lemit, rkb, rkv, remit,
                    ldat, lval, rdat, rval)
            # the plan program's replicated counts-gather is a real
            # collective dispatch — counted, so a launch comparison of
            # the shuffle and broadcast joins is honest on both
            _counter("cylon_collective_launches_total").inc()
            cm = np.asarray(
                _host_fetch("join.plan", rep_counts)).reshape(world, -1)
            collided = hash_mode and int(cm[:, 3].sum()) > 0
        if not collided:
            with _span("distributed_join.materialize", seq):
                cap_e = _join.stream_expand_capacity(int(cm[:, 0].max()),
                                                     br)
                res = _join_mat_stream_fn(
                    ctx.mesh, jt, cap_e, a_desc, b_desc, br)(
                    counts_dev, a_streams, b_streams,
                    ldat, lval, rdat, rval)
                rows_out = int(cm[:, 0].sum())
        # else: 64-bit hash collision — recompute via the exact XLA plan

    if res is not None:
        lod, lov, rod, rov, emit, lidx_o, ridx_o = res
    else:
        with _span("distributed_join.plan", seq):
            table_mod.count_plan_sort(lkb, (False,) * len(lkb),
                                      len(ldat) + len(rdat),
                                      rows=lkb[0].shape[-1]
                                      + rkb[0].shape[-1])
            counts2, lo, m, bperm, un_mask = _join_plan_fn(ctx.mesh, jt)(
                lkb, lkv, lemit, rkb, rkv, remit)
            # replicated counts-gather: a counted collective dispatch
            # (see the stream-plan branch above)
            _counter("cylon_collective_launches_total").inc()
            aemit = remit if jt == _join.JoinType.RIGHT else lemit
            # counts2 is the replicated [world, 2] matrix of per-shard
            # [n_primary, n_unmatched_b]; capacity = worst shard (all
            # shards share one program)
            counts = np.asarray(
                _host_fetch("join.plan", counts2)).reshape(world, 2)
            rows_out = int(counts[:, 0].sum())
            _annotate(rows_out=rows_out)
            # bucket_cap, not util.capacity: these caps are cache-key
            # parameters of _join_mat_fn — 1 bucket per octave bounds the
            # recompile count under varied cardinalities (specialization
            # analysis); padding rows are masked by emit, results
            # identical
            cap_p = _bucket_cap(int(counts[:, 0].max()))
            cap_u = _bucket_cap(int(counts[:, 1].max())) \
                if jt == _join.JoinType.FULL_OUTER else 0

        with _span("distributed_join.materialize", seq, world=world,
                   capacity=cap_p + cap_u):
            lod, lov, rod, rov, emit, lidx_o, ridx_o = _join_mat_fn(
                ctx.mesh, jt, cap_p, cap_u)(
                lo, m, bperm, un_mask, aemit, ldat, lval, rdat, rval)

    with _span("distributed_join.finish", seq, rows_out=rows_out):
        nl = left_d.column_count
        cols = _rebuild_columns(lod, lov, lcols_s,
                                [f"lt-{i}" for i in range(nl)])
        cols += _rebuild_columns(
            rod, rov, rcols_s,
            [f"rt-{nl + j}" for j in range(right_d.column_count)])
        # varbytes payload columns: per-shard varlen gather by the
        # materialized indices (fixed-width lanes carried only the
        # lengths)
        for i in lvb:
            vb = _varlen_take_sharded(ctx, lcols_s[i].varbytes, lidx_o)
            cols[i] = Column(vb.lengths, lcols_s[i].dtype,
                             cols[i].validity, None, cols[i].name,
                             varbytes=vb)
        for j in rvb:
            vb = _varlen_take_sharded(ctx, rcols_s[j].varbytes, ridx_o)
            cols[nl + j] = Column(vb.lengths, rcols_s[j].dtype,
                                  cols[nl + j].validity, None,
                                  cols[nl + j].name, varbytes=vb)
        result = Table(cols, ctx, emit)
        if exact_pairs:
            result, collided = _exact_post_verify(result, nl, exact_pairs,
                                                  config)
            if collided:
                # rare path (an actual 96-bit collision): skip the frees
                # — the encoded tables share payload columns with the
                # inputs
                return _exact_dict_redo(left, right, config, exact_pairs)
        # co-partitioning witness on the OUTPUT: every emitted row sits
        # on the shard its join-key hash routed it to, so a later shuffle
        # / pre-partitioned groupby on the same keys can skip its
        # exchange (the plan optimizer's shuffle-elision hook). Key
        # positions map straight through (left columns first); dtypes
        # come from the ALIGNED columns — if alignment promoted, the
        # signature's dtype string won't match the output column's and
        # the witness correctly never fires. Outer sides with unmatched
        # null keys invalidate the witness for that side.
        if jt in (_join.JoinType.INNER, _join.JoinType.LEFT):
            result._hash_partitioned = shard.partition_signature(
                lcols2, tuple(lidx), world)
        elif jt == _join.JoinType.RIGHT:
            result._hash_partitioned = shard.partition_signature(
                rcols2, tuple(nl + j for j in ridx), world)
        left._free_if_unretained()
        right._free_if_unretained()
        return _ledger.track(result, "distributed_join")


def _exact_post_verify(res: Table, nl: int, pairs, config):
    """Post-exchange byte verification for exact=True long varbytes keys.
    Both key columns sit row-aligned in the join output, so verification
    is one ``VarBytes.equals_rows`` per key pair: INNER joins filter the
    false matches out of the row mask; outer joins report any collision
    so the caller can redo on exact dictionary codes. Reference bar:
    arrow_hash_kernels.hpp:110-185 verifies true keys inline."""
    emit = res.row_mask
    if emit is None:
        emit = jnp.ones(res.capacity, bool)
    bad = jnp.zeros(res.capacity, bool)
    for li, rj in pairs:
        a, b = res._columns[li], res._columns[nl + rj]
        if not (a.is_varbytes and b.is_varbytes):
            continue
        both = a.valid_mask() & b.valid_mask()
        bad = bad | (emit & both & ~a.varbytes.equals_rows(b.varbytes))
    if config.type == _join.JoinType.INNER:
        return Table(res._columns, res._ctx, emit & ~bad), False
    collided = bool(_host_fetch("join.exact_verify", bad.any()))
    return res, collided


def _exact_dict_redo(left: Table, right: Table, config: _join.JoinConfig,
                     pairs) -> Table:
    """Collision recovery for exact outer joins on long varbytes keys:
    re-encode each colliding key pair over ONE shared sorted vocabulary
    (host round trip — paid only when a collision was actually detected,
    i.e. ~never) and redo the distributed join on the exact int32
    codes (same mechanism as the local `_exact_dict_fallback_join`).
    The redo's dictionary-coded key columns are re-materialized as
    varbytes so the recovery path's output schema matches the normal
    path, and the unretained originals are freed once the redo no
    longer shares their buffers."""
    from ..data.table import _dict_encode_pair

    ctx = left._ctx
    nl = left.column_count
    lcols2, rcols2 = list(left._columns), list(right._columns)
    for li, rj in pairs:
        lcols2[li], rcols2[rj] = _dict_encode_pair(left._columns[li],
                                                   right._columns[rj])
    cfg = _join.JoinConfig(config.type, config.left_column_idx,
                           config.right_column_idx, config.algorithm,
                           exact=False)
    res = distributed_join(Table(lcols2, left._ctx, left.row_mask),
                           Table(rcols2, right._ctx, right.row_mask),
                           cfg)
    # decode the redone key columns back through the shared vocab so the
    # output carries varbytes storage exactly like the collision-free path
    from ..data.column import as_varbytes

    out_cols = list(res._columns)
    for li, rj in pairs:
        for pos in (li, nl + rj):
            c = out_cols[pos]
            if c.dictionary is not None:
                vb_col = _dist_as_varbytes(ctx, c) \
                    if ctx.is_distributed() and ctx.get_world_size() > 1 \
                    else as_varbytes(c)
                out_cols[pos] = vb_col.rename(c.name)
    res = Table(out_cols, res._ctx, res.row_mask)
    # the redo is fully materialized now — nothing shares the originals'
    # buffers except via XLA refcounts, so the deferred frees are safe
    left._free_if_unretained()
    right._free_if_unretained()
    return res


# ---------------------------------------------------------------------------
# one join side as per-shard kernel operands and back: what the broadcast
# join hands its programs and rebuilds its result from
# ---------------------------------------------------------------------------


def _prep_join_side(ctx: CylonContext, t: Table, cols, other_cols):
    """One join side's per-shard kernel operands: key bit arrays +
    combined key validity + emit, plus the payload data/validity lanes
    with every (short) varbytes column's word lanes APPENDED as extra
    fixed-width lanes (the ArrowJoin trick — strings ride the
    fixed-width machinery; ``lane_slots`` maps column -> (first lane
    index, lane count) for the rebuild). The broadcast join's lanes
    gather with the replicated build side."""
    bits, kv = _dist_key_bits(ctx, cols, other_cols)
    emit = shard.pin(t.emit_mask(), ctx)
    dat = [shard.pin(c.data, ctx) for c in t._columns]
    val = [shard.pin(c.valid_mask(), ctx) for c in t._columns]
    lane_slots = {}
    for i, c in enumerate(t._columns):
        if c.is_varbytes:
            vb = c.varbytes
            lanes = _word_lanes_fn(ctx.mesh, vb.max_words)(
                shard.pin(vb.words, ctx), shard.pin(vb.starts, ctx),
                shard.pin(vb.lengths, ctx))
            lane_slots[i] = (len(dat), vb.max_words)
            dat.extend(lanes)
            val.extend([shard.pin(c.valid_mask(), ctx)] * vb.max_words)
    return bits, kv, emit, tuple(dat), tuple(val), lane_slots


def _rebuild_join_side(ctx: CylonContext, slabs_d, slabs_v, t: Table,
                       lane_slots, prefix: str):
    """Columns back out of one side's materialized slabs: varbytes
    columns reassemble from their word lanes (unmatched/dead/null slab
    rows carry garbage lanes — their lengths zero via the hit-AND-valid
    mask; never-written slab rows are zero-initialized)."""
    cols = []
    for i, c in enumerate(t._columns):
        d, v = slabs_d[i], slabs_v[i]
        if c.is_varbytes:
            off, k = lane_slots[i]
            lens = jnp.where(v, d, 0)
            vb = _from_lanes_sharded(
                ctx, [slabs_d[off + q] for q in range(k)], lens)
            cols.append(Column(vb.lengths, c.dtype, v, None,
                               f"{prefix}-{i}", varbytes=vb))
        else:
            cols.append(Column(d, c.dtype, v, c.dictionary,
                               f"{prefix}-{i}"))
    return cols


# ---------------------------------------------------------------------------
# broadcast-hash join (adaptive execution, ROADMAP item 1): when the
# planner has MEASURED one side small (stats warehouse, see
# plan/optimizer.adapt_from_stats), the all-to-all that dominates every
# distributed op per PAPER.md's local/shuffle/local composition is
# elided entirely — the build side is replicated to every shard via the
# counted-gather discipline (`replicated_gather`, the same psum one-hot
# trick `_join_plan_fn` uses for its counts) INSIDE the per-shard join
# program, and every shard probes its RESIDENT rows against the full
# build table with the same local join kernels. Zero payload
# all-to-all, zero probe-side movement: the probe side's
# `_hash_partitioned` witness survives the join unchanged.
# ---------------------------------------------------------------------------


def _gather_full(x, axis, world):
    """Per-shard [n, ...] leaf -> the FULL [world*n, ...] array
    replicated on every shard, rows in global (shard-major) order.
    psum-of-one-hot (replicated_gather) so shard_map's replication
    checker can statically prove the result replicated; bools ride as
    u8 (psum has no bool reduction)."""
    if x.dtype == jnp.bool_:
        g = replicated_gather(x.astype(jnp.uint8), axis, world)
        return g.reshape((-1,) + x.shape[1:]).astype(jnp.bool_)
    g = replicated_gather(x, axis, world)
    return g.reshape((-1,) + x.shape[1:])


@counted_cache
def _bcast_join_plan_fn(mesh, join_type: _join.JoinType):
    """Broadcast-join plan program: all_gather the (small) build
    side's key bits inside the shard_map, then run the SAME fused-sort
    join plan every shuffle join uses — probe rows per shard vs the
    full build table. Counts come back replicated (every controller
    process can fetch them, multi-host safe); the match arrays stay
    sharded for the materialize program."""
    axis = mesh.axis_names[0]
    world = mesh.devices.size
    spec = P(axis)

    def kernel(abits, akv, aemit, bbits, bkv, bemit):
        bb = tuple(_gather_full(x, axis, world) for x in bbits)
        bkv_f = _gather_full(bkv, axis, world)
        bemit_f = _gather_full(bemit, axis, world)
        counts2, lo, m, bperm, un_mask = _join.join_plan_keys(
            abits, akv, aemit, bb, bkv_f, bemit_f, join_type)
        return (replicated_gather(counts2, axis, world),
                lo, m, bperm, un_mask)

    return jax.jit(shard_map(kernel, mesh=mesh, in_specs=(spec,) * 6,
                             out_specs=(P(), spec, spec, spec, spec)))


@counted_cache
def _bcast_join_mat_fn(mesh, join_type: _join.JoinType, cap_p: int):
    """Broadcast-join materialize program: re-gather the build side's
    payload lanes (replication is recomputed, never cached — the build
    side is small by the planner's measured evidence), expand the
    match runs at the host-chosen capacity, and gather both sides.
    Probe gathers stay shard-local; build gathers index the replicated
    table."""
    axis = mesh.axis_names[0]
    world = mesh.devices.size
    spec = P(axis)

    def kernel(lo, m, bperm, un_mask, aemit, adat, aval, bdat, bval):
        bdat_f = tuple(_gather_full(x, axis, world) for x in bdat)
        bval_f = tuple(_gather_full(x, axis, world) for x in bval)
        # join_type is INNER or LEFT here (probe is always the a side),
        # so (lidx, ridx) == (aidx, bidx)
        aidx, bidx, emit = _join.join_materialize_gids(
            lo, m, bperm, un_mask, aemit, join_type, cap_p, 0)
        aod, aov = _gather_side(adat, aval, aidx)
        bod, bov = _gather_side(bdat_f, bval_f, bidx)
        return aod, aov, bod, bov, emit

    return jax.jit(shard_map(kernel, mesh=mesh, in_specs=(spec,) * 9,
                             out_specs=spec))


# sides a broadcast join may legally replicate, per join type: the
# probe must cover every row the join can emit unmatched. THREE
# deliberately-independent copies of this invariant exist — here (the
# runtime gate), plan/optimizer._BROADCAST_SIDES (the rewrite's choice,
# in preference order) and plan/verify._BROADCAST_SIDES (the
# optimizer-independent soundness check) — because the layering
# contracts forbid sharing (parallel never imports plan/, and the
# verifier must not share code with the optimizer). Their agreement is
# PINNED by tests/test_adaptive_join.py::test_broadcast_side_tables_agree;
# change one, change all three.
_BCAST_LEGAL_SIDES = {_join.JoinType.INNER: (0, 1),
                      _join.JoinType.LEFT: (1,),
                      _join.JoinType.RIGHT: (0,)}


def _broadcast_eligible(left: Table, right: Table,
                        config: _join.JoinConfig,
                        build_side: int) -> Optional[str]:
    """None when the broadcast path can run this join; otherwise the
    reason it must fall back to the shuffle composition."""
    from ..data.strings import EXACT_KEY_WORDS, LANE_WORDS_MAX

    jt = config.type
    legal = _BCAST_LEGAL_SIDES.get(jt, ())
    if build_side not in legal:
        return f"build_side={build_side} not replicable under {jt.name}"
    if any(c.is_varbytes and c.varbytes.max_words > LANE_WORDS_MAX
           for c in left._columns + right._columns):
        return "long varbytes payload cannot ride fixed word lanes"
    if getattr(config, "exact", False):
        for li, rj in zip(config.left_column_idx,
                          config.right_column_idx):
            kw = _pair_k(left._columns[li], right._columns[rj])
            if kw is not None and kw > EXACT_KEY_WORDS:
                # the shuffle path byte-verifies exact long keys
                # post-exchange; the broadcast path has no equivalent
                return "exact long varbytes keys need post-verification"
    return None


def broadcast_hash_join(left: Table, right: Table,
                        config: _join.JoinConfig,
                        build_side: int = 1) -> Table:
    """Replicate ``build_side`` (0=left, 1=right) to every shard and
    probe locally — the zero-all-to-all join for a measured-small
    build side. INNER may replicate either side; LEFT only its right
    input, RIGHT only its left (the probe must cover every row the
    join can emit unmatched). Ineligible shapes fall back to
    `distributed_join` (correct, just exchanged), annotating the open
    span with ``broadcast_fallback``. The output carries the PROBE
    side's placement witness unchanged: probe rows (and their
    duplicate expansions) never leave their shard."""
    ctx = left._ctx
    world = ctx.get_world_size()
    if world == 1:
        # a 1-wide mesh replicates nothing: the local join IS the
        # broadcast join (reference parity with distributed_join)
        _counter("cylon_join_algorithm_total", {"algo": "local"}).inc()
        return _ledger.track(table_mod.join(left, right, config),
                             "distributed_join")
    reason = _broadcast_eligible(left, right, config, build_side)
    if reason is not None:
        _annotate(join_algorithm="shuffle", broadcast_fallback=reason)
        return distributed_join(left, right, config)

    left_d = shard.distribute(left, ctx)
    right_d = shard.distribute(right, ctx)
    lidx, ridx = config.left_column_idx, config.right_column_idx
    lcols, rcols = _align_key_columns_dist(ctx, left_d, right_d, lidx,
                                           ridx)
    if build_side == 1:
        a_t, a_cols, b_t, b_cols = left_d, lcols, right_d, rcols
    else:
        a_t, a_cols, b_t, b_cols = right_d, rcols, left_d, lcols
    # the probe is always the a side, so LEFT/RIGHT both lower to the
    # local LEFT plan (emit unmatched probe rows)
    jt_local = _join.JoinType.INNER \
        if config.type == _join.JoinType.INNER else _join.JoinType.LEFT

    abits, akv, aemit, adat, aval, a_lane_slots = _prep_join_side(
        ctx, a_t, a_cols, b_cols)
    bbits, bkv, bemit, bdat, bval, b_lane_slots = _prep_join_side(
        ctx, b_t, b_cols, a_cols)

    seq = ctx.get_next_sequence()
    _counter("cylon_join_algorithm_total", {"algo": "broadcast"}).inc()
    with _span("broadcast_join.plan", seq, world=world,
               rows_in=a_t.capacity + b_t.capacity,
               build_rows=b_t.capacity, build_bytes=int(b_t.nbytes)):
        rep_counts, lo, m, bperm, un_mask = _bcast_join_plan_fn(
            ctx.mesh, jt_local)(abits, akv, aemit, bbits, bkv, bemit)
        # the gather program is this join's only collective transport
        _counter("cylon_collective_launches_total").inc()
        cm = np.asarray(
            _host_fetch("join.plan", rep_counts)).reshape(world, 2)
        _annotate(rows_out=int(cm[:, 0].sum()))
    cap_p = _bucket_cap(int(cm[:, 0].max()))

    with _span("broadcast_join.materialize", seq, world=world,
               capacity=cap_p):
        aod, aov, bod, bov, emit = _bcast_join_mat_fn(
            ctx.mesh, jt_local, cap_p)(lo, m, bperm, un_mask, aemit,
                                       adat, aval, bdat, bval)
        _counter("cylon_collective_launches_total").inc()

    a_cols_out = _rebuild_join_side(ctx, aod, aov, a_t, a_lane_slots,
                                    "a")
    b_cols_out = _rebuild_join_side(ctx, bod, bov, b_t, b_lane_slots,
                                    "b")
    if build_side == 1:
        cols = a_cols_out + b_cols_out
        nl = a_t.column_count
    else:
        cols = b_cols_out + a_cols_out
        nl = b_t.column_count
    cols = [c.rename(f"lt-{i}" if i < nl else f"rt-{i}")
            for i, c in enumerate(cols)]
    result = Table(cols, ctx, emit)
    # probe rows never moved (and duplicate expansions stay on their
    # source shard), so the probe table's placement witness survives —
    # position-mapped when the probe is the right side
    probe_t = left_d if build_side == 1 else right_d
    sig = probe_t._hash_partitioned
    if sig is not None:
        pos, dts, w = sig
        if build_side == 0:
            pos = tuple(nl + int(p) for p in pos)
        result._hash_partitioned = (tuple(int(p) for p in pos),
                                    tuple(dts), int(w))
    left._free_if_unretained()
    right._free_if_unretained()
    return _ledger.track(result, "distributed_join")


# ---------------------------------------------------------------------------
# distributed set ops (reference: DistributedUnion/Subtract/Intersect,
# table.cpp:948-1010 — ShuffleTwoTables on ALL columns + local set op)
# ---------------------------------------------------------------------------

def distributed_set_op(left: Table, right: Table,
                       op: _setops.SetOp) -> Table:
    ctx = left._ctx
    world = ctx.get_world_size()
    if world == 1:
        return _ledger.track(table_mod.set_op(left, right, op),
                             "distributed_set_op")
    if left.column_count != right.column_count:
        raise CylonPlanError("set ops need equal schemas")

    left_d = shard.distribute(left, ctx)
    right_d = shard.distribute(right, ctx)
    all_idx = list(range(left_d.column_count))
    lcols, rcols = _align_key_columns_dist(ctx, left_d, right_d,
                                           all_idx, all_idx)

    has_validity = [a.validity is not None or b.validity is not None
                    for a, b in zip(lcols, rcols)]

    seq = ctx.get_next_sequence()
    shuffled = []
    with _span("distributed_set_op.shuffle", seq, world=world,
               rows_in=left_d.capacity + right_d.capacity,
               op=str(op)):
        # exchange ONLY the aligned columns; key bits (word lanes /
        # hash quads / ordered bits) and validity key lanes are
        # recomputed per shard from the shuffled columns — the exchange
        # does not ship the lanes twice.
        # Both counts fuse into one program + one host sync.
        sides = []
        for cols, t, other in ((lcols, left_d, rcols),
                               (rcols, right_d, lcols)):
            view = Table(list(cols), ctx, t.row_mask)
            targets, emit = _targets_and_emit(ctx, t, cols, other)
            sides.append((view, targets, emit))
        cl, cr = count_pair(sides[0][1], sides[0][2],
                            sides[1][1], sides[1][2], ctx)
        for (view, targets, emit), cnt in zip(sides, (cl, cr)):
            out_cols, emit_s, _x = _exchange_table(view, targets, emit,
                                                   ctx, counts=cnt)
            shuffled.append((emit_s, out_cols))

    (lemit, lcols_s), (remit, rcols_s) = shuffled
    lcols_s2, rcols_s2 = _align_key_columns_dist(
        ctx, Table(list(lcols_s), ctx, lemit),
        Table(list(rcols_s), ctx, remit), all_idx, all_idx)

    # validity participates in the row key (nulls compare equal,
    # matching the reference's set-distinct semantics)
    lkb, _lkv = _dist_key_bits(ctx, lcols_s2, rcols_s2, has_validity)
    rkb, _rkv = _dist_key_bits(ctx, rcols_s2, lcols_s2, has_validity)
    ldat = tuple(shard.pin(c.data, ctx) for c in lcols_s)
    lval = tuple(shard.pin(c.valid_mask(), ctx) for c in lcols_s)
    rdat = tuple(shard.pin(c.data, ctx) for c in rcols_s)
    rval = tuple(shard.pin(c.valid_mask(), ctx) for c in rcols_s)

    with _span("distributed_set_op.count", seq):
        counts = np.asarray(_host_fetch(
            "setop.count", _setop_count_fn(ctx.mesh)(
                lkb, lemit, rkb, remit))).reshape(world, 3)
    total = counts[:, int(op)]
    cap = _bucket_cap(int(total.max()))

    with _span("distributed_set_op.materialize", seq):
        od, ov, emit, idx = _setop_mat_fn(ctx.mesh, op, cap)(
            lkb, lemit, rkb, remit, ldat, lval, rdat, rval)

    from ..data.strings import VarBytes

    cols = []
    for ci, (d, v, a) in enumerate(zip(od, ov, lcols_s)):
        if a.is_varbytes:
            bvb = rcols_s[ci].varbytes
            wcounts = np.asarray(_host_fetch(
                "varlen.count", _varlen_take_concat_count_fn(ctx.mesh)(
                    shard.pin(a.varbytes.lengths, ctx),
                    shard.pin(bvb.lengths, ctx), idx)))
            cap_w = _bucket_cap(int(wcounts.max()))
            w, s, ln = _varlen_take_concat_fn(ctx.mesh, cap_w)(
                shard.pin(a.varbytes.words, ctx),
                shard.pin(a.varbytes.starts, ctx),
                shard.pin(a.varbytes.lengths, ctx),
                shard.pin(bvb.words, ctx), shard.pin(bvb.starts, ctx),
                shard.pin(bvb.lengths, ctx), idx)
            vb = VarBytes(w, s, ln,
                          max(a.varbytes.max_words, bvb.max_words),
                          int(w.shape[0]),
                          shard_geom=(int(idx.shape[0]) // world, cap_w))
            cols.append(Column(vb.lengths, a.dtype, v, None, a.name,
                               varbytes=vb))
        else:
            cols.append(Column(d, a.dtype, v, a.dictionary, a.name))
    return _ledger.track(Table(cols, ctx, emit), "distributed_set_op")


# ---------------------------------------------------------------------------
# distributed groupby (reference: GroupBy, groupby/groupby.cpp:96-139 —
# local partial aggregation BEFORE the shuffle so exchanged bytes scale
# with groups, not rows; unlike the reference, partials merge with the
# CORRECT second-phase op — COUNT partials SUM, MEAN carries (sum, count)
# pairs — fixing the reference's COUNT-of-partials bug, SURVEY §3.2.)
# ---------------------------------------------------------------------------


def _group_key_columns(ctx: CylonContext, kout, kvout, safe, kcols):
    """The key columns of one per-shard aggregation step's groups, from
    the key data and validity `_groupby_fn` gives (read off the sorted
    lanes, validity None for a key without nulls; or gathered, and then
    a varbytes key's words by a per-shard varlen gather at each group's
    first row, ``safe``)."""
    out = []
    for d, v, kc in zip(kout, kvout, kcols):
        if kc.is_varbytes:
            vb = _varlen_take_sharded(ctx, kc.varbytes, safe)
            out.append(Column(vb.lengths, kc.dtype, v, None, kc.name,
                              varbytes=vb))
        else:
            out.append(Column(d, kc.dtype, v, kc.dictionary, kc.name))
    return out


def _groupby_shuffle_agg(ctx: CylonContext, phase: str, key_columns,
                         value_columns, ops: Tuple, emit, seq,
                         col_ids: Tuple = None,
                         skip_exchange: bool = False,
                         observe: bool = False):
    """Shuffle rows by key hash, then aggregate per shard (the step
    ``phase`` of `_aggregate_shards`). Returns (the arguments
    `_group_key_columns` makes the key columns from, agg list of (arr,
    valid), gvalid). ``emit``: the row mask, None for a table without one
    (then no dead flag rides a step in place, and the exchange's targets
    program gives the mask it moves the rows under). ``col_ids``: static
    source-column names for the aggregate's sub-reduction dedup (repeated
    (column, op) pairs compute once — see sorted_segment_aggregate).
    ``skip_exchange``: caller asserts every key's rows are already
    co-located on one shard (a co-partitioning witness from a prior
    shuffle/join on the same keys) — the per-shard aggregation is then
    globally exact with NO exchange at all (the plan optimizer's elided
    groupby-after-join path). ``observe``: the columns are the operator's
    input (moved, or in place), whose ranges may pack the sort
    (`_group_keys`); the merge's partial sums are not looked at, and a
    value column with validity None (a partial aggregate over a source
    without nulls: `_partial_masks_elided`) adds no leaf to the exchange
    and no mask operand to the step's sort. Every
    host statement runs under a leaf span (`distributed_groupby.targets`,
    the exchange's own leaves, `.keybits`, `.aggregate`), so that a
    chip's idle time books to a
    name; `distributed_groupby.shuffle` keeps what they leave."""
    if skip_exchange:
        out_cols = list(key_columns) + list(value_columns)
        emit_s = emit
        _annotate(exchange_skipped=True)
    else:
        with _span("distributed_groupby.shuffle", seq,
                   world=ctx.get_world_size(),
                   rows_in=int(key_columns[0].data.shape[0])):
            view = Table(list(key_columns) + list(value_columns), ctx,
                         None)
            with _span("distributed_groupby.targets", seq,
                       key_columns=len(key_columns)):
                if emit is None:   # the all-ones mask, out of the program
                    targets, emit = _dispatch_targets(ctx, key_columns,
                                                      None, True)
                else:
                    targets = _partition_targets_dist(ctx, key_columns)
            out_cols, emit_s, _x = _exchange_table(view, targets, emit,
                                                   ctx)

    with _span("distributed_groupby.keybits", seq) as _sp:
        nk = len(key_columns)
        kcols_s = out_cols[:nk]
        vcols_s = out_cols[nk:]
        # key bits recompute per shard from the shuffled key columns —
        # recomputable lanes never cross the exchange (round-4 review)
        vdat = tuple(shard.pin(c.data, ctx) for c in vcols_s)
        vval = tuple(None if c.validity is None
                     else shard.pin(c.valid_mask(), ctx) for c in vcols_s)
        key_spec, kbits, kdat, kval, packing = _group_keys(
            ctx, kcols_s, emit_s, vdat, ops, observe)
        _sp.set(key_lanes=len(kbits), rows=int(kbits[0].shape[0]))

    with _span("distributed_groupby.aggregate", seq):
        if col_ids is None:
            col_ids = tuple(range(len(vcols_s)))
        all_valid = tuple(c.validity is None for c in vcols_s)
        kout, kvout, gvalid, agg, safe = _aggregate_shards(
            ctx, phase, ops, col_ids, all_valid, key_spec, kbits, kdat,
            kval, emit_s, vdat, vval, packing)
    return (kout, kvout, safe, kcols_s), list(agg), gvalid


def distributed_groupby(table: Table, index_col, aggregate_cols: List,
                        aggregate_ops: List[_groupby.AggregationOp],
                        pre_aggregate: bool = True,
                        pre_partitioned: bool = False) -> Table:
    """``pre_partitioned``: caller asserts the table's rows are already
    hash-placed by the groupby keys (e.g. the output of a join/shuffle
    on the same keys, witnessed by ``_hash_partitioned``) — the whole
    exchange is skipped and ONE per-shard aggregation pass produces the
    exact global result. The plan executor verifies the witness before
    setting this; a false assertion would split groups across shards."""
    ctx = table._ctx
    world = ctx.get_world_size()
    if world == 1:
        return _ledger.track(
            table_mod.groupby_local(table, index_col, aggregate_cols,
                                    aggregate_ops),
            "distributed_groupby")

    seq = ctx.get_next_sequence()
    with _span("distributed_groupby.distribute", seq, world=world) as _sp:
        t = shard.distribute(table, ctx)
        _sp.set(already_distributed=int(t is table))
        idx_cols = index_col if isinstance(index_col, (list, tuple)) \
            else [index_col]
        idx_cols = [t._col_index(c) for c in idx_cols]
        val_cols = [t._col_index(c) for c in aggregate_cols]
        key_columns = [t._columns[i] for i in idx_cols]
        for vi, op in zip(val_cols, aggregate_ops):
            if t._columns[vi].is_varbytes and \
                    op != _groupby.AggregationOp.COUNT:
                raise CylonPlanError(
                    "varbytes value columns support COUNT only",
                    code=Code.NotImplemented)
        ops = list(aggregate_ops)
        # None (no row mask): every row is live, no dead flag rides the
        # first sort and no all-ones mask is made (a table `distribute`
        # had to pad keeps its mask)
        emit = None if t.row_mask is None else shard.pin(t.row_mask, ctx)
        # the input's capacity: what dist_groupby_partial_share holds
        # the exchange's live rows against
        _counter("cylon_groupby_rows_in_total").inc(int(t.capacity))
    MEAN = _groupby.AggregationOp.MEAN
    SUM = _groupby.AggregationOp.SUM
    COUNT = _groupby.AggregationOp.COUNT

    def agg_column(arr, av, src, op, dtype=None):
        keep_dict = (op in (_groupby.AggregationOp.MIN,
                            _groupby.AggregationOp.MAX)
                     and src.is_string)
        return Column(arr, dtype or table_mod._agg_dtype(src, op, arr), av,
                      src.dictionary if keep_dict else None, src.name)

    def finish(key_out, cols, gvalid):
        out = Table(list(key_out) + cols, ctx, gvalid)
        # output keys stay hash-placed (rows never moved / moved by key
        # hash; phase B placed every group on its key-hash shard):
        # witness lets a further same-key stage skip its shuffle
        out._hash_partitioned = shard.partition_signature(
            key_out, tuple(range(len(key_out))), world)
        return _ledger.track(out, "distributed_groupby")

    if pre_partitioned or not pre_aggregate:
        value_columns = [t._columns[vi] for vi in val_cols]
        keys, agg, gvalid = _groupby_shuffle_agg(
            ctx, "single", key_columns, value_columns, tuple(ops), emit,
            seq, col_ids=tuple(val_cols), skip_exchange=pre_partitioned,
            observe=True)
        with _span("distributed_groupby.finish", seq):
            return finish(_group_key_columns(ctx, *keys), [
                agg_column(arr, av, t._columns[vi], op)
                for (arr, av), vi, op in zip(agg, val_cols, ops)], gvalid)

    # ---- phase A: per-shard partial aggregation (shuffle bytes then
    # scale with per-shard GROUPS, not rows). MEAN expands to
    # (f64 SUM, COUNT) partial pairs; phase B merges with the correct
    # second-phase op (COUNT partials are SUMmed). The partial table's
    # columns carry a validity only where it says more than the table's
    # row mask (a nullable source: `_partial_masks_elided`); the merge's
    # results, the user's, keep theirs as `groupby_local` gives them.
    a_entries = []   # (orig_pos, opA, cast_f64)
    b_ops = []
    out_map = []     # per original op: ("d", a_idx) | ("mean", si, ci)
    for j, op in enumerate(ops):
        if op == MEAN:
            out_map.append(("mean", len(a_entries), len(a_entries) + 1))
            a_entries += [(j, SUM, True), (j, COUNT, False)]
            b_ops += [SUM, SUM]
        else:
            out_map.append(("d", len(a_entries)))
            a_entries.append((j, op, False))
            b_ops.append(_groupby.second_phase_op(op))

    with _span("distributed_groupby.pre_aggregate", seq):
        vdatA, vvalA = [], []
        for j, _opA, cast in a_entries:
            src = t._columns[val_cols[j]]
            d = src.data.astype(jnp.float64) if cast else src.data
            vdatA.append(shard.pin(d, ctx))
            vvalA.append(None if src.validity is None
                         else shard.pin(src.valid_mask(), ctx))
        opsA = tuple(opA for _j, opA, _c in a_entries)
        specA, kbitsA, kdatA, kvalA, packingA = _group_keys(
            ctx, key_columns, emit, tuple(vdatA), opsA, True)
        cidsA = tuple((val_cols[j], cast) for j, _opA, cast in a_entries)
        avA = tuple(t._columns[val_cols[j]].validity is None
                    for j, _opA, _c in a_entries)
        # which partials go without a validity (it would be gvalidA)
        elidedA = _partial_masks_elided(opsA, avA)
        _counter("cylon_groupby_partial_masks_elided_total").inc(
            sum(elidedA))
        _counter("cylon_groupby_partial_masks_carried_total").inc(
            len(elidedA) - sum(elidedA))
        koutA, kvoutA, gvalidA, aggA, safeA = _aggregate_shards(
            ctx, "partial", opsA, cidsA, avA, specA, kbitsA, kdatA, kvalA,
            emit, tuple(vdatA), tuple(vvalA), packingA, elidedA)
        # the partial table: one row a (shard, group); an elided
        # aggregate's validity comes back None
        pkey_cols = _group_key_columns(ctx, koutA, kvoutA, safeA,
                                       key_columns)
        pval_cols = [
            agg_column(arr, av, t._columns[val_cols[j]], opA,
                       dtypes.Double() if cast else None)
            for (arr, av), (j, opA, cast) in zip(aggA, a_entries)]

    # ---- phase B: shuffle the partials, merge with second-phase ops
    keys, aggB, gvalid = _groupby_shuffle_agg(
        ctx, "merge", pkey_cols, pval_cols, tuple(b_ops), gvalidA, seq)

    with _span("distributed_groupby.finish", seq):
        cols = []
        for op, vi, m in zip(ops, val_cols, out_map):
            src = t._columns[vi]
            if m[0] == "mean":
                s_arr, s_av = aggB[m[1]]
                c_arr, c_av = aggB[m[2]]
                data = s_arr / jnp.maximum(c_arr.astype(jnp.float64), 1)
                av = s_av & c_av & (c_arr > 0)
                cols.append(Column(data, table_mod._agg_dtype(src, op, data), av,
                                   None, src.name))
            else:
                cols.append(agg_column(*aggB[m[1]], src, op))
        return finish(_group_key_columns(ctx, *keys), cols, gvalid)


# ---------------------------------------------------------------------------
# distributed sort. The reference has local Sort only (table.hpp:365);
# this extension is splitter-based: sample keys → agree global range
# splitters → range-partition through the SAME exchange the joins use →
# fused per-shard sort. Nothing ever all-gathers; shard i's rows all
# precede shard i+1's, so global order = (shard, position). Multi-key
# and varbytes ORDER columns use the XLA global-sort fallback /
# local-sort path.
# ---------------------------------------------------------------------------

# per-shard sample count for splitter estimation (total = world * this)
SORT_SAMPLES_PER_SHARD = 4096


@counted_cache
def _shard_sort_fn(mesh, nd: int, nv: int, nk: int = 1):
    """Per-shard fused sort by (dead-last, key lanes…): every payload
    column rides as a sort operand; returns sorted dat/val/emit plus the
    permutation (for varbytes content takes). ``nk``: number of key
    lanes (multi-key / varbytes-prefix sorts pass several)."""
    spec = P(mesh.axis_names[0])

    def kernel(bits, emit, dat, val):
        n = bits[0].shape[0]
        dead = (~emit).astype(jnp.uint8)
        iota = jnp.arange(n, dtype=jnp.int32)
        ops = (dead,) + tuple(bits) + tuple(dat) + tuple(val) + (emit, iota)
        res = jax.lax.sort(ops, num_keys=1 + nk, is_stable=True)
        o = 1 + nk
        return (res[o:o + nd], res[o + nd:o + nd + nv], res[-2], res[-1])

    return jax.jit(shard_map(kernel, mesh=mesh, in_specs=(spec,) * 4,
                             out_specs=spec))


def _range_splitters(ctx: CylonContext, lanes, emit):
    """Host-side splitter agreement over COMPOSITE keys: gather a small
    random sample of every key lane, keep live rows, take world-1
    lexicographic quantiles. Deterministic seed keeps every controller
    process agreeing (multi-host: same computation on the replicated
    sample). Returns a list of world-1 key TUPLES."""
    world = ctx.get_world_size()
    n = int(lanes[0].shape[0])
    rng = np.random.default_rng(0xC11)
    k = min(n, SORT_SAMPLES_PER_SHARD * world)
    pos = jnp.asarray(np.sort(rng.integers(0, n, k)).astype(np.int32))
    # ONE device_get for all lanes + emit (round-5: was len(lanes)+1
    # sequential fetches, one host round trip each). Samples pack into
    # a single matrix of the widest unsigned lane type; unsigned casts round-trip
    # each lane's values exactly. uint64 packing only arises under x64
    # (TPU mode keeps lanes <=32-bit, so the cast never narrows).
    wide = jnp.uint64 if max(l.dtype.itemsize for l in lanes) == 8 \
        else jnp.uint32
    packed = jnp.stack(
        [jnp.take(l, pos).astype(wide) for l in lanes]
        + [jnp.take(emit, pos).astype(wide)])
    host = np.asarray(_host_fetch("sort.splitters", packed))
    live = host[-1].astype(bool)
    samples = [host[i].astype(l.dtype)[live]
               for i, l in enumerate(lanes)]
    if samples[0].size == 0:
        return [tuple(s.dtype.type(0) for s in samples)] * (world - 1)
    order = np.lexsort(tuple(reversed(samples)))
    q = (np.arange(1, world) * samples[0].size) // world
    return [tuple(s[order[qi]] for s in samples) for qi in q]


def _splitter_targets(lanes, splitters):
    """target = #splitter-tuples lexicographically <= the row's key
    tuple: (world-1) * n_lanes vector compares, no searchsorted."""
    targets = jnp.zeros(lanes[0].shape[0], jnp.int32)
    for tup in splitters:
        ge = jnp.zeros(lanes[0].shape[0], bool)
        eq = jnp.ones(lanes[0].shape[0], bool)
        for lane, sv in zip(lanes, tup):
            v = jnp.asarray(sv)
            ge = ge | (eq & (lane > v))
            eq = eq & (lane == v)
        targets = targets + (ge | eq).astype(jnp.int32)
    return targets


def _dist_order_lanes(ctx: CylonContext, c: Column, a: bool):
    """Bit lanes whose lexicographic tuple order equals column c's sort
    order (ascending=a, nulls last) — the distributed analog of
    table._sort_keys_mixed. Varbytes columns use per-shard big-endian
    prefix word lanes + length (exact up to SORT_PREFIX_WORDS*4 bytes;
    beyond that returns None → host path). Reference: sort kernels incl.
    strings, arrow_kernels.cpp:136-317."""
    if c.is_varbytes:
        from ..data.strings import SORT_PREFIX_WORDS, _bswap32

        vb = c.varbytes
        if not vb.sortable_on_device:
            return None
        k_lim = min(vb.max_words, SORT_PREFIX_WORDS)
        lanes = [_bswap32(l) for l in _dist_word_lanes(ctx, c, k_lim)]
        lanes.append(vb.lengths.astype(jnp.uint32))
        if not a:
            lanes = [l ^ jnp.uint32(0xFFFFFFFF) for l in lanes]
        if c.validity is not None:
            ext = jnp.uint32(0xFFFFFFFF)
            lanes = [jnp.where(c.validity, l, ext) for l in lanes]
        return lanes
    return list(_order.sort_keys([c], [a]))


def distributed_sort(table: Table, order_by, ascending=True) -> Table:
    """Splitter-based distributed sort over ANY key combination: sample
    composite key-lane tuples, agree range splitters, range-partition
    through the same exchange the joins use, per-shard fused sort. No
    global gather for multi-key or (short) varbytes ORDER columns; rows
    beyond the device prefix bound (> SORT_PREFIX_WORDS*4-byte strings)
    take the host path. Reference: Sort + sort kernels incl. strings
    (table.hpp:365, arrow_kernels.cpp:136-317)."""
    ctx = table._ctx
    t = shard.distribute(table, ctx) if ctx.is_distributed() else table
    by = order_by if isinstance(order_by, (list, tuple)) else [order_by]
    idxs = [t._col_index(c) for c in by]
    asc = list(ascending) if isinstance(ascending, (list, tuple)) \
        else [ascending] * len(idxs)
    world = ctx.get_world_size()
    order_cols = [t._columns[i] for i in idxs]

    if not (ctx.is_distributed() and world > 1):
        return t.sort(by, ascending)

    per_col = [_dist_order_lanes(ctx, c, a)
               for c, a in zip(order_cols, asc)]
    if any(l is None for l in per_col):
        # >SORT_PREFIX_WORDS varbytes keys: host sort of the SORT
        # columns only, then redistribute (the reference's string sort
        # is a host-memory Arrow kernel too, arrow_kernels.cpp:136-230)
        return shard.distribute(t.compact().sort(by, ascending), ctx)
    lanes = [l for col_lanes in per_col for l in col_lanes]

    seq = ctx.get_next_sequence()
    with _span("distributed_sort.partition", seq, world=world,
               rows_in=t.capacity):
        lanes = [shard.pin(l, ctx) for l in lanes]
        emit = shard.pin(t.emit_mask(), ctx)
        # splitter memoization (the count-cache pattern, weakref-keyed
        # on the SOURCE column buffers): repeat sorts of the same table
        # skip the ~100 ms sample fetch — the lanes themselves are fresh
        # derived arrays every call, so the key is the source data
        from .shuffle import _count_cached

        # memo key/refs span data + validity + varbytes buffers (data
        # ids alone could alias columns differing only in validity or
        # string content), same discipline as the join memos
        src_ids, src_refs = table_mod._memo_refs(order_cols)
        if t.row_mask is not None:
            src_ids = src_ids + (id(t.row_mask),)
            src_refs = src_refs + (t.row_mask,)
        splitters = _count_cached(
            ("splitters", id(ctx.mesh), tuple(asc), world) + src_ids,
            src_refs, lambda: _range_splitters(ctx, lanes, emit))
        targets = _splitter_targets(lanes, splitters)
        cols_s, emit_s, _x = _exchange_table(
            t, shard.pin(targets, ctx), emit, ctx)

    with _span("distributed_sort.local", seq):
        # key lanes recompute per shard from the shuffled columns —
        # recomputable lanes never cross the exchange (same pattern as
        # the join/set-op/groupby shuffles)
        t_s = Table(list(cols_s), ctx, emit_s)
        order_cols_s = [t_s._columns[i] for i in idxs]
        per_col_s = [_dist_order_lanes(ctx, c, a)
                     for c, a in zip(order_cols_s, asc)]
        sbits = tuple(shard.pin(l, ctx)
                      for col_lanes in per_col_s for l in col_lanes)
        dat = tuple(shard.pin(c.data, ctx) for c in cols_s)
        val = tuple(shard.pin(c.valid_mask(), ctx) for c in cols_s)
        sdat, sval, semit, perm = _shard_sort_fn(
            ctx.mesh, len(dat), len(val), len(sbits))(
            sbits, emit_s, dat, val)
    out_cols = []
    for d, v, c in zip(sdat, sval, cols_s):
        if c.is_varbytes:
            vb = _varlen_take_sharded(ctx, c.varbytes, perm)
            out_cols.append(Column(vb.lengths, c.dtype, v, None, c.name,
                                   varbytes=vb))
        else:
            out_cols.append(Column(d, c.dtype, v, c.dictionary, c.name))
    return _ledger.track(Table(out_cols, ctx, semit), "distributed_sort")


