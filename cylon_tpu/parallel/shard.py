"""Row-sharding of tables over the device mesh.

The reference's distribution model is "one ragged Arrow table per MPI rank"
(reference: cpp/src/cylon/ctx/cylon_context.hpp:29 — rank/world_size; every
distributed op is a collective all ranks enter). The TPU-native model keeps
ONE global Table whose column arrays carry a `jax.sharding.NamedSharding`
over the 1-D mesh axis: shard i of every array is partition i. Raggedness
is expressed by padding every shard to one common capacity and masking the
padding rows via the table's ``row_mask`` — XLA requires static, equal
shapes per shard; the mask is the moral equivalent of Arrow's per-rank row
counts.

`distribute` is the entry point: pad → device_put with the row sharding.
It is a no-op for tables already laid out on the context's mesh, so eager
op pipelines don't re-transfer.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..context import CylonContext
from ..data.column import Column, refuse_planes
from ..data.table import Table
from ..status import Code, CylonPlanError
from ..telemetry import host_fetch as _host_fetch

# Per-shard capacities are rounded to a multiple of 8 (TPU sublane quantum)
_ROW_QUANTUM = 8


def row_sharding(ctx: CylonContext) -> NamedSharding:
    """The canonical row-partitioned sharding for this context's mesh."""
    return NamedSharding(ctx.mesh, P(ctx.axis))


def is_row_sharded(arr, ctx: CylonContext) -> bool:
    sh = getattr(arr, "sharding", None)
    if not isinstance(sh, NamedSharding):
        return False
    return sh.mesh == ctx.mesh and sh.spec == P(ctx.axis)


def is_distributed_table(table: Table, ctx: CylonContext) -> bool:
    if not table._columns:
        return False
    n = table.capacity
    if n % ctx.get_world_size() != 0:
        return False
    return all(is_row_sharded(c.data, ctx) for c in table._columns)


def pin(arr, ctx: CylonContext):
    """Force an array onto the row sharding (no-op when already there).

    Eager elementwise ops usually preserve sharding, but host-built or
    gather-produced arrays may not carry it — pin before entering a
    shard_map kernel."""
    if is_row_sharded(arr, ctx):
        return arr
    return jax.device_put(arr, row_sharding(ctx))


def shard_capacity(n: int, world: int) -> int:
    """Per-shard padded capacity for n global rows."""
    c = -(-max(n, 1) // world)
    return -(-c // _ROW_QUANTUM) * _ROW_QUANTUM


def _pad_to(arr: jnp.ndarray, total: int, fill):
    n = arr.shape[0]
    if n == total:
        return arr
    pad = jnp.full((total - n,) + arr.shape[1:], fill, arr.dtype)
    return jnp.concatenate([arr, pad])


def distribute(table: Table, ctx: CylonContext) -> Table:
    """Shard a table's rows over the context mesh (pad + device_put).

    Already-distributed tables pass through untouched. The result's
    ``row_mask`` marks padding rows dead; real rows keep their validity.
    """
    if is_distributed_table(table, ctx):
        return table
    world = ctx.get_world_size()
    refuse_planes(table._columns, f"distribute over {world} chips")
    n = table.capacity
    cap = shard_capacity(n, world)
    total = world * cap
    sharding = row_sharding(ctx)

    cols = []
    for c in table._columns:
        if c.is_varbytes:
            cols.append(_distribute_varbytes(c, n, cap, world, sharding))
            continue
        data = jax.device_put(_pad_to(c.data, total, 0), sharding)
        validity = None
        if c.validity is not None:
            validity = jax.device_put(_pad_to(c.validity, total, False), sharding)
        cols.append(Column(data, c.dtype, validity, c.dictionary, c.name))
    if table.row_mask is None and total == n:
        # no padding, all rows live: preserve mask-None — downstream
        # routing reads "row_mask is None" as the dense invariant (the
        # groupby's fused sort carries no dead flag without a mask)
        mask = None
    else:
        mask = jax.device_put(_pad_to(table.emit_mask(), total, False),
                              sharding)
    return Table(cols, ctx, mask)


def _distribute_varbytes(c: Column, n: int, cap: int, world: int,
                         sharding) -> Column:
    """Shard a varbytes column: each shard gets a SELF-CONTAINED local
    (words, starts, lengths) layout — starts are shard-relative word
    indices, so per-shard kernels (hash, take) run with no cross-shard
    word addressing. Shards' word buffers pad to a common capacity."""
    from ..data.strings import VarBytes
    from ..util import capacity as _capacity

    vb = c.varbytes
    # one device_get + numpy slicing + one device_put: each shard's rows
    # are a CONTIGUOUS row range, so its words are a contiguous slice of
    # the source buffer (monotone starts) — no per-shard device gathers
    words_h, starts_h, lens_h = (np.asarray(a) for a in _host_fetch(
        "distribute.varbytes", (vb.words, vb.eff_starts(), vb.lengths)))
    nw_h = (lens_h.astype(np.int64) + 3) // 4
    slices = []
    for s in range(world):
        lo, hi = s * cap, min((s + 1) * cap, n)
        if lo >= hi:
            slices.append((0, 0, lo, hi))
            continue
        w_lo = int(starts_h[lo])
        w_hi = int(starts_h[hi - 1] + nw_h[hi - 1])
        slices.append((w_lo, w_hi, lo, hi))
    wc = _capacity(max(max(w_hi - w_lo for w_lo, w_hi, _l, _h in slices), 1))
    words = np.zeros(world * wc, np.uint32)
    starts = np.zeros(world * cap, np.int32)
    lengths = np.zeros(world * cap, np.int32)
    for s, (w_lo, w_hi, lo, hi) in enumerate(slices):
        words[s * wc: s * wc + (w_hi - w_lo)] = words_h[w_lo:w_hi]
        starts[s * cap: s * cap + (hi - lo)] = starts_h[lo:hi] - w_lo
        lengths[s * cap: s * cap + (hi - lo)] = lens_h[lo:hi]
    out_vb = VarBytes(jax.device_put(jnp.asarray(words), sharding),
                      jax.device_put(jnp.asarray(starts), sharding),
                      jax.device_put(jnp.asarray(lengths), sharding),
                      vb.max_words, world * wc, shard_geom=(cap, wc))
    validity = None
    if c.validity is not None:
        validity = jax.device_put(
            _pad_to(c.validity, world * cap, False), sharding)
    return Column(out_vb.lengths, c.dtype, validity, None, c.name,
                  varbytes=out_vb)


def distribute_array(arr, n_src_rows: int, ctx: CylonContext,
                     fill=0) -> jnp.ndarray:
    """Shard an auxiliary per-row array with the same padding geometry a
    table of ``n_src_rows`` rows gets from `distribute`."""
    world = ctx.get_world_size()
    cap = shard_capacity(n_src_rows, world)
    return jax.device_put(_pad_to(jnp.asarray(arr), world * cap, fill),
                          row_sharding(ctx))


def partition_signature(key_cols, idxs, world: int):
    """Hashable co-partitioning witness: a table whose rows were placed
    by hash of these key columns can skip a later shuffle on the same
    keys — but only when the key dtypes at join time match the dtypes
    hashed at placement time (align_key_columns may promote), and never
    for strings (vocabulary unification re-codes them)."""
    if any(c.is_string for c in key_cols):
        return None
    return (tuple(int(i) for i in idxs),
            tuple(str(c.data.dtype) for c in key_cols), int(world))


def host_partition_arrays(t: Table, idxs, world: int):
    """Shared host-side partition preamble: pull a COMPACTED table's
    columns to host, run the native partitioner over its key columns,
    and return (host_cols, valids, counts, order, offsets). Used by both
    distribute_by_key and dist_ops.hash_partition so placement logic
    lives in exactly one place.

    Varbytes columns come to host as object arrays; varbytes KEY columns
    hash their actual BYTES through the host mirror of the device
    content hash (native.np_varbytes_hash == strings._hash_rows h1), so
    placement is a pure function of key VALUES — equal keys in two
    independently built tables land on the same partition, and the host
    fallback agrees with the device hash_partition path. (ADVICE r5
    medium: the previous table-local np.unique dictionary codes made
    placement depend on each table's whole key set.)"""
    from .. import native as _native
    from ..dtypes import Type

    data_h, valids = _host_fetch("ingest.host_partition", (
        [None if c.is_varbytes else c.data for c in t._columns],
        [None if c.validity is None else c.valid_mask()
         for c in t._columns]))
    host = [c.varbytes.to_host(as_str=c.dtype.type != Type.BINARY)
            if c.is_varbytes else np.asarray(d)
            for c, d in zip(t._columns, data_h)]
    valids = [None if v is None else np.asarray(v) for v in valids]
    keys = []
    pre = []
    for i in idxs:
        if t._columns[i].is_varbytes:
            keys.append(_native.np_varbytes_hash(host[i]))
            pre.append(True)
        else:
            keys.append(host[i])
            pre.append(False)
    flags = [False if p else t._columns[i].is_string
             for i, p in zip(idxs, pre)]
    _targets, counts, order = _native.hash_partition(
        keys, [valids[i] for i in idxs], world, is_string=flags,
        prehashed=pre)
    offs = np.concatenate([[0], np.cumsum(counts)])
    return host, valids, counts, order, offs


def distribute_by_key(table: Table, ctx: CylonContext, key_columns) -> Table:
    """Host-side pre-partitioned ingest: place every row on the shard its
    key HASHES to (the placement a device shuffle would produce), using
    the native partitioner (native/cylon_host.cpp ct_row_hash /
    ct_partition_order — bit-identical to ops/hash.partition_targets).

    The result carries a co-partitioning witness, so `shuffle` on the
    same keys is a no-op and `distributed_join` skips that side's
    exchange — the ingest-time analog of the reference shuffling inside
    DistributedJoin (table.cpp:656-696), moved off the device entirely.
    """
    world = ctx.get_world_size()
    refuse_planes(table._columns, "distribute_by_key")
    idxs = [table._col_index(c) for c in key_columns]
    t = table.compact()
    key_cols = [t._columns[i] for i in idxs]
    host, valids, counts, order, offs = host_partition_arrays(t, idxs, world)

    cap = shard_capacity(int(counts.max()), 1)
    total = world * cap
    sharding = row_sharding(ctx)

    def build(arr, fill, dtype=None):
        a = np.asarray(arr)
        g = a[order]
        out = np.full((total,) + a.shape[1:], fill,
                      a.dtype if dtype is None else dtype)
        for s in range(world):
            out[s * cap:s * cap + counts[s]] = g[offs[s]:offs[s + 1]]
        return jax.device_put(jnp.asarray(out), sharding)

    if any(c.is_varbytes for c in t._columns):
        # varbytes rows can't lift through the fixed-width build():
        # materialize each shard's rows as a host table (VarBytes
        # rebuilt from the partitioned object arrays) and assemble —
        # shard i of the result holds partition i, same placement
        from ..data.strings import VarBytes

        if ctx.get_process_count() > 1:
            raise CylonPlanError(
                "multi-host distribute_by_key with varbytes columns: "
                "use per-rank file placement (read_csv_per_rank)",
                code=Code.NotImplemented)

        shard_tables = []
        for s in range(world):
            seg = order[offs[s]:offs[s + 1]]
            cols = []
            for ci, c in enumerate(t._columns):
                v = None if valids[ci] is None \
                    else jnp.asarray(valids[ci][seg])
                if c.is_varbytes:
                    vb = VarBytes.from_host(host[ci][seg])
                    cols.append(Column(vb.lengths, c.dtype, v, None,
                                       c.name, varbytes=vb))
                else:
                    cols.append(Column(jnp.asarray(host[ci][seg]),
                                       c.dtype, v, c.dictionary, c.name))
            shard_tables.append(Table(cols, ctx))
        out = assemble_process_local(shard_tables, ctx)
        out._hash_partitioned = partition_signature(key_cols, idxs, world)
        return out

    cols = []
    for ci, c in enumerate(t._columns):
        data = build(host[ci], 0)
        validity = None if valids[ci] is None else build(valids[ci], False)
        cols.append(Column(data, c.dtype, validity, c.dictionary, c.name))
    emit = np.zeros(total, np.bool_)
    for s in range(world):
        emit[s * cap:s * cap + counts[s]] = True
    out = Table(cols, ctx, jax.device_put(jnp.asarray(emit), sharding))
    out._hash_partitioned = partition_signature(key_cols, idxs, world)
    return out


def assemble_process_local(tables, ctx: CylonContext) -> Table:
    """Build ONE global distributed Table from per-shard host tables, one
    per shard this process owns (the multi-host ingest path: the
    reference's per-rank CSV convention, cpp/test/join_test.cpp:22-24,
    maps to per-shard files read by the owning controller).

    Every process calls this collectively with its own local shard list
    (len == len(ctx.local_shard_indices())). Per-shard row counts may be
    ragged; shards are padded to the global max (agreed via a tiny
    all-gathered count exchange) and the padding is masked dead.

    String columns are lifted to device-native varbytes storage
    (data/strings.py): content hashes need NO global vocabulary, so
    every process ingests its strings independently — the reference's
    per-rank binary columns (arrow_partition_kernels.hpp:94) with zero
    cross-process coordination beyond the word-capacity agreement.
    """
    from jax.experimental import multihost_utils

    from ..data.column import as_varbytes
    from ..util import capacity as _capacity

    local = ctx.local_shard_indices()
    if len(tables) != len(local):
        raise CylonPlanError(
            f"need one table per local shard ({len(local)}), "
            f"got {len(tables)}")
    for t in tables:
        refuse_planes(t._columns, "assemble_process_local")
    tables = [t.compact() for t in tables]

    first = tables[0]
    vb_cols = [ci for ci in range(first.column_count)
               if any(t._columns[ci].is_string for t in tables)]
    # lift once; the counts matrix AND the buffer assembly reuse these
    lifted = {ci: [as_varbytes(t._columns[ci]) for t in tables]
              for ci in vb_cols}

    # rows AND per-string-column word counts agree via one allgather
    counts = np.array(
        [[t.capacity for t in tables]]
        + [[c.varbytes.total_words for c in lifted[ci]]
           for ci in vb_cols], np.int64)
    if ctx.get_process_count() > 1:
        all_counts = np.asarray(multihost_utils.process_allgather(
            counts.T.copy())).reshape(-1, counts.shape[0]).T
    else:
        all_counts = counts
    cap = -(-int(all_counts[0].max()) // _ROW_QUANTUM) * _ROW_QUANTUM
    cap = max(cap, _ROW_QUANTUM)
    word_caps = {ci: _capacity(max(int(all_counts[1 + k].max()), 1))
                 for k, ci in enumerate(vb_cols)}

    sharding = row_sharding(ctx)
    world = ctx.get_world_size()

    def build(arrays, fill, pad_len=None):
        """Pad each local shard's array to a common length, stack, and
        lift to the global sharded array."""
        tgt = cap if pad_len is None else pad_len
        blocks = []
        for arr in arrays:
            a = np.asarray(arr)
            if a.shape[0] < tgt:
                pad = np.full((tgt - a.shape[0],) + a.shape[1:], fill,
                              a.dtype)
                a = np.concatenate([a, pad])
            blocks.append(a)
        local_np = np.ascontiguousarray(np.concatenate(blocks))
        if ctx.get_process_count() == 1:
            return jax.device_put(jnp.asarray(local_np), sharding)
        return jax.make_array_from_process_local_data(
            sharding, local_np, (world * tgt,) + local_np.shape[1:])

    cols = []
    for ci in range(first.column_count):
        ref = first._columns[ci]
        if ci in vb_cols:
            from ..data.strings import VarBytes

            parts = [c.varbytes for c in lifted[ci]]
            wc = word_caps[ci]
            words = build([np.asarray(jax.device_get(
                p.words[:p.total_words])) for p in parts], 0, pad_len=wc)
            starts = build([np.asarray(jax.device_get(p.starts))
                            for p in parts], 0)
            lengths = build([np.asarray(jax.device_get(p.lengths))
                             for p in parts], 0)
            max_words = max(p.max_words for p in parts)
            if ctx.get_process_count() > 1:
                max_words = int(np.asarray(multihost_utils.process_allgather(
                    np.array([max_words]))).max())
            vb = VarBytes(words, starts, lengths, max_words, world * wc,
                          shard_geom=(cap, wc))
            validity = None
            if any(t._columns[ci].validity is not None for t in tables):
                validity = build(
                    [jax.device_get(t._columns[ci].valid_mask())
                     for t in tables], False)
            cols.append(Column(vb.lengths, ref.dtype, validity, None,
                               ref.name, varbytes=vb))
            continue
        data = build([jax.device_get(t._columns[ci].data) for t in tables],
                     0)
        validity = None
        if any(t._columns[ci].validity is not None for t in tables):
            validity = build(
                [jax.device_get(t._columns[ci].valid_mask())
                 for t in tables], False)
        cols.append(Column(data, ref.dtype, validity, None, ref.name))
    emit = build([np.ones(t.capacity, np.bool_) for t in tables], False)
    return Table(cols, ctx, emit)


def _local_blocks(arr) -> list:
    """This process's shards of a row-sharded array, as numpy blocks in
    global shard order."""
    shards = sorted(arr.addressable_shards,
                    key=lambda s: (s.index[0].start or 0) if s.index else 0)
    return [np.asarray(s.data) for s in shards]


def extract_process_local(table: Table, ctx: CylonContext) -> dict:
    """Host numpy dict of THIS process's shards' live rows — the
    per-process handoff out of a distributed table (the export mirror of
    `assemble_process_local`). Each controller process of a multi-host
    mesh gets exactly its own shards, so a DDP training loop can feed
    its accelerator without any global gather (reference:
    demo_pytorch_distributed.py:1-50 feeds each rank its pycylon
    partition; python/examples/cylon_sequential_mnist.py).

    Varbytes columns decode per shard: their starts are SHARD-RELATIVE
    by invariant (strings.py shard_geom), so each addressable word block
    pairs with its row block with no global gather."""
    from ..dtypes import Type, from_word_planes

    t = table
    n_local = None
    out = {}
    for name, c in zip(t._unique_names(), t._columns):
        if c.is_varbytes:
            vb = c.varbytes
            vals = []
            for wb, sb, lb in zip(_local_blocks(vb.words),
                                  _local_blocks(vb.starts),
                                  _local_blocks(vb.lengths)):
                raw = np.ascontiguousarray(wb).view(np.uint8).tobytes()
                for s, ln in zip(sb.tolist(), lb.tolist()):
                    b = raw[4 * s: 4 * s + ln]
                    vals.append(b if c.dtype.type == Type.BINARY
                                else b.decode("utf-8", errors="replace"))
            vals = np.array(vals, dtype=object)
            n_local = vals.shape[0]
            if c.validity is not None:
                m = np.concatenate(_local_blocks(c.validity))
                vals[~m] = None
            out[name] = vals
            continue
        d = np.concatenate(_local_blocks(c.data), axis=-1)  # rows last
        if c.is_planes:  # only a table on one chip holds such a column
            d = from_word_planes(d, c.dtype.np_dtype)
        n_local = d.shape[0]
        vals = c.dictionary[d].astype(object) if c.is_string else d
        if c.validity is not None:
            m = np.concatenate(_local_blocks(c.validity))
            if vals.dtype.kind == "f":
                vals = vals.copy()
                vals[~m] = np.nan
            else:
                vals = vals.astype(object)
                vals[~m] = None
        out[name] = vals
    if t.row_mask is not None:
        em = np.concatenate(_local_blocks(t.row_mask))
    else:
        em = np.ones(n_local if n_local is not None else 0, bool)
    return {k: v[em] for k, v in out.items()}
