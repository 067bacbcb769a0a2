"""The shuffle: hash-partition + blockwise all-to-all on XLA collectives.

This is the TPU-native replacement for the reference's entire four-layer
communication stack (reference: cpp/src/cylon/net/mpi/mpi_channel.cpp:30-247
two-phase header+body MPI protocol with per-peer FSMs; net/ops/
all_to_all.cpp:26-178 queue/FIN machinery; arrow/arrow_all_to_all.cpp:24-264
per-buffer Arrow serialization). None of that machinery is translated:
inside one compiled SPMD program, `jax.lax.all_to_all` over the mesh axis IS
the transport, XLA program order replaces MPI tags/edges, and program
completion replaces the FIN handshake.

The reference's variable-length problem (its 8-int length header preceding
every body message) maps to the static-shape world as a TWO-PHASE exchange:

  phase 1 ("header"): a tiny compiled program computes the per-(src,dst)
     send-count matrix — one [W] vector per shard, gathered to the host;
  phase 2 ("body"):   a BLOCKWISE exchange. The host picks a pow2 block
     size B (capped at MAX_BLOCK) and a round count K with K*B >= the
     largest single (src,dst) transfer; the compiled program bucket-sorts
     rows by target once, then loops K rounds, each round moving one [W,B]
     block per payload leaf through `all_to_all` and compacting received
     rows into a [cap_out] output at running per-source offsets.
     (Where the pairs are level enough the body is ONE round with no
     receive scatter, the "padded" layout, in a block on
     `util.capacity`'s grid: see exchange() and `_padded_route`.)

The blockwise loop is the TPU analog of the reference's incremental
buffer-at-a-time streaming (arrow_all_to_all.cpp:83-135): peak comm-buffer
memory is bounded by W*MAX_BLOCK rows per leaf regardless of skew, and the
output capacity tracks the worst RECEIVE TOTAL over shards
(pow2(max_t sum_s C[s,t])) instead of W*pow2(max C[s,t]) — up to W× smaller
when one (src,dst) pair is hot. Receivers place each source's rows
contiguously, so shuffle output is COMPACT (emit = leading prefix).

Rows whose emit mask is False (table padding, filtered rows) are dropped in
transit — the shuffle doubles as a compaction step.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from jax import shard_map

from ..context import CylonContext
from ..ops import hash as _hash
from ..ops import tpu_kernels as _tpuk
from ..resilience import inject as _inject
from ..resilience import retry as _retry
from ..telemetry import counted_cache, counter as _counter, \
    host_fetch as _host_fetch, span as _span
from ..telemetry import skew as _skew
from ..util import capacity as _capacity, pow2 as _pow2, \
    pow2_floor as _pow2_floor

# Upper bound on the per-round block (rows per (src,dst) pair per round)
# where the memory-pool budget is UNKNOWN (stats unavailable): comm/
# scratch memory per leaf is then 2*W*MAX_BLOCK rows. Where the budget is
# known (comm_budget_bytes, real HBM stats on TPU) it alone sizes the
# block (_padded_route). Skew beyond the budgeted block degrades into
# more rounds, not bigger buffers.
# (1<<16 was measured 64 rounds = 5x slower than one round at 4M rows on
# a 1-wide v5e mesh — round count, not block memory, was the binding
# constraint.)
MAX_BLOCK = 1 << 22

# Chunk-count ceiling for the overlapped (chunked) padded exchange: the
# chunk block is floored so one exchange never fans out into more than
# this many pipeline programs — past ~64 the per-dispatch fixed cost
# dwarfs any remaining overlap win (the 1<<16 MAX_BLOCK measurement
# above is the same lesson: round count, not block memory, binds).
MAX_CHUNKS = 64


def _shard_map_for(part, kernel, mesh, in_specs, out_specs):
    """jitted shard_map builder for the padded exchange programs: the
    sort path keeps the varying-mesh-axes replication check (the exact
    pre-kernel program); the Pallas partition path disables it —
    shard_map has no replication rule for pallas_call, and the kernel
    is purely per-shard (no collectives inside)."""
    if part == "sort":
        return jax.jit(shard_map(kernel, mesh=mesh, in_specs=in_specs,
                                 out_specs=out_specs))
    return jax.jit(shard_map(kernel, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False))


def replicated_gather(x, axis: str, world: int):
    """Per-shard [..] value → [world, ..] matrix REPLICATED on every shard.

    psum of a one-hot row scatter rather than `all_gather`: shard_map's
    varying-mesh-axes check can statically prove a psum result is
    replicated (out_specs=P() legal), which it cannot for all_gather.
    Replication matters on multi-host meshes — the host fetch of a
    *sharded* count array would not be addressable from other controller
    processes."""
    row = jax.lax.axis_index(axis)
    mat = jnp.zeros((world,) + x.shape, x.dtype).at[row].set(x)
    return jax.lax.psum(mat, axis)


def _payload_nbytes(payload) -> int:
    """Host-computable byte size of a payload pytree (shape × itemsize;
    no device sync) — the ``bytes_moved`` span attribute and the
    ``cylon_shuffle_bytes_total`` counter feed."""
    return sum(int(np.dtype(x.dtype).itemsize) * int(np.prod(x.shape))
               for x in jax.tree.leaves(payload))


def _record_exchange(rows: int, nbytes: int, programs: int = 1) -> None:
    """Metrics for one exchange dispatch: payload bytes through the
    collective, live rows moved, compiled-program launches."""
    _counter("cylon_shuffle_bytes_total").inc(nbytes)
    _counter("cylon_rows_exchanged_total").inc(rows)
    _counter("cylon_collective_launches_total").inc(programs)


def _launch_exchange(fn):
    """One exchange program dispatch under the resilience policy: the
    chaos injector's ``exchange`` choke point fires first (so every
    retry attempt is one arrival — a persistent fault plan keeps
    failing), then the dispatch runs under bounded retry-with-backoff.
    Re-dispatching is safe: the compiled program is a pure function of
    its device inputs, and a faulted kernel-factory build is not
    cached, so retries rebuild it. Runs INSIDE the exchange span, so a
    recovered stage carries the ``retries`` attr EXPLAIN ANALYZE
    renders as ``[RETRY×n]``."""
    def attempt():
        _inject.fire("exchange")
        return fn()

    return _retry.run_retryable("exchange", attempt)


def _payload_row_bytes(payload) -> int:
    """Host-computable bytes per ROW of a payload pytree — the
    per-shard byte-histogram feed (skew.observe_exchange)."""
    return sum(int(np.dtype(x.dtype).itemsize) * int(np.prod(x.shape[1:]))
               for x in jax.tree.leaves(payload))


# beyond this world size, per-target compare-sum passes cost more than
# one scatter-class segment_sum
_COUNT_COMPARE_MAX_W = 64


def _target_counts(t, world):
    """counts[w] = #rows with target w. Compare-sum for small W (W cheap
    vector passes; segment_sum's scatter costs ~15-30 ns/element on TPU
    and was measured at ~0.3 s per 16M-row count phase)."""
    if world <= _COUNT_COMPARE_MAX_W:
        return jnp.stack(
            [(t == w).sum(dtype=jnp.int32) for w in range(world)])
    return jax.ops.segment_sum(jnp.ones(t.shape[0], jnp.int32), t,
                               num_segments=world + 1)[:world]


@counted_cache
def _count_fn(mesh):
    """Send-count matrix counts[s, t] = live rows shard s sends to shard t,
    REPLICATED on every shard (an in-program all_gather) so the host fetch
    is valid on every controller process — a sharded output would not be
    addressable from the other hosts of a multi-host mesh.

    The moral equivalent of the reference's header phase
    (mpi_channel.cpp:211-225 sendHeader)."""
    axis = mesh.axis_names[0]
    world = mesh.devices.size
    spec = P(axis)

    def kernel(targets, emit):
        t = jnp.where(emit, targets.astype(jnp.int32), world)
        return replicated_gather(_target_counts(t, world), axis, world)

    return jax.jit(shard_map(kernel, mesh=mesh, in_specs=(spec, spec),
                             out_specs=P()))


def _to_varying_fn(axis):  # cylint: disable=collectives/uncataloged-factory — returns a plain host callable, not a jitted program
    return lambda x: jax.lax.pcast(x, axis, to="varying")


def _bucket_sort(payload, targets, emit, world):
    """Stable bucket sort by target: ONE fused device sort carries every
    1-D payload leaf as a sort OPERAND (the reference's per-dtype split
    kernels, arrow_kernels.cpp:24-134, collapse into this sort). Payload
    operands ride the sort at near-memcpy bandwidth; a per-leaf
    take(perm) gather costs ~15-30 ns/element on TPU and was measured
    dominating the whole exchange. Non-1-D leaves (rare) fall back to
    the gather. Returns (sorted leaves, counts_out, start offsets)."""
    n = targets.shape[0]
    t = jnp.where(emit, targets.astype(jnp.int32), world)
    leaves, treedef = jax.tree.flatten(payload)
    ride = [x.ndim == 1 for x in leaves]
    ops = tuple(x for x, r in zip(leaves, ride) if r)
    need_perm = not all(ride)
    # stability is load-bearing: the varbytes word/row exchanges must
    # keep matching within-source order (previously via an iota tiebreak)
    if need_perm:
        iota = jnp.arange(n, dtype=jnp.int32)
        res = jax.lax.sort((t,) + ops + (iota,), num_keys=1,
                           is_stable=True)
        perm = res[-1]
        sorted_ops = list(res[1:-1])
    else:
        res = jax.lax.sort((t,) + ops, num_keys=1, is_stable=True)
        sorted_ops = list(res[1:])
    out_leaves = []
    k = 0
    for x, r in zip(leaves, ride):
        if r:
            out_leaves.append(sorted_ops[k])
            k += 1
        else:
            out_leaves.append(jnp.take(x, perm, axis=0))
    counts_out = _target_counts(t, world)
    start = jnp.cumsum(counts_out) - counts_out
    return jax.tree.unflatten(treedef, out_leaves), counts_out, start


def _send_block(xs, start, o, block, world):
    """[world, block] send stack via ONE contiguous dynamic slice per
    target — rows are target-bucket-sorted, so sends are slices, never
    gathers (XLA gathers cost ~15-30 ns/element; slices are memcpys).
    ``xs`` must be pre-padded by ``block`` so slices stay in range;
    over-read rows belong to other targets and are dropped receive-side."""
    outs = []
    for t in range(world):
        pos = jnp.clip(start[t] + o, 0, xs.shape[0] - block)
        outs.append(jax.lax.dynamic_slice_in_dim(xs, pos, block, axis=0))
    return jnp.stack(outs)


# ---------------------------------------------------------------------------
# the fused partition kernel (ROADMAP item 2 close-out, SURVEY §7): the
# padded-mode partition — a stable bucket sort by target — is the one
# spot the survey reserves Pallas for. `_partition_path` routes it by
# what it observes, no knob: the two-pass histogram+scatter kernel on a
# TPU (up to _PARTITION_MAX_WORLD targets — past that the scatter's
# per-bucket passes cost more than the sort), the XLA stable sort
# everywhere else (the path string is part of every factory cache key).
# Both paths return the identical (sorted_leaves, counts_out, start)
# triple, so everything downstream — chunk pipeline, skew attrs,
# ledger, admission — is partition-path-oblivious.
# ---------------------------------------------------------------------------

# beyond this world size the scatter pass's per-bucket input streaming
# (~world+2 elementwise-priced passes) loses to the one stable sort
_PARTITION_MAX_WORLD = 16


def _partition_eligible(payload) -> bool:
    """Every leaf must split into u32 legs: 1-D/2-D, 1/2/4/8-byte."""
    return all(
        x.ndim in (1, 2) and np.dtype(x.dtype).itemsize in (1, 2, 4, 8)
        for x in jax.tree.leaves(payload))


def _partition_path(mesh, world: int, payload) -> str:
    """Resolve the partition path for one exchange dispatch, from
    platform, world and payload alone: "sort" or "pallas" (the compiled
    kernel). The result keys the exchange factory caches, so a program
    built for one path is never reused for the other. A third spelling,
    "interp" (the kernel under the interpreter), is only ever passed by
    a test that patches this function."""
    # world+1 buckets (dead rows included) must fit one histogram lane
    # row
    if world < 2 or world + 1 > _tpuk.LANES \
            or not _partition_eligible(payload):
        return "sort"
    on_tpu = mesh.devices.flat[0].platform == "tpu"
    return "pallas" if on_tpu and world <= _PARTITION_MAX_WORLD \
        else "sort"


def partition_path_label(part: str) -> str:
    """The PUBLIC spelling of a partition path: "interp" is the
    interpreter form of the kernel — one label, ``pallas``."""
    return "sort" if part == "sort" else "pallas"


def _payload_legs(payload) -> int:
    """The u32 legs `_leg_split` cuts a payload's leaves into: one a
    column of at most 4 bytes, two an 8-byte one."""
    return sum(int(np.prod(x.shape[1:]))
               * (2 if np.dtype(x.dtype).itemsize == 8 else 1)
               for x in jax.tree.leaves(payload))


def _record_partition(sp, world: int, *sides) -> None:
    """Observability for the partition-path decisions of one dispatch
    (one ``(part, payload)`` per exchange, two for a fused pair): the
    cylon_partition_path_total counter per side, the grid steps a
    shard's `partition_scatter` takes over the side
    (cylon_partition_steps_total: 0 where the sort partitions), and ONE
    partition_path span attr EXPLAIN ANALYZE folds per node ("mixed"
    when a pair's sides differ)."""
    paths = [partition_path_label(p) for p, _ in sides]
    sp.set(partition_path=paths[0] if len(set(paths)) == 1 else "mixed")
    for path, (_, payload) in zip(paths, sides):
        _counter("cylon_partition_path_total", {"path": path}).inc()
        steps = 0
        if path == "pallas":
            rows = jax.tree.leaves(payload)[0].shape[0] // world
            steps = _tpuk.partition_scatter_steps(
                rows, world + 1, _payload_legs(payload))
        _counter("cylon_partition_steps_total").inc(steps)


def _leg_split(x):
    """One payload leaf → (u32 (n,) legs, join(legs) -> leaf).

    The partition kernel moves 32-bit lanes; wider dtypes ride as
    word legs (the varbytes trick applied to every column), narrower
    ones widen value-exactly, 2-D leaves split per column. Round trips
    are bit-exact: bitcasts for 4/8-byte, value casts for 1/2-byte
    (lossless by range).

    A 2-D leaf here has its rows FIRST (``[n, k]``), as everywhere in
    this module. A 64-bit column's word planes are ``[2, n]``, rows LAST
    (data/column.py, THE AXIS CONVENTION): they come here as two 1-D
    leaves, never as that array."""
    if x.ndim == 2:
        subs = [_leg_split(x[:, j]) for j in range(x.shape[1])]
        legs = [leg for sub_legs, _ in subs for leg in sub_legs]

        def join2d(ls, subs=subs):
            outs, i = [], 0
            for sub_legs, sub_join in subs:
                outs.append(sub_join(ls[i:i + len(sub_legs)]))
                i += len(sub_legs)
            return jnp.stack(outs, axis=1)

        return legs, join2d
    dt = x.dtype
    size = np.dtype(dt).itemsize
    if size == 4:
        if dt == jnp.uint32:
            return [x], lambda ls: ls[0]
        return ([jax.lax.bitcast_convert_type(x, jnp.uint32)],
                lambda ls: jax.lax.bitcast_convert_type(ls[0], dt))
    if size == 8:
        pair = jax.lax.bitcast_convert_type(x, jnp.uint32)  # (n, 2)
        return ([pair[:, 0], pair[:, 1]],
                lambda ls: jax.lax.bitcast_convert_type(
                    jnp.stack(ls, axis=1), dt))
    if dt == jnp.bool_:
        return ([x.astype(jnp.uint32)],
                lambda ls: ls[0].astype(jnp.bool_))
    narrow = jnp.uint16 if size == 2 else jnp.uint8
    return ([jax.lax.bitcast_convert_type(x, narrow).astype(jnp.uint32)],
            lambda ls: jax.lax.bitcast_convert_type(
                ls[0].astype(narrow), dt))


def _kernel_partition(payload, targets, emit, world, interpret: bool):
    """The Pallas twin of `_bucket_sort`: identical contract — stable
    by target, dead rows (emit False) keyed ``world`` to the tail,
    (sorted leaves, counts_out, start) — via one histogram pass and one
    counting-scatter pass instead of an O(n log n) multi-operand sort.
    Bit-for-bit the same permutation: the scatter's sequential
    bucket-major appends ARE the stable sort order."""
    t = jnp.where(emit, targets.astype(jnp.int32), world)
    leaves, treedef = jax.tree.flatten(payload)
    splits = [_leg_split(x) for x in leaves]
    flat_legs = [leg for legs, _ in splits for leg in legs]
    hist = _tpuk.partition_hist(t, world + 1, interpret=interpret)
    counts_out = hist[:, :world].sum(axis=0, dtype=jnp.int32)
    start = jnp.cumsum(counts_out) - counts_out
    outs = _tpuk.partition_scatter(t, flat_legs, world + 1,
                                   interpret=interpret)
    out_leaves, i = [], 0
    for legs, join in splits:
        out_leaves.append(join(list(outs[i:i + len(legs)])))
        i += len(legs)
    return jax.tree.unflatten(treedef, out_leaves), counts_out, start


def _padded_emit(counts_in, block: int):
    """The padded layout's emit mask: source s's live rows are the first
    ``counts_in[s]`` of its ``block`` slots. A compare against a
    broadcast, not ``pos % block`` / ``pos // block`` over every slot: a
    block on `util.capacity`'s grid is no power of two, and a division
    by one over a chip's tens of millions of positions is no shift."""
    biota = jnp.arange(block, dtype=jnp.int32)
    return (biota[None, :] < counts_in[:, None]).reshape(-1)


def _padded_partition(axis, world, block, payload, targets, emit,
                      part: str = "sort"):
    """The shared partition prefix of BOTH padded-mode bodies (the
    single-shot program and the chunked pipeline): stable partition by
    target (`part` picks the XLA bucket sort or the fused Pallas
    kernel — bit-identical layouts), device counts exchange, per-target
    start offsets and the final emit mask. ONE copy on purpose — the
    chunked path's bit-identity with the single-shot program is
    structural, not two texts kept in sync."""
    if part == "sort":
        sorted_leaves, counts_out, start = _bucket_sort(
            payload, targets, emit, world)
    else:
        sorted_leaves, counts_out, start = _kernel_partition(
            payload, targets, emit, world, interpret=part == "interp")
    counts_in = jax.lax.all_to_all(counts_out, axis, split_axis=0,
                                   concat_axis=0, tiled=True)
    return sorted_leaves, counts_in, start, _padded_emit(counts_in, block)


def _padded_body(axis, world, block, payload, targets, emit,
                 part: str = "sort"):
    """The padded-mode exchange as a pure function of per-shard values —
    shared by the single and the PAIR program builders. ``part`` picks
    the partition path."""
    cap_out = world * block
    sorted_leaves, counts_in, start, new_emit = _padded_partition(
        axis, world, block, payload, targets, emit, part)

    def one(xs):
        pad = jnp.zeros((block,) + xs.shape[1:], xs.dtype)
        xp = jnp.concatenate([xs, pad])
        send = _send_block(xp, start, 0, block, world)
        recv = jax.lax.all_to_all(send, axis, split_axis=0,
                                  concat_axis=0, tiled=False)
        return recv.reshape((cap_out,) + xs.shape[1:])

    outs = jax.tree.map(one, sorted_leaves)
    return outs, new_emit, counts_in


@counted_cache
def _exchange_padded_fn(mesh, block: int, part: str = "sort"):
    """Scatter-free single-shot exchange: every (src,dst) pair moves ONE
    [block] slice and lands at the STATIC slot dst_out[src*block:...] —
    no receive scatter at all. Output is PADDED per source (emit mask
    marks each source's live prefix), capacity world*block; the host
    routes here when that padding is acceptable (see exchange()).
    ``part`` (the partition path — see _partition_path) is part of the
    cache key: one path's program is never reused for the other."""
    axis = mesh.axis_names[0]
    world = mesh.devices.size
    spec = P(axis)

    def kernel(payload, targets, emit):
        return _padded_body(axis, world, block, payload, targets, emit,
                            part)

    return _shard_map_for(part, kernel, mesh, (spec, spec, spec),
                          spec)


# ---------------------------------------------------------------------------
# the chunked padded exchange: the padded payload splits into blocks
# whose send and receive stacks fit the comm budget, one program a
# chunk, each landing its rows in a donated accumulator — peak
# comm-buffer HBM per leaf is 2*W*chunk_block where the single-shot
# program holds 2*W*block. It runs ONLY where the budget refuses the
# single-shot stacks (`_chunk_plan`): on four v5e chips the wire is
# 3-4.5 ms a query and there is nothing for a pipeline to hide, while
# every chunk program copies its whole accumulator (0.97 ms a leaf a
# program whatever the chunk's rows: PERF.md section 6, PR 44): 64 MiB
# chunks cost 4.6 / 20.0 / 16.4 ms a query in the three four-chip
# cells, the chunk programs' whole time (PR 48; the pipeline was built
# on a CPU in PR 13 to overlap the wire with the landing). Chunk geometry
# derives from the count matrix and the budget the host already holds
# for the route — zero new host syncs.
# ---------------------------------------------------------------------------


def _budget_block_cap(block: int, world: int, bytes_per_row: int, budget,
                      buffer_factor: int) -> int:
    """``block`` as it is where buffer_factor * world * block * row bytes
    (``buffer_factor`` is 4 a table the program holds) fits the comm
    budget, or where no budget is known; else halved until it fits,
    never under 1,024 rows — the Allocator analog feeding receive
    buffers from the pool (arrow_all_to_all.cpp:234-247). Only a block
    that has to be cut is pow2-floored first (one chunk or round block
    an octave): a padded block on `util.capacity`'s grid that fits must
    come back whole, or the route would refuse the very block it sized
    (`_padded_route`) and the plan would chunk it (`_chunk_plan`). ONE
    copy: the route's block cap and the chunk block are the same
    arithmetic."""
    def fits(b):
        return buffer_factor * world * b * bytes_per_row <= budget

    if not budget or fits(block):
        return block
    block = _pow2_floor(block)
    while block > 1024 and not fits(block):
        block //= 2
    return block


def _chunks_of(block: int, cb: int):
    """(chunk_block, chunks) for a chunk block of about ``cb`` rows,
    raised so the pipeline never exceeds MAX_CHUNKS programs; a chunk
    block that covers the block is the single-shot program. The chunk
    block need not divide the block (one on `util.capacity`'s grid is no
    power of two): the last chunk is moved back to end with the block
    (`_chunk_offset`)."""
    cb = max(cb, _pow2_floor(max(block // MAX_CHUNKS, 1)))
    if cb >= block:
        return block, 1
    return cb, -(-block // cb)


def _chunk_plan(block: int, world: int, bytes_per_row: int, budget,
                buffer_factor: int = 4):
    """(chunk_block, chunks) for a padded exchange with per-(src,dst)
    ``block``; chunks == 1 means single-shot: whenever the single-shot
    stacks fit ``budget`` (`_padded_route`'s own check, so every payload
    it admitted on that budget) or no budget is known. Else the chunk
    block is the largest power of two whose stacks fit. Pure host
    arithmetic over already-known geometry; no knob."""
    return _chunks_of(block, _budget_block_cap(
        block, world, max(int(bytes_per_row), 1), budget, buffer_factor))


def _chunk_offset(k, block: int, cb: int):
    """Where chunk ``k`` starts inside the block: k * cb, but a last
    chunk that would pass the block's end starts at block - cb and
    moves some of the rows before it a second time, the same rows to the
    same slots. So every chunk is a whole [cb] slice whatever the block
    (a grid block is s * 2^e, s in [17, 32]: no power of two above 2^e
    divides it) and none needs a scatter to drop what would wrap."""
    return jnp.minimum(k * cb, block - cb)


def _chunk_write(axis, world, block, cb, xs, start, out, o):
    """Move ONE chunk of one leaf: slice rows [start[t]+o, +cb) per
    target (contiguous — the payload is bucket-sorted), all_to_all,
    land source s's rows at the STATIC padded slot s*block + o by a
    memcpy-class dynamic_update_slice (``o`` is a `_chunk_offset`, so
    o + cb <= block)."""
    send = _send_block(xs, start, o, cb, world)
    recv = jax.lax.all_to_all(send, axis, split_axis=0,
                              concat_axis=0, tiled=False)
    out2d = out.reshape((world, block) + xs.shape[1:])
    out2d = jax.lax.dynamic_update_slice_in_dim(out2d, recv, o, axis=1)
    return out2d.reshape((world * block,) + xs.shape[1:])


def _partition_body(axis, world, block, cb, payload, targets, emit,
                    first_chunk: bool, part: str = "sort"):
    """The partition phase of the chunked exchange as a pure per-shard
    function: stable partition (``part``-routed), device counts
    exchange, chunk-padded sorted leaves, zeroed output accumulators
    and the final emit mask — everything the per-chunk programs
    consume. ``first_chunk`` folds chunk 0's exchange+compaction in
    (the fused form)."""
    cap_out = world * block
    sorted_leaves, counts_in, start, new_emit = _padded_partition(
        axis, world, block, payload, targets, emit, part)
    padded = jax.tree.map(
        lambda x: jnp.concatenate(
            [x, jnp.zeros((cb,) + x.shape[1:], x.dtype)]),
        sorted_leaves)
    _to_varying = _to_varying_fn(axis)
    out0 = jax.tree.map(
        lambda x: _to_varying(jnp.zeros((cap_out,) + x.shape[1:],
                                        x.dtype)), payload)
    if first_chunk:
        out0 = jax.tree.map(
            lambda xs, ob: _chunk_write(axis, world, block, cb, xs,
                                        start, ob, 0),
            padded, out0)
    return padded, start, counts_in, new_emit, out0


@counted_cache
def _exchange_partition_fn(mesh, block: int, chunk_block: int,
                           part: str = "sort"):
    """The partition program WITHOUT chunk 0: what `_dispatch_chunked`
    rebuilds the pipeline state with after a faulted dispatch consumed
    the donated accumulator. It leaves the accumulators zeroed, so the
    rebuild replays every landed chunk through the one chunk program."""
    axis = mesh.axis_names[0]
    world = mesh.devices.size
    spec = P(axis)

    def kernel(payload, targets, emit):
        return _partition_body(axis, world, block, chunk_block,
                               payload, targets, emit,
                               first_chunk=False, part=part)

    return _shard_map_for(part, kernel, mesh, (spec,) * 3, spec)


@counted_cache
def _exchange_chunk_first_fn(mesh, block: int, chunk_block: int,
                             part: str = "sort"):
    """FUSED partition+exchange program — the single-table analog of
    the `_exchange_padded_pair_fn` trick (two stages in ONE compiled
    program, one dispatch where two would do): the partition body with
    chunk 0's all_to_all+compaction folded in, so XLA schedules the
    bucket sort, the counts exchange and the first payload collective
    together and `cylon_collective_launches_total` drops by one per
    chunked exchange."""
    axis = mesh.axis_names[0]
    world = mesh.devices.size
    spec = P(axis)

    def kernel(payload, targets, emit):
        return _partition_body(axis, world, block, chunk_block,
                               payload, targets, emit,
                               first_chunk=True, part=part)

    return _shard_map_for(part, kernel, mesh, (spec,) * 3, spec)


@counted_cache
def _exchange_chunk_fn(mesh, block: int, chunk_block: int):
    """One pipeline chunk: slice, all_to_all, compact at the static
    padded slots. The chunk index ``k`` rides as a DEVICE operand
    (replicated scalar), so every chunk of every exchange with this
    geometry shares ONE compiled program — chunk count never enters a
    cache key. The output accumulator is donated on TPU: the pipeline's
    live buffers are the in-flight chunk's send/recv stacks plus one
    accumulator (the double buffer), not one fresh [cap_out] copy per
    chunk. (Donation is a no-op on host backends, which do not
    implement it.)"""
    axis = mesh.axis_names[0]
    world = mesh.devices.size
    spec = P(axis)

    def kernel(padded, start, out, k):
        o = _chunk_offset(k.astype(jnp.int32), block, chunk_block)
        return jax.tree.map(
            lambda xs, ob: _chunk_write(axis, world, block, chunk_block,
                                        xs, start, ob, o),
            padded, out)

    donate = (2,) if mesh.devices.flat[0].platform == "tpu" else ()
    return jax.jit(shard_map(kernel, mesh=mesh,
                             in_specs=(spec, spec, spec, P()),
                             out_specs=spec),
                   donate_argnums=donate)


def _dispatch_chunked(ctx: CylonContext, block: int, cb: int,
                      chunks: int, payload, targets, emit,
                      part: str = "sort"):
    """Launch the chunked pipeline: one partition program with chunk 0
    folded in, then one chunk program per remaining chunk — dispatched
    back to back WITHOUT waiting, so chunk N+1's all_to_all runs while
    chunk N's received rows are compacted (and while the consumer's
    local kernels on already-landed rows queue behind them). Every
    dispatch runs under the per-chunk retry policy; re-dispatch is
    idempotent because the chaos injector fires BEFORE the program
    consumes its (donated) buffers. Returns (outs, new_emit,
    counts_in); ``chunks`` programs were launched."""
    mesh = ctx.mesh
    padded, start, counts_in, new_emit, outs = _launch_exchange(
        lambda: _exchange_chunk_first_fn(mesh, block, cb, part)(
            payload, targets, emit))
    step = _exchange_chunk_fn(mesh, block, cb)
    for k in range(1, chunks):
        karr = np.int32(k)

        def attempt(karr=karr, k=k):
            # donation caveat: a faulted dispatch that already consumed
            # the donated accumulator (possible only on TPU — donation
            # is a no-op on host backends) would make a plain
            # re-dispatch fail hard on a deleted buffer; a retry
            # attempt therefore rebuilds the pipeline state from the
            # (never-donated) payload and replays the landed chunks
            # before re-dispatching — idempotent recovery either way
            nonlocal padded, start, counts_in, new_emit, outs
            leaf = next(iter(jax.tree.leaves(outs)), None)
            if leaf is not None and \
                    getattr(leaf, "is_deleted", lambda: False)():
                padded, start, counts_in, new_emit, outs = \
                    _exchange_partition_fn(mesh, block, cb, part)(
                        payload, targets, emit)
                for j in range(k):
                    outs = step(padded, start, outs, np.int32(j))
            return step(padded, start, outs, karr)

        outs = _launch_exchange(attempt)
    return outs, new_emit, counts_in


def _record_chunked(sp, chunks: int, cb: int) -> None:
    """Chunk-pipeline observability: per-exchange span attrs plus the
    cylon_exchange_chunks_total counter. ``overlap_ratio`` is
    (chunks-1)/chunks — the fraction of the pipeline's programs, one a
    chunk, issued while earlier chunk work was still in flight."""
    sp.set(chunks=chunks, chunk_block=cb,
           overlap_ratio=round((chunks - 1) / chunks, 4))
    _counter("cylon_exchange_chunks_total").inc(chunks)


@counted_cache
def _exchange_padded_pair_fn(mesh, block1: int, block2: int,
                             part1: str = "sort", part2: str = "sort"):
    """BOTH sides of a two-table shuffle in ONE compiled program — one
    dispatch instead of two, and XLA schedules the two bucket sorts and
    collective pairs together (the distributed join's composition cost
    is dominated by fixed per-program cost)."""
    axis = mesh.axis_names[0]
    world = mesh.devices.size
    spec = P(axis)

    def kernel(p1, t1, e1, p2, t2, e2):
        o1 = _padded_body(axis, world, block1, p1, t1, e1, part1)
        o2 = _padded_body(axis, world, block2, p2, t2, e2, part2)
        return o1 + o2

    # any pallas side forces the unchecked shard_map build (a mixed
    # sort+pallas pair still contains a pallas_call)
    part = part1 if part1 != "sort" else part2
    return _shard_map_for(part, kernel, mesh, (spec,) * 6, spec)


def exchange_pair(payload1, targets1, emit1, counts1,
                  payload2, targets2, emit2, counts2, ctx: CylonContext):
    """Two shuffles in one program when both route to padded mode
    (the uniform-hash common case); otherwise two sequential
    exchanges. Returns (result1, result2) where each result is the
    exchange() 4-tuple."""
    world = ctx.get_world_size()
    with _span("shuffle.route", world=world, tables=2) as rsp:
        budget = ctx.memory_pool.comm_budget_bytes()
        # buffer_factor=8: the pair program holds BOTH tables' comm
        # buffers
        ok1, b1, _mb1 = _padded_route(counts1, payload1, world, budget,
                                      buffer_factor=8)
        ok2, b2, _mb2 = _padded_route(counts2, payload2, world, budget,
                                      buffer_factor=8)
        # the pair program holds BOTH tables' stacks: one program where
        # each side's fit the half of the budget its route was held to
        # (on four v5e chips the fused program beat a chunked exchange a
        # side by 4.6 ms a query, 20 under skew: PERF.md section 6, PR 48); a
        # side that must chunk, or cannot go padded, sends both through
        # exchange()
        chunks = max(
            _chunk_plan(b1, world, _payload_row_bytes(payload1), budget,
                        buffer_factor=8)[1],
            _chunk_plan(b2, world, _payload_row_bytes(payload2), budget,
                        buffer_factor=8)[1]) \
            if ok1 and ok2 else 1
        fused = ok1 and ok2 and chunks == 1
        rsp.set(mode="pair" if fused else "each", block=max(b1, b2),
                chunks=chunks)
        if fused:
            seq = ctx.get_next_sequence()
            rows = (int(counts1.sum()) if counts1 is not None else 0) \
                + (int(counts2.sum()) if counts2 is not None else 0)
            nbytes = _payload_nbytes(payload1) + _payload_nbytes(payload2)
            # per-side histograms carry each table's own row width; the
            # span attributes carry the COMBINED per-destination totals
            # (what each shard actually absorbs from the fused program)
            _skew.observe_exchange(counts1, _payload_row_bytes(payload1),
                                   world * world * b1)
            _skew.observe_exchange(counts2, _payload_row_bytes(payload2),
                                   world * world * b2)
            pair_stats = _skew.SkewStats.from_counts(
                np.asarray(counts1) + np.asarray(counts2)) \
                if counts1 is not None and counts2 is not None else None
            part1 = _partition_path(ctx.mesh, world, payload1)
            part2 = _partition_path(ctx.mesh, world, payload2)
    if not fused:
        return (exchange(payload1, targets1, emit1, ctx, counts=counts1),
                exchange(payload2, targets2, emit2, ctx, counts=counts2))
    with _span("shuffle.exchange_pair", seq, world=world,
               mode="padded", rows=rows, bytes_moved=nbytes,
               block=max(b1, b2)) as sp:
        if pair_stats is not None:
            sp.set(**pair_stats.span_attrs())
        # one decision per side; the fused program partitions both
        _record_partition(sp, world, (part1, payload1),
                          (part2, payload2))
        res = _launch_exchange(
            lambda: _exchange_padded_pair_fn(ctx.mesh, b1, b2,
                                             part1, part2)(
                payload1, targets1, emit1, payload2, targets2,
                emit2))
    _record_exchange(rows, nbytes)
    out1, emit1_o, ci1, out2, emit2_o, ci2 = res
    return ((out1, emit1_o, world * b1,
             {"mode": "padded", "block": b1, "counts_in": ci1}),
            (out2, emit2_o, world * b2,
             {"mode": "padded", "block": b2, "counts_in": ci2}))


@counted_cache
def _exchange_fn(mesh, block: int, rounds: int, cap_out: int):
    """The blockwise body phase (skew fallback): K rounds, each moving
    one [W,B] block per leaf and compacting received rows at running
    per-source offsets — bounded comm memory under any skew."""
    axis = mesh.axis_names[0]
    world = mesh.devices.size
    spec = P(axis)

    def kernel(payload, targets, emit):
        sorted_leaves, counts_out, start = _bucket_sort(
            payload, targets, emit, world)
        # the header exchange, on device: each shard learns how many rows
        # every source will send it, and writes source s's rows at offset
        # S[s] — arrivals are contiguous per source, output is compact
        counts_in = jax.lax.all_to_all(counts_out, axis, split_axis=0,
                                       concat_axis=0, tiled=True)
        S = jnp.cumsum(counts_in) - counts_in
        total_in = counts_in.sum()

        biota = jnp.arange(block, dtype=jnp.int32)[None, :]      # [1,B]
        padded = jax.tree.map(
            lambda x: jnp.concatenate(
                [x, jnp.zeros((block,) + x.shape[1:], x.dtype)]),
            sorted_leaves)
        _to_varying = _to_varying_fn(axis)
        out0 = jax.tree.map(
            lambda x: _to_varying(jnp.zeros((cap_out,) + x.shape[1:],
                                            x.dtype)), payload)

        def round_body(k, outs):
            o = k * block
            # receive slots: S[s] + [o, o+B), dropped past counts_in[s]
            pos = S[:, None] + o + biota
            pvalid = (o + biota) < counts_in[:, None]
            psafe = jnp.where(pvalid, pos, cap_out).reshape(-1)

            def one(xs, out):
                send = _send_block(xs, start, o, block, world)
                recv = jax.lax.all_to_all(send, axis, split_axis=0,
                                          concat_axis=0, tiled=False)
                flat = recv.reshape((world * block,) + xs.shape[1:])
                return out.at[psafe].set(flat, mode="drop")

            return jax.tree.map(one, padded, outs)

        outs = jax.lax.fori_loop(0, rounds, round_body, out0) if rounds > 1 \
            else round_body(0, out0)
        new_emit = jnp.arange(cap_out, dtype=jnp.int32) < total_in
        counts_in_out = counts_in
        return outs, new_emit, counts_in_out

    return jax.jit(shard_map(kernel, mesh=mesh, in_specs=(spec, spec, spec),
                             out_specs=spec))


# padded-mode acceptance: worst-case capacity blowup over the compact
# layout before the blockwise (skew) path takes over. The padded block
# is the worst pair's rows on `util.capacity`'s grid (at most 6.25%
# over), so uniform hash placement gives W*block <= ~1.07*recv_max,
# well inside 2*pow2(recv_max); one hot (src,dst) pair blows past 2 and
# routes to the blockwise path.
PADDED_WASTE_FACTOR = 2


@counted_cache
def _count2_fn(mesh):
    """Both sides' send-count matrices in ONE compiled program (one
    host sync for a two-table shuffle instead of two)."""
    axis = mesh.axis_names[0]
    world = mesh.devices.size
    spec = P(axis)

    def kernel(t1, e1, t2, e2):
        a = jnp.where(e1, t1.astype(jnp.int32), world)
        b = jnp.where(e2, t2.astype(jnp.int32), world)
        both = jnp.stack([_target_counts(a, world),
                          _target_counts(b, world)])
        return replicated_gather(both, axis, world)

    return jax.jit(shard_map(kernel, mesh=mesh, in_specs=(spec,) * 4,
                             out_specs=P()))


# ---------------------------------------------------------------------------
# hot-key salting (adaptive execution, ROADMAP item 1): under a Zipfian
# key column every row of the hot key hashes to ONE destination, so the
# receiving shard's local kernel does most of the query's work however
# fast the exchange itself runs. The salted variant of the partition
# decides, ON DEVICE and from the true global count matrix, which
# destinations are hot (receive total beyond the warn factor x the
# mean), and spreads exactly those destinations' rows across
# CYLON_SALT_FACTOR consecutive shards — the salt is a per-row value
# folded into the routing (fmix32(iota) % S), never into the payload,
# so receive-side rows are already "un-salted": downstream kernels see
# the original keys, and the caller withholds the placement witness
# (salted placement is positional, not key-hash). One program, one
# host sync: the salted targets, the salted count matrix AND the raw
# (pre-mitigation) matrix come back together — skew observability and
# the warehouse's salting decision read the RAW skew, so the decision
# never oscillates on its own mitigation.
# ---------------------------------------------------------------------------


@counted_cache
def _salted_targets_fn(mesh, salt: int):
    """(targets, emit, warn_factor) -> (salted targets [sharded],
    stacked [2, W, W] salted+raw count matrices [replicated]). ``salt``
    is the declared CYLON_SALT_FACTOR (>= 2, structural — a tiny
    finite set of compiled programs)."""
    axis = mesh.axis_names[0]
    world = mesh.devices.size
    spec = P(axis)

    def kernel(targets, emit, warn):
        t = jnp.where(emit, targets.astype(jnp.int32), world)
        raw = replicated_gather(_target_counts(t, world), axis, world)
        recv = raw.sum(axis=0)
        total = jnp.maximum(recv.sum(), 1)
        # hot destination: receive total > warn x mean = warn x total/W
        hot = recv.astype(jnp.float32) * np.float32(world) \
            > warn * total.astype(jnp.float32)
        iota = jnp.arange(targets.shape[0], dtype=jnp.uint32)
        sub = (_hash.fmix32(iota) % np.uint32(salt)).astype(jnp.int32)
        safe = jnp.clip(targets.astype(jnp.int32), 0, world - 1)
        spread = (safe + sub) % np.int32(world)
        t2 = jnp.where(jnp.take(hot, safe) & emit, spread, safe)
        t2d = jnp.where(emit, t2, world)
        salted = replicated_gather(_target_counts(t2d, world), axis,
                                   world)
        return t2, jnp.stack([salted, raw])

    return jax.jit(shard_map(kernel, mesh=mesh,
                             in_specs=(spec, spec, P()),
                             out_specs=(spec, P())))


def salted_exchange_targets(targets, emit, ctx: CylonContext,
                            salt: int, warn_factor: float):
    """Host wrapper: run the salted-targets program, fetch BOTH count
    matrices in one sync, and return (salted targets, salted counts,
    raw counts) — the caller feeds the salted counts to exchange()
    (no second count round trip) and observes skew from the raw ones."""
    def compute():
        t2, both = _salted_targets_fn(ctx.mesh, salt)(
            targets, emit, jnp.float32(warn_factor))
        host = np.asarray(_host_fetch("shuffle.salt", both))
        _counter("cylon_collective_launches_total").inc()
        return t2, host[0], host[1]

    return _retry.run_retryable("exchange.count", compute)


# Repeat-shuffle count cache: jax Arrays are immutable, so identical
# (targets, emit) OBJECTS imply identical counts — iterative pipelines
# that re-shuffle the same key column skip the count round trip on
# every repeat.
# WEAK refs only: entries die with their arrays (no HBM pinned beyond
# the caller's own lifetime), and a hit additionally verifies object
# identity so a recycled id can never alias a dead entry.
_COUNT_CACHE: "dict[tuple, tuple]" = {}
_COUNT_CACHE_CAP = 8


def _count_cached(ids_key, refs, compute):
    import weakref

    hit = _COUNT_CACHE.get(ids_key)
    if hit is not None:
        wrs, val = hit
        if all(w() is r for w, r in zip(wrs, refs)):
            return val
        del _COUNT_CACHE[ids_key]
    val = compute()
    if len(_COUNT_CACHE) >= _COUNT_CACHE_CAP:
        _COUNT_CACHE.pop(next(iter(_COUNT_CACHE)))
    try:
        wrs = tuple(weakref.ref(r) for r in refs)
    except TypeError:  # pragma: no cover - non-weakref-able array impl
        return val  # skip caching rather than pin device memory
    _COUNT_CACHE[ids_key] = (wrs, val)
    return val


def count_pair(targets1, emit1, targets2, emit2, ctx: CylonContext):
    """Host (countsL, countsR) for two shuffles, one program + one sync.
    Feed the results to exchange(..., counts=...)."""
    def compute():
        # result is [src, 2, dst] (replicated_gather stacks per source)
        with _span("shuffle.count", ctx.get_next_sequence(),
                   world=ctx.get_world_size(), tables=2):
            both = np.asarray(_host_fetch(
                "shuffle.count_pair",
                _count2_fn(ctx.mesh)(targets1, emit1, targets2, emit2)))
        _counter("cylon_collective_launches_total").inc()
        return both[:, 0, :], both[:, 1, :]

    # the count program is part of the exchange stage: transient
    # failures (and injected compile faults in its factory build)
    # retry under the same policy as the body dispatch
    return _count_cached(
        ("pair", id(ctx.mesh), id(targets1), id(emit1), id(targets2),
         id(emit2)),
        (targets1, emit1, targets2, emit2),
        lambda: _retry.run_retryable("exchange.count", compute))


def _padded_route(counts, payload, world: int, budget,
                  buffer_factor: int = 4, max_block: int = None):
    """(padded_ok, block, block cap) — ONE routing rule shared by
    exchange() and exchange_pair() so the two paths can never silently
    diverge. The block every (src,dst) pair crosses in is the worst
    pair's rows on `util.capacity`'s 16-an-octave grid, not its octave:
    each chip receives world * block SLOTS and the operator behind the
    exchange (a join's or a merge's sort) pays for every one of them
    (four v5e chips, PR 52: 15% over a power of two used to double the
    block, and `join-w4-zipf` sorted 50.3M slots a chip for 35.3M rows).
    Where the pairs INTO the worst chip are level that is the capacity a
    compaction behind the exchange would have cut to, with no pass. The
    price is up to 16 exchange programs (and per-shard consumers) an
    octave of the worst pair where there was one."""
    max_pair = int(counts.max()) if counts.size else 0
    recv_max = int(counts.sum(axis=0).max()) if counts.size else 0
    block_p = _capacity(max_pair)
    if max_block is None:
        # a known budget decides alone: capped at MAX_BLOCK too, 16M
        # rows a chip on 4 chips (blocks over 4M) could never go padded
        # and every such exchange fell to the blockwise sort+scatter
        # rounds
        max_block = max(block_p, MAX_BLOCK) if budget else MAX_BLOCK
    mb = _budget_block_cap(max_block, world,
                           _payload_row_bytes(payload) or 4, budget,
                           buffer_factor)
    ok = (world * block_p
          <= PADDED_WASTE_FACTOR * max(_pow2(recv_max), 1)
          and block_p <= mb)
    return ok, block_p, mb


def exchange(payload: Dict[str, jnp.ndarray], targets: jnp.ndarray,
             emit: jnp.ndarray, ctx: CylonContext,
             max_block: Optional[int] = None,
             counts: Optional[np.ndarray] = None
             ) -> Tuple[Dict[str, jnp.ndarray], jnp.ndarray, int, dict]:
    """Shuffle a pytree of row-sharded per-row arrays to their target shards.

    Returns (exchanged payload, new emit mask, per-shard capacity, meta).
    All outputs are row-sharded with each source's rows CONTIGUOUS and
    in stable order; live rows are marked by the emit mask. Two layouts,
    host-selected from the count matrix:

    * "padded" (the fast path): every (src,dst) pair moves one slice and
      lands at a static slot — no receive scatter. Source s's rows start
      at s*block; capacity world*block, the block being the worst
      pair's rows on `util.capacity`'s grid (at most 6.25% over; NOT a
      power of two: `_padded_route`). Picked when that padding stays
      within PADDED_WASTE_FACTOR of the compact capacity (uniform-ish
      distributions, which hash placement makes the common case).
    * "compact" (skew fallback): blockwise rounds with bounded comm
      buffers; live rows form a leading prefix, capacity pow2 of the
      worst receive total.

    meta = {"mode", "block", "counts_in"} — counts_in is the [world*W]
    sharded per-source receive-count matrix (each shard's own [W] slice),
    consumed by the varbytes word/row layout reconciliation. A
    padded-mode exchange is ONE program wherever its send and receive
    stacks fit the pool's comm budget (every shape the benchmark holds:
    one program beat 2-4 chunk programs by 4.6-20 ms a query on four
    chips, PERF.md section 6, PR 48); where they do not it runs as the
    chunked pipeline (`_chunk_plan`; meta gains ``chunks``), and the two
    are bit-identical on every live row.
    ``max_block`` caps the per-round blockwise block size.
    """
    world = ctx.get_world_size()
    seq = ctx.get_next_sequence()
    if counts is None:
        def compute():
            with _span("shuffle.count", seq, world=world, tables=1):
                res = np.asarray(_host_fetch(
                    "shuffle.count", _count_fn(ctx.mesh)(targets, emit)))
            _counter("cylon_collective_launches_total").inc()
            return res

        counts = _count_cached(
            ("one", id(ctx.mesh), id(targets), id(emit)),
            (targets, emit),
            lambda: _retry.run_retryable("exchange.count", compute))
    with _span("shuffle.route", seq, world=world, tables=1) as rsp:
        max_pair = int(counts.max()) if counts.size else 0
        recv_max = int(counts.sum(axis=0).max()) if counts.size else 0
        budget = ctx.memory_pool.comm_budget_bytes()
        padded_ok, block_p, mb = _padded_route(counts, payload, world,
                                               budget, buffer_factor=4,
                                               max_block=max_block)
        cap_padded = world * block_p
        cap_compact = _pow2(recv_max)
        rows_live = int(counts.sum()) if counts.size else 0
        nbytes = _payload_nbytes(payload)
        row_bytes = _payload_row_bytes(payload)
        # skew observability rides the ALREADY-FETCHED count matrix: zero
        # extra device→host transfers (None on a 1-wide mesh)
        skew_stats = _skew.observe_exchange(
            counts, row_bytes,
            world * (cap_padded if padded_ok else cap_compact))
        if padded_ok:
            part = _partition_path(ctx.mesh, world, payload)
            cb, chunks = _chunk_plan(block_p, world, row_bytes, budget)
        else:
            # the blockwise rounds scatter what they receive, whatever
            # the slots: their block keeps its power of two, and their
            # programs stay one an octave
            round_block = min(_pow2(max_pair), _pow2_floor(mb))
            chunks = 1
        rsp.set(mode="padded" if padded_ok else "compact",
                block=block_p if padded_ok else round_block,
                chunks=chunks)
    with _span("shuffle.exchange", seq, world=world,
               mode="padded" if padded_ok else "compact",
               rows=rows_live, bytes_moved=nbytes) as sp:
        if skew_stats is not None:
            sp.set(**skew_stats.span_attrs())
        if padded_ok:
            sp.set(block=block_p)
            _record_partition(sp, world, (part, payload))
            if chunks > 1:
                out, new_emit, counts_in = _dispatch_chunked(
                    ctx, block_p, cb, chunks, payload, targets, emit,
                    part)
                _record_chunked(sp, chunks, cb)
                _record_exchange(rows_live, nbytes, chunks)
                return out, new_emit, cap_padded, {
                    "mode": "padded", "block": block_p,
                    "counts_in": counts_in, "chunks": chunks}
            out, new_emit, counts_in = _launch_exchange(
                lambda: _exchange_padded_fn(
                    ctx.mesh, block_p, part)(payload, targets, emit))
            _record_exchange(rows_live, nbytes)
            return out, new_emit, cap_padded, {
                "mode": "padded", "block": block_p, "counts_in": counts_in}
        # pow2 round count bounds the compile cache to O(log^3) programs
        rounds = _pow2(-(-max(max_pair, 1) // round_block))
        sp.set(block=round_block, rounds=rounds)
        out, new_emit, counts_in = _launch_exchange(
            lambda: _exchange_fn(
                ctx.mesh, round_block, rounds, cap_compact)(
                payload, targets, emit))
    _record_exchange(rows_live, nbytes)
    return out, new_emit, cap_compact, {
        "mode": "compact", "block": 0, "counts_in": counts_in}
