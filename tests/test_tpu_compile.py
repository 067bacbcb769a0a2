"""The main path's Pallas kernels, compiled by the chip's own compiler for
a DESCRIBED (not attached) TPU v5e at the real 16M-row shapes: what
Mosaic refuses — a block shape off the (8, 128) tiling, too much VMEM,
a program past HBM — fails here, at no chip time, where the interpreter
tests pass. Nothing runs: results, donation and collectives are
`chip_smoke.py`'s to prove.

Only one process at a time may load libtpu, and it keeps it until it
exits: the topology is described inside a module-scoped fixture (never
at import), the compiles run in this process, and all of them live in
this ONE file so that one xdist worker owns the library.
"""
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from cylon_tpu.ops import groupby as _groupby
from cylon_tpu.ops import join as _join
from cylon_tpu.ops import tpu_kernels as tk

N = 1 << 24  # rows a side per chip: the size every record uses


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu / lock held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def chip(topo):
    """One described chip, with the persistent compile cache off around
    the module: a described-chip entry cannot be read back and warns."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sds(chip, n, dtype):
    return jax.ShapeDtypeStruct((n,), dtype, sharding=chip)


def _compiled_text(fn, *args, **kw):
    """Lower + compile for the described chip with x64 OFF, as on the
    chip (tier-1 runs with it on, conftest.py)."""
    with jax.enable_x64(False):
        jitted = fn if hasattr(fn, "lower") else jax.jit(fn)
        return jitted.lower(*args, **kw).compile().as_text()


@pytest.mark.parametrize("n,legs", [
    (16_000_000, 2),     # join-w4: a side's key and payload
    (62_500_000, 4),     # groupby-q5-w4: the partial table's four leaves
    (N, 21),             # a wide table: 64 rows a block
], ids=["join_w4", "groupby_q5_w4", "wide"])
def test_partition_kernels_compile(chip, n, legs):
    """The exchange's fused partitioner at world 4 -> 5 buckets (4 live
    targets + the dead-row tail), at the block height the legs give
    (`partition_block_rows`: 256 rows at 2 and at 4 legs)."""
    def part(t, *streams):
        return (tk.partition_hist(t, 5),
                tk.partition_scatter(t, streams, 5))

    text = _compiled_text(part, _sds(chip, n, jnp.int32),
                          *[_sds(chip, n, jnp.uint32)] * legs)
    assert text.count("tpu_custom_call") >= 2


def test_stream_compact_compiles(chip):
    def compact(mask, a, b):
        return tk.stream_compact(mask, [a, b])

    text = _compiled_text(compact, _sds(chip, N, jnp.bool_),
                          _sds(chip, N, jnp.uint32),
                          _sds(chip, N, jnp.float32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("n,num_segments,keys_ride", [
    (100_000_000, 1 << 20, True),    # groupby-q5: 1e8 rows, 2^20 slots
    (100_000_000, 1 << 20, False),   # the same with a row mask and the index
    (N, N, False),             # the distributed caller: num_segments = n
], ids=["q5", "q5_masked_index", "segments_eq_rows"])
def test_groupby_stream_reduce_compiles(chip, monkeypatch, n, num_segments,
                                        keys_ride):
    """The groupby's reduce step on its streaming path, as a TPU backend
    chooses it (this process's backend is the CPU: the choice is steered
    here), q5's three sums: int32, int32, float32. ``keys_ride``: as
    groupby-q5 runs it, no row mask and the sorted key lane as the
    "first" stream in the index's place, the int32 key column read back
    off it inside the program."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    SUM = _groupby.AggregationOp.SUM
    vals = (_sds(chip, n, jnp.int32), _sds(chip, n, jnp.int32),
            _sds(chip, n, jnp.float32))
    emit, first, key_spec = (
        (None, (_sds(chip, n, jnp.uint32),),
         ((np.dtype(np.int32), False, False),)) if keys_ride else
        (_sds(chip, n, jnp.bool_), _sds(chip, n, jnp.int32), None))
    text = _compiled_text(
        _groupby.sorted_segment_aggregate_jit, _sds(chip, n, jnp.bool_),
        emit, first, vals, (None,) * 3, num_segments=num_segments,
        ops=(SUM,) * 3, col_ids=(0, 1, 2), all_valid=(True,) * 3,
        key_spec=key_spec)
    assert text.count("tpu_custom_call") == 1   # ONE pass for all streams
    assert "scatter" not in text and "gather" not in text


@pytest.mark.parametrize("n,slots", [
    (100_000_000, 128), (100_000_000, _groupby.DENSE_MAX_SLOTS), (1000, 8)],
    ids=["q4", "max_slots", "small"])
def test_groupby_dense_compiles(chip, n, slots):
    """The no-sort groupby as groupby-q4 runs it: 1e8 rows, an int32 key,
    three means (two int32 columns converted in the kernel, one float32),
    no row mask, no nulls; the same at the most slots `group_path`
    admits, where the table is largest; and a table smaller than one
    block of the kernel. ONE pass: one kernel, no sort, no scatter."""
    MEAN = _groupby.AggregationOp.MEAN

    def dense(key, lohi, a, b, c):
        return _groupby.dense_aggregate(
            key, None, None, lohi, (a, b, c), (None,) * 3, slots,
            (MEAN,) * 3, (1, 2, 3))

    text = _compiled_text(dense, _sds(chip, n, jnp.int32),
                          _sds(chip, 2, jnp.int32),
                          _sds(chip, n, jnp.int32), _sds(chip, n, jnp.int32),
                          _sds(chip, n, jnp.float32))
    assert text.count("tpu_custom_call") == 1
    assert "scatter" not in text and " sort(" not in text


def test_groupby_key_range_probe_compiles(chip):
    """The probe that every candidate groupby pays (groupby-q5 too): min
    and max of 1e8 keys, no temporary of the key's size."""
    fn = jax.jit(lambda k: _groupby.key_range_probe(k, None, None))
    with jax.enable_x64(False):
        compiled = fn.lower(_sds(chip, 100_000_000, jnp.int32)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < (1 << 20)


def test_groupby_packed_sort_compiles_with_two_operands(chip):
    """groupby-q5's fused sort as PR 35 packs it (at a sixth of its rows:
    a sort compiles by the operand, seconds each at 1e8): the key lane,
    v1 and v2 go in as ONE uint32 word and v3 beside it, so the sort as
    COMPILED has two operands; and the value columns' range probe keeps
    no temporary of a column's size."""
    n = N
    u32, i32, f32 = (_sds(chip, n, d) for d in (jnp.uint32, jnp.int32,
                                                jnp.float32))
    plan = _groupby.sort_pack_plan(20, [3, 4, None])
    assert plan == ((-1, 0, 1), (2,))
    params = jax.ShapeDtypeStruct((3, 3), jnp.uint32, sharding=chip)
    text = _compiled_text(_groupby.presort_groups_jit, (u32,), None,
                          (i32, i32, f32), (None,) * 3, index=False,
                          plan=plan, params=params)
    sorts = [ln for ln in text.splitlines() if re.search(r"\bsort\(", ln)]
    assert len(sorts) == 1, sorts
    operands = re.search(r"\bsort\((.*?)\), dimensions=", sorts[0]).group(1)
    assert operands.count("%") == 2, sorts[0]
    assert _groupby.sort_operand_count((u32,), None, (i32, i32, f32),
                                       (None,) * 3, False, plan) == 2
    with jax.enable_x64(False):
        probe = jax.jit(_groupby.value_range_probe).lower(
            (i32, i32)).compile()
    assert probe.memory_analysis().temp_size_in_bytes < (1 << 20)


def test_setop_stream_compiles(chip):
    """Distinct union over two 8M-row tables of one payload lane."""
    def setop(bits, bits2, tag, lane):
        return tk.setop_stream(bits, bits2, tag, [lane], op=0)

    u32 = _sds(chip, N, jnp.uint32)
    assert "tpu_custom_call" in _compiled_text(setop, u32, u32, u32, u32)


# the smoke's pruned join inputs: left (k,), right (k, w), all rows valid,
# the key map as the local join passes it (both keys are column 0)
def _join_shapes(chip):
    k, w = _sds(chip, N, jnp.int32), _sds(chip, N, jnp.float32)
    cols = ((k,), (None,), (k, w), (None, None))
    a_desc, b_desc = _join.plan_lane_descs(*cols, _join.JoinType.INNER, 0, 0)
    keys = ((k,), (None,), None, (k,), (None,), None)
    kw = dict(join_type=_join.JoinType.INNER, a_desc=a_desc, b_desc=b_desc,
              block_rows=_join.stream_block_rows(N, N), interpret=False)
    return keys, cols, kw


def test_join_plan_stream_compiles(chip):
    """The 16M x 16M stream join plan at block_rows 64 — the ~2 minute
    compile of the main path."""
    keys, cols, kw = _join_shapes(chip)
    assert kw["block_rows"] == 64
    text = _compiled_text(_join._plan_program_stream_jit, *keys, *cols,
                          str_flags=(False,), hash_mode=False, **kw)
    assert "tpu_custom_call" in text
    # the fused sort as COMPILED: key bits, tag and one payload slot (w);
    # the key rides once and, the sort not being stable, the compiler
    # appends no index of its own (PERF.md section 6)
    sorts = [ln for ln in text.splitlines() if re.search(r"\bsort\(", ln)]
    assert len(sorts) == 1, sorts
    operands = re.search(r"\bsort\((.*?)\), dimensions=", sorts[0]).group(1)
    assert operands.count("%") == 3, sorts[0]
    assert _join.plan_sort_operand_count(
        keys[0], (False,), kw["a_desc"], kw["b_desc"]) == 3


def test_join_expand_compiles(chip):
    """The expand (materialize) program on that plan's outputs, at the
    output capacity of the smoke's 64M joined rows."""
    keys, cols, kw = _join_shapes(chip)
    with jax.enable_x64(False):
        plan_out = jax.eval_shape(
            lambda *a: _join._plan_program_stream_impl(
                *a, str_flags=(False,), hash_mode=False, **kw),
            *keys, *cols)
    counts, a_streams, b_streams = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip),
        plan_out)
    cap_e = _join.stream_expand_capacity(4 * N, kw["block_rows"])
    text = _compiled_text(_join._materialize_program_stream_jit, counts,
                          a_streams, b_streams, *cols, cap_e=cap_e, **kw)
    assert "tpu_custom_call" in text


# upstream Cylon's join schema (PR 32): int64 key, float64 value, each ONE
# uint32[2, n] array of word planes, the key riding once as its two
# ordered lanes. 1M rows a side: block_rows is 64 as at 31.25M, and what
# Mosaic may refuse (the second run stream, 12 compacted streams, 8 expand
# lanes in VMEM) does not depend on the rows; the sort at full size was
# compiled by hand (PERF.md section 6, PR 32).
N64 = 1 << 20


def _join64_shapes(chip):
    p = jax.ShapeDtypeStruct((2, N64), jnp.uint32, sharding=chip)
    cols = ((p, p), (None, None), (p, p), (None, None))
    a_desc, b_desc = _join.plan_lane_descs(*cols, _join.JoinType.INNER, 0, 0,
                                           "int64")
    keys = ((p,), (None,), None, (p,), (None,), None)
    kw = dict(join_type=_join.JoinType.INNER, a_desc=a_desc, b_desc=b_desc,
              block_rows=_join.stream_block_rows(N64, N64), interpret=False)
    return keys, cols, kw


def test_join64_plan_and_expand_compile(chip):
    keys, cols, kw = _join64_shapes(chip)
    assert kw["block_rows"] == 64
    plan_kw = dict(str_flags=(False,), hash_mode=False,
                   wide_key="int64", **kw)
    text = _compiled_text(_join._plan_program_stream_jit, *keys, *cols,
                          **plan_kw)
    assert "tpu_custom_call" in text
    # (hi, lo, tag) and the payload's two planes: 5 operands as COMPILED
    sorts = [ln for ln in text.splitlines() if re.search(r"\bsort\(", ln)]
    assert len(sorts) == 1, sorts
    operands = re.search(r"\bsort\((.*?)\), dimensions=", sorts[0]).group(1)
    assert operands.count("%") == 5, sorts[0]
    with jax.enable_x64(False):
        plan_out = jax.eval_shape(
            lambda *a: _join._plan_program_stream_impl(*a, **plan_kw),
            *keys, *cols)
    counts, a_streams, b_streams = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip),
        plan_out)
    cap_e = _join.stream_expand_capacity(N64, kw["block_rows"])
    text = _compiled_text(_join._materialize_program_stream_jit, counts,
                          a_streams, b_streams, *cols, cap_e=cap_e,
                          wide_key="int64", **kw)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("stage", ["targets", "keybits"])
def test_key_programs_compile_across_four_chips(topo, chip, stage):
    """The two key programs of a distributed operator (PR 39) at
    join-w4's shape, 2^24 int32 keys a chip over the described 2x2 mesh:
    each is ONE elementwise pass a shard (a loop fusion and the all-ones
    mask's broadcast), no collective, every output on the row sharding
    (so `shard.pin` after it launches nothing)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from cylon_tpu.parallel import dist_ops

    mesh = Mesh(np.array(topo.devices), ("shards",))
    row = NamedSharding(mesh, P("shards"))
    forms = (("plain", "int32", False, False),)
    fn = dist_ops._partition_targets_program_fn(mesh, forms, True) \
        if stage == "targets" \
        else dist_ops._key_bits_program_fn(mesh, forms, (False,))
    keys = jax.ShapeDtypeStruct((4 * N,), jnp.int32, sharding=row)
    with jax.enable_x64(False):
        compiled = fn.lower(((keys,),)).compile()
    text = compiled.as_text()
    assert not re.search(r"all-to-all|all-reduce|all-gather|"
                         r"collective-permute", text)
    fusions = [ln for ln in text.splitlines()
               if re.search(r"= \S+ fusion\(", ln)]
    assert len(fusions) == 1, fusions
    for sharding in jax.tree.leaves(compiled.output_shardings):
        assert sharding.spec == P("shards")


def _sort_lines(text):
    return [ln for ln in text.splitlines() if re.search(r"= \S.* sort\(", ln)]


@pytest.fixture
def shards4(topo, chip, monkeypatch):
    """(the described 2x2 mesh, n -> a maker of row-sharded shapes) for a
    per-shard program, traced as a TPU backend traces it (this process's
    backend is the CPU: `ops/groupby.reduce_path` is steered here)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = Mesh(np.array(topo.devices), ("shards",))
    row = NamedSharding(mesh, P("shards"))
    return mesh, lambda n: lambda dtype: jax.ShapeDtypeStruct(
        (n,), dtype, sharding=row)


@pytest.mark.parametrize("phase,operands", [
    ("partial", 2), ("partial-masked", 5), ("merge", 5),
    ("merge-nullable", 8)])
def test_dist_groupby_lanes_program_compiles(shards4, phase, operands):
    """`groupby-q5-w4`'s per-shard program (`jit_groupby`) as a TPU
    backend gets it, for the described 2x2 mesh, with the cell's operand
    lists at 2^13 rows a chip: the program's SHAPE is what is held here,
    and a sort's compile time goes with its rows up to 2^15 and then
    stays (3 + 5 s here; 70 + 128 s at 2^20, minutes at the cell's
    6.25e7 rows and 2^24 slots: PERF.md section 6, where the real
    shapes' compile is recorded; the reduce kernel at num_segments = n =
    2^24 is `test_groupby_stream_reduce_compiles`'s). An int32 key
    without nulls read back off its sorted lane (PR 41), so ONE sort that
    is not stable, ONE Pallas pass, and no gather or scatter at all.
    "partial" is the cell's first step since PR 43: no row mask, so no
    dead flag, and the plan the host makes from the observed ranges (id6
    + v1 + v2 in ONE word, v3) with its replicated ``params``: 2
    operands. "partial-masked": a table with a row mask whose ranges do
    not pack, the unpacked list (dead flag, key lane, three values).
    "merge" is the cell's second step since PR 44: the dead flag (after
    an exchange there is always a row mask), the key lane and the three
    partial sums, whose validity is the partial table's row mask and so
    None (`dist_ops._partial_masks_elided`): 5. "merge-nullable": the
    partial sums of three nullable columns keep their masks and the merge
    its any-valid passes, the cell's program before PR 44: 8."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from cylon_tpu.parallel import dist_ops

    mesh, shapes = shards4
    rows = shapes(4 << 13)
    SUM = _groupby.AggregationOp.SUM
    nullable = phase == "merge-nullable"
    vdat = (rows(jnp.int32), rows(jnp.int32), rows(jnp.float32))
    vval = (rows(jnp.bool_),) * 3 if nullable else (None,) * 3
    emit = None if phase == "partial" else rows(jnp.bool_)
    plan = params = None
    if phase == "partial":
        plan = _groupby.sort_pack_plan(24, [3, 4, None])
        assert plan == ((-1, 0, 1), (2,))
        params = jax.ShapeDtypeStruct((3, 3), jnp.uint32,
                                      sharding=NamedSharding(mesh, P()))
    all_valid = (not nullable,) * 3
    # a step that makes the partial table drops the masks that repeat its
    # row mask, here all three
    elided = dist_ops._partial_masks_elided((SUM,) * 3, all_valid) \
        if phase.startswith("partial") else None
    fn = dist_ops._groupby_fn(
        mesh, (SUM,) * 3, (0, 1, 2), all_valid,
        ((np.dtype(np.int32), False, False),), plan, elided)
    assert _groupby.sort_operand_count(
        (rows(jnp.uint32),), emit, vdat, vval, False, plan) == operands
    with jax.enable_x64(False):
        text = fn.lower((rows(jnp.uint32),), (), (), emit, vdat, vval,
                        params).compile().as_text()
    assert text.count("tpu_custom_call") == 1
    sorts = _sort_lines(text)
    assert len(sorts) == 1, sorts
    args = re.search(r" sort\((.*?)\), dimensions", sorts[0]).group(1)
    assert len(args.split(", ")) == operands, sorts[0]
    assert "is_stable=true" not in sorts[0]
    assert not re.search(r"\b(gather|scatter)\(", text)


def test_dist_groupby_pack_probe_compiles(shards4):
    """The probe that packs `groupby-q5-w4`'s first sort
    (`jit_groupby_pack_ranges_program`) over the described 2x2 mesh at
    the cell's 6.25e7 rows a chip: a min / max of id6, v1 and v2 a shard
    and ONE small all-reduce for the whole table's ranges, one
    ``uint32[3, 3]`` array on every chip; no row mask, so no temporary of
    a column's size."""
    from cylon_tpu.data import table as T

    _mesh, shapes = shards4
    rows = shapes(250_000_000)
    with jax.enable_x64(False):
        compiled = T._groupby_pack_ranges_program_fn().lower(
            rows(jnp.int32), None, (rows(jnp.int32),) * 2).compile()
    text = compiled.as_text()
    assert re.search(r"all-reduce", text)
    assert not re.search(r"all-to-all|all-gather|collective-permute", text)
    out, = jax.tree.leaves(compiled.output_shardings)
    assert out.is_fully_replicated
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


def test_dist_groupby_gather_program_still_compiles(shards4):
    """The fallback the same function selects (a varbytes key: four
    content-hash lanes with no way back): the stable sort with the row
    index, the key's lengths and mask gathered at each group's first row."""
    from cylon_tpu.parallel import dist_ops

    mesh, shapes = shards4
    rows = shapes(4 << 12)
    SUM = _groupby.AggregationOp.SUM
    fn = dist_ops._groupby_fn(mesh, (SUM,), (0,), (True,), None)
    with jax.enable_x64(False):
        text = fn.lower((rows(jnp.uint32),) * 4, (rows(jnp.int32),),
                        (rows(jnp.bool_),), rows(jnp.bool_),
                        (rows(jnp.float32),), (None,),
                        None).compile().as_text()
    assert text.count("tpu_custom_call") == 1
    sorts = _sort_lines(text)
    assert len(sorts) == 1 and "is_stable=true" in sorts[0], sorts
    assert re.search(r"\bgather\(", text)


# -- the padded exchange as ONE program (PR 48): what the three four-chip
# cells run since `_chunk_plan` chunks by the budget and not by 64 MiB ---------

@pytest.mark.parametrize("cell,blocks,rows,leaves,collectives", [
    ("join-w4", (4_063_232, 4_063_232), 16_000_000, 2, 6),
    ("join-w4-zipf", (4_063_232, 4_980_736), 16_000_000, 2, 6),
    ("groupby-q5-w4", (2_621_440,), 62_500_000, 4, 5),
])
def test_single_shot_exchange_compiles_at_the_cells_shapes(
        shards4, cell, blocks, rows, leaves, collectives):
    """A join's two sides in the fused pair program (no cell reached it on
    a TPU before PR 48) and the groupby's partial table in the single
    program, partitioned by the Pallas kernels, for the described 2x2
    mesh at the cells' rows a chip and in the blocks they cross in since
    PR 52 (`util.capacity` of the worst pair's 4.00M, 4.81M and 2.4955M
    rows: 31 * 2^17, 19 * 2^18, 20 * 2^17; 2^22, 2^23 and 2^22 before):
    Mosaic and HBM take them, no power of two among them, one
    all_to_all a leaf and one for the counts a table, and of the chunk
    programs' loops and landings (2 `while`, 4 `dynamic-update-slice` a
    two-leaf chunk program) only the partition prefix's own are left,
    one of each a table."""
    from cylon_tpu.parallel import shuffle

    mesh, shaped = shards4
    row = shaped(4 * rows)

    def side():
        payload = {f"c{i}": row(jnp.int32 if i else jnp.float32)
                   for i in range(leaves)}
        return payload, row(jnp.int32), row(jnp.bool_)

    if len(blocks) == 2:
        fn = shuffle._exchange_padded_pair_fn(mesh, *blocks, "pallas",
                                              "pallas")
        operands = side() + side()
    else:
        fn = shuffle._exchange_padded_fn(mesh, blocks[0], "pallas")
        operands = side()
    with jax.enable_x64(False):
        compiled = fn.lower(*operands).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 2 * len(blocks)
    assert len(re.findall(r" all-to-all\(", text)) == collectives
    for opcode in ("while", "dynamic-update-slice"):
        assert len(re.findall(rf" {opcode}\(", text)) == len(blocks), opcode
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.output_size_in_bytes \
        + mem.argument_size_in_bytes < 6 * 10 ** 9, mem


def test_join_plan_compiles_at_the_zipf_cells_slots(shards4):
    """What the exchange hands `join-w4-zipf`'s per-shard join since PR 52:
    16,252,928 slots of R and 19,922,944 of S a chip (four blocks of
    4,063,232 and of 4,980,736; 2^24 and 2^25 before), neither a power of
    two. The stream plan under `shard_map` over the described 2x2 mesh:
    ONE sort of 36,175,872 slots and three operands, one kernel."""
    from cylon_tpu.parallel import dist_ops

    mesh, rows = shards4
    na, nb = 4 * 4_063_232, 4 * 4_980_736
    r, s = rows(4 * na), rows(4 * nb)
    ldat, rdat = (r(jnp.int32), r(jnp.float32)), (s(jnp.int32),
                                                  s(jnp.float32))
    none2 = (None, None)
    a_desc, b_desc = _join.plan_lane_descs(ldat, none2, rdat, none2,
                                           _join.JoinType.INNER, 0, 0)
    fn = dist_ops._join_plan_stream_fn(
        mesh, _join.JoinType.INNER, 1, a_desc, b_desc,
        _join.stream_block_rows(na, nb), False)
    with jax.enable_x64(False):
        text = fn.lower((r(jnp.uint32),), None, r(jnp.bool_),
                        (s(jnp.uint32),), None, s(jnp.bool_),
                        ldat, none2, rdat, none2).compile().as_text()
    assert text.count("tpu_custom_call") == 1
    sorts = _sort_lines(text)
    assert len(sorts) == 1 and sorts[0].count(f"u32[{na + nb}]") == 3, sorts


# -- TPC-H Q1 (PR 42): the expression programs and the dense table over two
# keys and 64-bit limb streams, at the cell's 75,004,738 rows ---------------

Q1_ROWS = 75_004_738
# the ranges Q1's columns reach at that size (hundredths): they choose the
# forms of the steps and the limbs of the sums
Q1_RANGES = {1: (90_000, 10_495_000), 2: (0, 10), 3: (0, 8)}
Q1_EXPRS = (("mul", ("col", 1), ("sub", ("lit", 100), ("col", 2))),
            ("mul", ("col", 7), ("add", ("lit", 100), ("col", 3))))


def _planes(chip, n):
    return jax.ShapeDtypeStruct((2, n), jnp.uint32, sharding=chip)


def test_q1_expression_programs_compile(chip):
    """`Table.with_columns` as tpch-q1 runs it: the range probe over three
    plane-held columns keeps no temporary of a column's size, and the two
    computed columns come out of ONE program with no 64-bit lane in it
    (disc_price fits an int32 by its observed range, charge does not)."""
    from cylon_tpu.ops import expr as E

    leaves = {p: _planes(chip, Q1_ROWS) for p in (1, 2, 3)}
    with jax.enable_x64(False):
        probe = jax.jit(E.range_probe).lower(
            tuple(leaves.values())).compile()
    assert probe.memory_analysis().temp_size_in_bytes < (1 << 20)
    ranges = dict(Q1_RANGES)
    forms = []
    for i, tokens in enumerate(Q1_EXPRS):
        f, ranges[7 + i] = E.plan_forms(tokens, ranges, "int64", f"e{i}")
        forms.append(f)
    assert forms[0][0] == "i32" and forms[1][0] == "i64"

    def compute(leaves):
        leaves, out = dict(leaves), []
        for i, tokens in enumerate(Q1_EXPRS):
            out.append(E.evaluate_words(tokens, forms[i], leaves, "int64"))
            leaves[7 + i] = out[-1]
        return out

    text = _compiled_text(compute, leaves)
    assert "s64[" not in text and "u64[" not in text


def test_q1_dense_groupby_compiles(chip):
    """Q1's groupby at the cell's size: two int32 code keys under a row
    mask into 8 slots, eight aggregates over five plane-held int64
    columns that the kernel cuts into limbs itself, ONE kernel, no sort,
    no scatter; and its probe (two keys) in one small program."""
    G = _groupby
    S, M, C = G.AggregationOp.SUM, G.AggregationOp.MEAN, G.AggregationOp.COUNT
    n = Q1_ROWS
    keys = (_sds(chip, n, jnp.int32),) * 2
    emit = _sds(chip, n, jnp.bool_)
    with jax.enable_x64(False):
        probe = jax.jit(G.ranges_probe).lower(
            keys, emit, (None, None)).compile()
    assert probe.memory_analysis().temp_size_in_bytes < (1 << 20)
    # value columns: qty, price, disc_price, charge, qty, price, disc, qty
    cols = (0, 1, 7, 8, 0, 1, 2, 0)
    ops = (S, S, S, S, M, M, M, C)
    wide = tuple(op != C for op in ops)
    by_col = {c: _planes(chip, n) for c in set(cols)}

    def dense(keys, emit, ranges, values):
        return G.dense_aggregate_keys(keys, (None, None), emit, ranges,
                                      values, (None,) * 8, 8, ops, cols,
                                      wide)

    with jax.enable_x64(False):
        compiled = jax.jit(dense).lower(
            keys, emit,
            jax.ShapeDtypeStruct((2, 3), jnp.uint32, sharding=chip),
            tuple(by_col[c] for c in cols)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert "scatter" not in text and " sort(" not in text
    # the slot ids and the ten planes as the kernel's (rows, 128) inputs
    # and one more array: 48 B a row, 3.6 GB beside the 3.3 GB table, its
    # copy and 1.2 GB of computed columns
    assert compiled.memory_analysis().temp_size_in_bytes < 50 * n


# TPC-H Q12 (PR 46): 75,000,000 lines filtered to 0.52% (390,367 rows),
# compacted to 393,216 slots (3 x 2^17: `util.capacity`'s grid since PR
# 50, 524,288 before), joined to 18,750,000 orders
Q12_LINES, Q12_ORDERS, Q12_CAP = 75_000_000, 18_750_000, 3 << 17
# TPC-H Q4 (PR 49): 18,750,000 orders filtered to the quarter's 716,958
# and compacted to 720,896 slots (22 x 2^15; 1,048,576 before PR 50),
# semi-joined to 75,000,000 lines whose 63% row mask PR 50 cuts too:
# 47.4M rows in 48,234,496 slots (23 x 2^21)
Q4_LINES, Q4_ORDERS = 75_000_000, 18_750_000
Q4_CAP, Q4_LINES_CAP = 22 << 15, 23 << 21


@pytest.mark.parametrize("rows,streams,cap", [
    (Q12_LINES, 2, Q12_CAP), (Q4_ORDERS, 2, Q4_CAP),
    (Q4_LINES, 1, Q4_LINES_CAP)], ids=["q12-lines", "q4-orders", "q4-lines"])
def test_compaction_compiles(chip, rows, streams, cap):
    """The cells' compactions as a TPU runs them: the row mask and the
    side's streams (`tpch-q12`: l_orderkey and l_shipmode's codes of
    75,000,000 rows; `tpch-q4`: the orders' key and priority codes, and
    the lines' key alone, 63% alive) through ONE Pallas pass into a
    capacity on the 16-an-octave grid, none a power of two; the outputs
    are of the capacity, not of the input (`stream_compact`'s
    ``out_elems``)."""
    from cylon_tpu import util
    from cylon_tpu.data import table as T

    assert util.capacity(cap) == cap and cap & (cap - 1)
    fn = T._compact_program_fn(cap, "stream")
    i32 = _sds(chip, rows, jnp.int32)
    with jax.enable_x64(False):
        compiled = fn.lower(_sds(chip, rows, jnp.bool_), [i32] * streams,
                            []).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert "scatter" not in text and " sort(" not in text
    assert compiled.memory_analysis().output_size_in_bytes \
        < (4 * streams + 8) * cap
    assert T.compact_streams([]) == 0


def test_q12_join_compiles(chip):
    """The cell's join: 18,750,000 orders (key, priority codes) against
    the compacted lines (key, ship-mode codes; a row mask: the prefix of
    live rows), 19,143,216 slots through the plan sort, block_rows 64;
    the key rides once, so the sort as COMPILED has key bits, tag and one
    payload slot."""
    ok = _sds(chip, Q12_ORDERS, jnp.int32)
    lk = _sds(chip, Q12_CAP, jnp.int32)
    cols = ((ok, ok), (None, None), (lk, lk), (None, None))
    a_desc, b_desc = _join.plan_lane_descs(*cols, _join.JoinType.INNER, 0, 0)
    keys = ((ok,), (None,), None, (lk,), (None,),
            _sds(chip, Q12_CAP, jnp.bool_))
    kw = dict(join_type=_join.JoinType.INNER, a_desc=a_desc, b_desc=b_desc,
              block_rows=_join.stream_block_rows(Q12_ORDERS, Q12_CAP),
              interpret=False)
    assert kw["block_rows"] == 64
    plan_kw = dict(str_flags=(False,), hash_mode=False, **kw)
    text = _compiled_text(_join._plan_program_stream_jit, *keys, *cols,
                          **plan_kw)
    assert "tpu_custom_call" in text
    sorts = [ln for ln in text.splitlines() if re.search(r"\bsort\(", ln)]
    assert len(sorts) == 1, sorts
    operands = re.search(r"\bsort\((.*?)\), dimensions=", sorts[0]).group(1)
    assert operands.count("%") == 3, sorts[0]
    with jax.enable_x64(False):
        plan_out = jax.eval_shape(
            lambda *a: _join._plan_program_stream_impl(*a, **plan_kw),
            *keys, *cols)
    counts, a_streams, b_streams = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip),
        plan_out)
    cap_e = _join.stream_expand_capacity(390_564, kw["block_rows"])
    assert cap_e == 1 << 19      # the RESULT's capacity keeps its octave
    text = _compiled_text(_join._materialize_program_stream_jit, counts,
                          a_streams, b_streams, *cols, cap_e=cap_e, **kw)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("how,lines", [("SEMI", Q4_LINES_CAP),
                                       ("ANTI", 1 << 22)])
def test_q4_semi_join_compiles(chip, how, lines):
    """(The anti join, one comparison apart, at a size that compiles in
    seconds.) The cell's semi join, ONE program: the compacted orders (key,
    priority codes; a row mask: the prefix of live rows) against the
    compacted lines (key alone; a row mask: the prefix of live rows),
    48,955,392 slots through the plan sort (76,048,576 before PR 50),
    block_rows 64. The build side rides with its key and tag alone and the
    key rides once, so the sort as COMPILED has key bits, tag and ONE
    payload slot; one Pallas call (the plan pass; no expand), and the
    result is of the PROBE side's capacity."""
    jt = _join.JoinType[how]
    ok = _sds(chip, Q4_CAP, jnp.int32)
    lk = _sds(chip, lines, jnp.int32)
    cols = ((ok, ok), (None, None), (), ())
    a_desc, b_desc = _join.plan_lane_descs(*cols, jt, 0, None)
    assert a_desc == ((0, "k"), (1, "d")) and b_desc == ()
    keys = ((ok,), (None,), _sds(chip, Q4_CAP, jnp.bool_),
            (lk,), (None,), _sds(chip, lines, jnp.bool_))
    kw = dict(str_flags=(False,), join_type=jt, a_desc=a_desc, b_desc=b_desc,
              block_rows=_join.stream_block_rows(Q4_CAP, lines),
              hash_mode=False, interpret=False)
    assert kw["block_rows"] == 64
    with jax.enable_x64(False):
        compiled = _join._plan_program_stream_jit.lower(
            *keys, *cols, **kw).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    sorts = [ln for ln in text.splitlines() if re.search(r"\bsort\(", ln)]
    assert len(sorts) == 1, sorts
    operands = re.search(r"\bsort\((.*?)\), dimensions=", sorts[0]).group(1)
    assert operands.count("%") == 3, sorts[0]
    assert f"u32[{lines + Q4_CAP}]" in sorts[0]
    # counts, two columns, the row mask, the kept rows' indices: of the
    # probe side's capacity, not of the sort's
    assert compiled.memory_analysis().output_size_in_bytes < 24 * Q4_CAP
    # it fits the chip beside the placed tables and their fresh copies
    assert compiled.memory_analysis().temp_size_in_bytes < 4_000_000_000


def test_semi_join_plan_compiles_across_four_chips(shards4):
    """The distributed semi join's per-shard program on the sort-stream
    path at join-w4's shape (2^24 rows a side a chip over the described
    2x2 mesh): the plan pass in its semi form under shard_map, no
    collective (no replicated counts gather), every output on the row
    sharding."""
    from jax.sharding import PartitionSpec as P

    from cylon_tpu.parallel import dist_ops

    mesh, rows = shards4
    row = rows(4 * N)
    u32, i32, b = row(jnp.uint32), row(jnp.int32), row(jnp.bool_)
    ldat, lval = (i32, i32), (None, None)
    a_desc, _ = _join.plan_lane_descs(ldat, lval, (), (),
                                      _join.JoinType.SEMI, 0, None)
    fn = dist_ops._semi_plan_stream_fn(mesh, _join.JoinType.SEMI, a_desc,
                                       _join.stream_block_rows(N, N))
    with jax.enable_x64(False):
        compiled = fn.lower((u32,), b, b, (u32,), b, b, ldat, lval).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert not re.search(r"all-to-all|all-reduce|all-gather|"
                         r"collective-permute", text)
    assert len(_sort_lines(text)) == 1
    for sharding in jax.tree.leaves(compiled.output_shardings):
        assert sharding.spec == P("shards")
