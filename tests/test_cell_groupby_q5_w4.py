"""The cell `groupby-q5-w4` at a test size on the CPU mesh (PR 40): the
planner's groupby over a table spread by rows goes through
`dist_ops.distributed_groupby` (partial sums a shard, the exchange of the
partial rows, the merge) and is held to the benchmark's own plain reference
(`benchmarks/references/groupby_sum_f64`) on the benchmark's own data
(`benchmarks/generators/h2o_g1`), in the cell's shape: a key range wider
than a shard's rows, so most groups have 0-2 rows a shard and nearly every
partial row has to cross. Besides: the four hosts' shares of one table add
up to the whole table's answer (what ties the configuration's share to its
source), the bfloat16 control fails by the float bound alone, the leaf
spans and the two counters this PR brought, and the streaming reduce kernel
under `shard_map` (the step the chip takes, never lowered there before)
against the `segment_*` path. What the first per-shard sort is handed
(PR 43) is `tests/test_cell_groupby_q5_w4_pack.py`, a file of its own so
that `--dist loadfile` gives each half a worker (PR 45); what both use
is `tests/cell_groupby_q5_w4_cases.py`.
"""
import numpy as np
import pytest

import cylon_tpu as ct
from cylon_tpu import plan, telemetry
from cylon_tpu.ops import groupby as G
from cylon_tpu.parallel import dist_ops

from cell_groupby_q5_w4_cases import (
    EXACT, FLOAT, REFERENCE, ROWS4, RUN, TRAFFIC, _as_on_a_tpu, _by_key,
    _data, _numbers, _planned, _q5_cols, _readback, _spread,
    _with_columns)


@pytest.mark.parametrize("masked", [False, True], ids=["dense", "masked"])
@pytest.mark.parametrize("world", [4, 8])
def test_planned_groupby_across_chips_matches_the_reference(
        dist_ctx, dist_ctx8, world, masked):
    ctx = dist_ctx if world == 4 else dist_ctx8
    # 4096 rows over 3000 keys: ~1.4 rows a group, 0-2 a shard
    config, tables = _data(4096, 3000, seed=2_147_483_659 + world)
    cols = tables[TRAFFIC["table"]]
    mask = None
    if masked:   # a third of the rows dead, as after a filter
        mask = np.random.default_rng(world).random(4096) > 1 / 3
        tables = {TRAFFIC["table"]: {k: v[mask] for k, v in cols.items()}}
    with telemetry.collect_phases() as cp:
        out = _planned(ctx, cols, mask).execute()
    assert cp.count("plan.shuffle.groupby") == 1
    assert cp.count("distributed_groupby.pre_aggregate") == 1
    got = _numbers(out, tables, config)
    assert [got[k] for k in EXACT] == [0] * len(EXACT), got
    assert got[FLOAT] <= 1.0, got


def test_four_hosts_shares_add_up_to_the_whole_table(dist_ctx):
    """The configuration holds ONE host's quarter of G1_1e9 and leaves
    out what the other twelve chips hold, in program and reference alike.
    What ties that share to the source: the four hosts' results, each the
    exact groupby of its contiguous quarter over the WHOLE key range,
    summed by key, are the reference's answer over the whole table."""
    config, tables = _data(8192, 3000, seed=3_000_000_019)
    whole = tables[TRAFFIC["table"]]
    nk = 3000 + 1
    sums = np.zeros((3, nk), np.float64)
    seen = np.zeros(nk, bool)
    for host in range(4):
        part = {k: v[host * 2048:(host + 1) * 2048]
                for k, v in whole.items()}
        out = _planned(dist_ctx, part).execute()
        mine = _numbers(out, {TRAFFIC["table"]: part}, config)
        assert [mine[k] for k in EXACT] == [0] * len(EXACT), (host, mine)
        got = RUN.host_result(out)
        keys = got["columns"][0]
        assert len(np.unique(keys)) == len(keys)   # once a host
        seen[keys] = True
        for j in range(3):
            sums[j, keys] += got["columns"][1 + j].astype(np.float64)
    keys = np.flatnonzero(seen)
    added = {"names": [TRAFFIC["by"]] + list(TRAFFIC["values"]),
             "nulls": 0,
             "columns": [keys.astype(np.int32)] + [
                 sums[j, keys].astype(whole[name].dtype)
                 for j, name in enumerate(TRAFFIC["values"])]}
    numbers = {n["name"]: n["value"] for n in REFERENCE.compare(
        added, REFERENCE.reference(tables, config, TRAFFIC))}
    assert [numbers[k] for k in EXACT] == [0] * len(EXACT), numbers
    assert numbers[FLOAT] <= 1.0, numbers


def test_bfloat16_control_fails_by_the_float_bound_alone():
    config, tables = _data(4096, 3000, seed=1_618_033_988)
    numbers = {n["name"]: n["value"] for n in REFERENCE.compare(
        REFERENCE.control(tables, config, TRAFFIC),
        REFERENCE.reference(tables, config, TRAFFIC))}
    assert [numbers[k] for k in EXACT] == [0] * len(EXACT), numbers
    assert numbers[FLOAT] > 100.0, numbers   # 2^15 on a one-row group


# the spans of one planned groupby across chips, in the order they open
SPANS = [
    "plan.query", "plan.scan", "plan.shuffle.groupby",
    "distributed_groupby.distribute",
    "distributed_groupby.pre_aggregate",
    "distributed_groupby.shuffle",
    "distributed_groupby.targets",
    "shuffle.payload", "shuffle.count", "sync.shuffle.count",
    "shuffle.route", "shuffle.exchange", "shuffle.unpack",
    "distributed_groupby.keybits",
    "distributed_groupby.aggregate",
    "distributed_groupby.finish",
]
LEAVES = ("distributed_groupby.distribute",
          "distributed_groupby.pre_aggregate",
          "distributed_groupby.targets", "distributed_groupby.keybits",
          "distributed_groupby.aggregate", "distributed_groupby.finish")


def _counted():
    snap = telemetry.metrics_snapshot()
    return {k: snap.get(k, 0) for k in (
        'cylon_groupby_phase_total{phase="partial"}',
        'cylon_groupby_phase_total{phase="merge"}',
        'cylon_groupby_phase_total{phase="single"}',
        "cylon_groupby_rows_in_total", "cylon_exchange_live_rows_total",
        'cylon_join_algorithm_total{algo="shuffle"}')}


def test_leaf_spans_once_a_query_and_the_counters(dist_ctx):
    config, tables = _data(4096, 3000, seed=2_718_281_828)
    pipe = _planned(dist_ctx, tables[TRAFFIC["table"]])
    before = _counted()
    with telemetry.collect_phases() as cp:
        out = pipe.execute()
    names = [s.name for s in cp.spans]
    assert [n for n in names if n in SPANS] == SPANS, names
    by_id = {s.span_id: s for s in cp.spans}
    for s in cp.spans:   # a leaf is a leaf: none encloses another
        at, above = s, []
        while at.parent_id in by_id:
            at = by_id[at.parent_id]
            above.append(at.name)
        if s.name.startswith(("distributed_groupby.", "shuffle.")):
            assert "plan.shuffle.groupby" in above, s.name
        assert not (s.name in LEAVES and set(above) & set(LEAVES)), s.name
        if s.name in ("distributed_groupby.targets", "shuffle.payload",
                      "shuffle.route", "shuffle.exchange", "shuffle.unpack"):
            assert "distributed_groupby.shuffle" in above, s.name
    after = _counted()
    moved = {k: after[k] - before[k] for k in after}
    groups = int(out.row_count)
    assert moved['cylon_groupby_phase_total{phase="partial"}'] == 1
    assert moved['cylon_groupby_phase_total{phase="merge"}'] == 1
    assert moved['cylon_groupby_phase_total{phase="single"}'] == 0
    assert moved["cylon_groupby_rows_in_total"] == 4096
    # the partial rows: one a (shard, group), so between the groups and
    # the rows; what dist_groupby_partial_share holds against the rows in
    assert groups <= moved["cylon_exchange_live_rows_total"] <= 4096
    assert moved['cylon_join_algorithm_total{algo="shuffle"}'] == 0


def test_groupby_without_pre_aggregation_is_one_single_phase(dist_ctx):
    config, tables = _data(2048, 3000, seed=5)
    t = ct.Table.from_pydict(dist_ctx, tables[TRAFFIC["table"]])
    before = _counted()
    with telemetry.collect_phases() as cp:
        out = dist_ops.distributed_groupby(
            t, 0, [1, 2, 3], [G.AggregationOp.SUM] * 3,
            pre_aggregate=False)
    got = _numbers(out, tables, config)
    assert [got[k] for k in EXACT] == [0] * len(EXACT), got
    moved = {k: v - before[k] for k, v in _counted().items()}
    assert moved['cylon_groupby_phase_total{phase="single"}'] == 1
    assert moved['cylon_groupby_phase_total{phase="partial"}'] == 0
    assert moved["cylon_exchange_live_rows_total"] == 2048   # every row
    assert cp.count("distributed_groupby.pre_aggregate") == 0
    assert cp.count("distributed_groupby.finish") == 1


def test_one_chip_groupby_opens_no_leaf_and_counts_no_phase(local_ctx):
    config, tables = _data(2048, 3000, seed=6)
    before = _counted()
    with telemetry.collect_phases() as cp:
        out = _planned(local_ctx, tables[TRAFFIC["table"]]).execute()
    got = _numbers(out, tables, config)
    assert [got[k] for k in EXACT] == [0] * len(EXACT), got
    assert not any(s.name.startswith("distributed_groupby.")
                   for s in cp.spans)
    assert _counted() == before


def test_stream_reduce_under_shard_map_equals_the_segment_path(
        dist_ctx, monkeypatch):
    """On a TPU `_groupby_fn`'s reduce step is the Pallas pass
    `groupby_run_reduce`, inside `shard_map`: the program the parent could
    not lower there (a `pallas_call`'s outputs carry no varying-mesh-axes
    annotation, so the checking `shard_map` raised while tracing). Here
    under the interpreter, both phases, against the `segment_*` scatters
    that every other CPU test takes."""
    config, tables = _data(2048, 1500, seed=7)
    cols = tables[TRAFFIC["table"]]
    want = RUN.host_result(_planned(dist_ctx, cols).execute())

    real = G.sorted_segment_aggregate
    paths = []

    def interpreted(*args, **kwargs):
        paths.append(G.reduce_path([v.dtype for v in args[3]], args[6],
                                   args[0].shape[0], interpret=True))
        return real(*args, **kwargs, interpret=True)

    monkeypatch.setattr(G, "sorted_segment_aggregate", interpreted)
    dist_ops._groupby_fn.cache_clear()
    try:
        got = RUN.host_result(_planned(dist_ctx, cols).execute())
    finally:
        dist_ops._groupby_fn.cache_clear()
    assert paths == ["stream", "stream"]   # the partial sums, the merge
    order_w, order_g = (np.argsort(r["columns"][0]) for r in (want, got))
    for j, (w, g) in enumerate(zip(want["columns"], got["columns"])):
        w, g = w[order_w], g[order_g]
        if w.dtype.kind == "f":   # a group's adds in another order
            np.testing.assert_allclose(g, w, rtol=1e-6)
        else:
            assert np.array_equal(g, w), j
    numbers = {n["name"]: n["value"] for n in REFERENCE.compare(
        got, REFERENCE.reference(tables, config, TRAFFIC))}
    assert [numbers[k] for k in EXACT] == [0] * len(EXACT), numbers
    assert numbers[FLOAT] <= 1.0, numbers


def _case_no_nulls(ctx, cols, rng):
    return _with_columns(ctx, ct.Table.from_pydict(ctx, cols)), [0], {}


def _case_nullable_key(ctx, cols, rng):
    t = ct.Table.from_pydict(ctx, cols)
    return _with_columns(ctx, t, {0: rng.random(t.capacity) > 0.05}), \
        [0], {}


def _case_two_keys(ctx, cols, rng):
    second = {"id4": (cols["id6"] % 3).astype(np.int16)}
    t = ct.Table.from_pydict(ctx, {**second, **cols})   # id4, id6, v1..v3
    return _with_columns(ctx, t), [0, 1], {}


def _case_masked(ctx, cols, rng):
    t = ct.Table.from_pydict(ctx, cols)
    return _with_columns(ctx, t, mask=rng.random(t.capacity) > 1 / 3), \
        [0], {}


def _case_single(ctx, cols, rng):
    t, keys, _kw = _case_no_nulls(ctx, cols, rng)
    return t, keys, {"pre_aggregate": False}


def _case_varbytes(ctx, cols, rng):
    names = np.array([f"customer-{k:08d}" for k in cols["id6"]], object)
    t = ct.Table.from_pydict(ctx, {**cols, "id6": names})
    assert t.get_column(0).is_varbytes
    return _with_columns(ctx, t), [0], {}


# case -> (its table, sort operands a query on the lanes path, on the
# gather path, dispatches of jit_groupby): the dead flag only with a row
# mask (PR 43: a table `distribute` did not have to pad has none, and
# after an exchange there always is one), then the key lanes, the values,
# their masks where they have one (PR 44: the partial sums of columns
# without nulls have none, their validity is the partial table's row
# mask; they had one each: + 8, + 9, + 10, + 12), and the index. The
# cell's shape is 4 + 5 against 6 + 7 (the chip packs the 4 into 2:
# tests/test_groupby_sort_pack.py)
KEY_READBACK_CASES = {
    "no_nulls": (_case_no_nulls, 4 + 5, 6 + 7, 2),
    "nullable_key": (_case_nullable_key, 5 + 6, 6 + 7, 2),
    "two_keys": (_case_two_keys, 5 + 6, 8 + 9, 2),
    "masked": (_case_masked, 5 + 5, 7 + 7, 2),
    "single": (_case_single, 5, 7, 1),
    "varbytes": (_case_varbytes, None, None, 2),
}


@pytest.mark.parametrize("case", list(KEY_READBACK_CASES))
def test_keys_off_the_sorted_lanes_equal_the_gathered_keys(
        dist_ctx, monkeypatch, case):
    """The per-shard step reads its groups' keys off the sorted key lanes
    where `G.sort_carries_index` lets it (PR 41: no row index, no stable
    sort, no gather over the rows; a key without nulls carries no
    validity lane and comes back without a mask), and gathers them from
    each group's first row where it does not: the same groups, keys bit
    for bit, integer sums equal, a float sum's adds in another order."""
    make, lanes_ops, gather_ops, steps = KEY_READBACK_CASES[case]
    _config, tables = _data(2048, 1500, seed=41)
    cols = dict(tables[TRAFFIC["table"]])
    t, keys, kw = make(dist_ctx, cols, np.random.default_rng(41))
    values = list(range(len(keys), len(keys) + 3))
    frames, moved = {}, {}
    for path in ("gather", "lanes"):
        with monkeypatch.context() as m:
            if path == "lanes":   # "gather": the CPU's own program
                _as_on_a_tpu(m)
            dist_ops._groupby_fn.cache_clear()
            before = _readback()
            try:
                out = dist_ops.distributed_groupby(
                    t, keys, values, [G.AggregationOp.SUM] * 3, **kw)
                frames[path] = out.to_pandas()
            finally:
                dist_ops._groupby_fn.cache_clear()
            moved[path] = [a - b for a, b in zip(_readback(), before)]
        if path == "lanes" and case != "varbytes":
            # a mask only where the key has nulls
            assert [out.get_column(i).validity is not None for i in keys] \
                == [case == "nullable_key"] * len(keys)
    if case == "varbytes":   # hash lanes have no way back: both gather
        assert moved["lanes"] == moved["gather"]
        assert moved["gather"][:2] == [0, steps]
    else:
        assert moved["lanes"] == [steps, 0, lanes_ops]
        assert moved["gather"] == [0, steps, gather_ops]
    by = list(frames["gather"].columns[:len(keys)])
    want, got = (f.sort_values(by, na_position="last").reset_index(drop=True)
                 for f in (frames["gather"], frames["lanes"]))
    assert len(want) == len(got)
    if case == "nullable_key":   # the null group once, on one shard
        assert got[by[0]].isna().sum() == want[by[0]].isna().sum() == 1
    for name in want.columns:
        w, g = want[name].to_numpy(), got[name].to_numpy()
        if w.dtype.kind == "f" and name not in by:
            np.testing.assert_allclose(g, w, rtol=1e-6)
        else:
            assert g.dtype == w.dtype and (
                (g == w) | ((g != g) & (w != w))).all(), name
    # and the plain oracle of the same rows
    live = t.to_pandas()
    oracle = live.groupby(list(live.columns[:len(keys)]), dropna=False)
    assert len(got) == oracle.ngroups
    assert int(got.iloc[:, len(keys)].sum()) == int(live.iloc[:, values[0]].sum())


# --------------------------------------------------------------------------
# the partial table's masks (PR 44): a partial aggregate whose validity
# would only repeat the partial table's row mask is built with validity
# None (`dist_ops._partial_masks_elided`: a COUNT, or a SUM / MIN / MAX over
# a source column without nulls), so it adds no leaf to the exchange and no
# mask operand to the merge's sort; a nullable source keeps its mask. The
# parent's path, every partial with a mask of its own, is the same code
# under a rule that elides nothing
# --------------------------------------------------------------------------

OPS = G.AggregationOp
MASKS = ("cylon_groupby_partial_masks_elided_total",
         "cylon_groupby_partial_masks_carried_total",
         "cylon_groupby_sort_operands_total")
# four shards of 512 rows: keys no random row has, placed by shard
HALF_NULL, ALL_NULL = 5000, 5001


def _null_groups(cols, valid):
    """HALF_NULL: null on shard 0 and valid (7, 9) on shard 1. ALL_NULL:
    rows on shards 0 and 2, every value null."""
    for row, key, ok, value in ((3, HALF_NULL, False, 100),
                                (9, HALF_NULL, False, 100),
                                (512 + 5, HALF_NULL, True, 7),
                                (512 + 6, HALF_NULL, True, 9),
                                (11, ALL_NULL, False, 100),
                                (1024 + 2, ALL_NULL, False, 100)):
        cols["id6"][row], valid[row] = key, ok
        for name in ("v1", "v2", "v3"):
            cols[name][row] = value
    return cols, valid


def _m_q5(ctx, rng):
    # the cell's query: three sums over columns without nulls. 4 columns
    # cross and no mask (7 leaves before); 2 + 5 operands
    return _spread(ctx, _q5_cols(rng)), [1, 2, 3], [OPS.SUM] * 3, dict(
        elided=3, carried=0, lanes=4, operands=2 + 5, parent_lanes=7,
        parent_operands=2 + 8)


def _m_nullable(ctx, rng):
    # a nullable value column keeps its mask leg and its any-valid pass
    # (id6 + v1 in one word and v1's mask; then dead flag, key lane, the
    # partial sum and its mask)
    cols, valid = _null_groups(_q5_cols(rng), rng.random(ROWS4) > 0.2)
    return _spread(ctx, cols, validity={1: valid}), [1], [OPS.SUM], dict(
        elided=0, carried=1, lanes=3, operands=2 + 4, parent_lanes=3,
        parent_operands=2 + 4, nullable=("v1",))


def _m_mixed(ctx, rng):
    # one nullable and two plain columns: 1 leg carried, 2 elided
    cols, valid = _null_groups(_q5_cols(rng), rng.random(ROWS4) > 0.2)
    return _spread(ctx, cols, validity={2: valid}), [1, 2, 3], \
        [OPS.SUM] * 3, dict(
            elided=2, carried=1, lanes=5, operands=3 + 6, parent_lanes=7,
            parent_operands=3 + 8, nullable=("v2",))


def _m_all_ops(ctx, rng):
    # MEAN + COUNT + MIN + MAX in one query, v2 nullable: MEAN(v2) is a
    # SUM that carries and a COUNT that does not; COUNT(v2) elides
    # whatever the column; MIN(v2) carries; MAX(v1), MEAN(v3) elide (the
    # 8-byte accumulators take the gather path on any backend)
    cols, valid = _null_groups(_q5_cols(rng), rng.random(ROWS4) > 0.2)
    return _spread(ctx, cols, validity={2: valid}), [2, 2, 2, 1, 3], [
        OPS.MEAN, OPS.COUNT, OPS.MIN, OPS.MAX, OPS.MEAN], dict(
            elided=5, carried=2, nullable=("v2",))


MASK_CASES = {f.__name__[3:]: f for f in (
    _m_q5, _m_nullable, _m_mixed, _m_all_ops)}


def _masks_counted():
    snap = telemetry.metrics_snapshot()
    return [snap.get(k, 0) for k in MASKS]


def _grouped(ctx, monkeypatch, t, values, ops, tpu):
    """(result table, [elided, carried, sort operands] counted, the
    payload's leaves) of one distributed groupby, as a TPU backend runs it
    or as the CPU does."""
    with monkeypatch.context() as m:
        m.setattr(G, "SORT_PACK_MIN_ROWS", 0)
        if tpu:
            _as_on_a_tpu(m)
        dist_ops._groupby_fn.cache_clear()
        before = _masks_counted()
        try:
            with telemetry.collect_phases() as cp:
                out = dist_ops.distributed_groupby(t, 0, values, ops)
        finally:
            dist_ops._groupby_fn.cache_clear()
        moved = [a - b for a, b in zip(_masks_counted(), before)]
    lanes, = [s.attrs["lanes"] for s in cp.spans
              if s.name == "shuffle.payload"]
    return out, moved, lanes


@pytest.mark.parametrize("backend", ["as_tpu", "cpu"])
@pytest.mark.parametrize("name", list(MASK_CASES))
def test_a_partial_mask_rides_only_where_it_says_more_than_the_row_mask(
        dist_ctx, monkeypatch, name, backend):
    """Which partial aggregates leave without a mask and which keep
    theirs (the two counters), what then crosses the exchange (the
    `shuffle.payload` span's leaves) and rides the merge's sort, and the
    result: equal to the parent's path (no mask elided) bit for bit, every
    result column's validity array and the row mask included, and to the
    plain oracle of the same rows, a group whose values are all null on
    every shard a null and one that is null on one shard only a value."""
    t, values, ops, want = MASK_CASES[name](
        dist_ctx, np.random.default_rng(len(name)))
    tpu = backend == "as_tpu"
    out, moved, lanes = _grouped(dist_ctx, monkeypatch, t, values, ops, tpu)
    with monkeypatch.context() as m:   # the parent: every partial a mask
        m.setattr(dist_ops, "_partial_masks_elided",
                  lambda ops, all_valid: (False,) * len(ops))
        parent, parent_moved, parent_lanes = _grouped(
            dist_ctx, m, t, values, ops, tpu)

    assert moved[:2] == [want["elided"], want["carried"]]
    assert parent_moved[:2] == [0, want["elided"] + want["carried"]]
    if tpu and "lanes" in want:   # the key lane has no mask leg there
        assert (lanes, parent_lanes) == (want["lanes"], want["parent_lanes"])
        assert (moved[2], parent_moved[2]) == (want["operands"],
                                               want["parent_operands"])
    # a leaf and a sort operand fewer an elided mask, on either path
    assert parent_lanes - lanes == want["elided"]
    assert parent_moved[2] - moved[2] == want["elided"]

    assert np.array_equal(np.asarray(out.row_mask),
                          np.asarray(parent.row_mask))
    for c, p in zip(out.columns(), parent.columns()):
        assert (c.name, c.dtype, c.validity is None) == (
            p.name, p.dtype, p.validity is None)
        assert np.asarray(c.data).tobytes() == np.asarray(p.data).tobytes()
        assert np.array_equal(np.asarray(c.valid_mask()),
                              np.asarray(p.valid_mask())), c.name
        # the user's result keeps its validity as `groupby_local` gives it
        assert c.validity is not None or c.name == "id6"

    live = t.to_pandas()
    got = _by_key(out.to_pandas())
    fn = {OPS.SUM: "sum", OPS.MEAN: "mean", OPS.COUNT: "count",
          OPS.MIN: "min", OPS.MAX: "max"}
    grouped = live.groupby("id6")
    assert list(got["id6"]) == sorted(grouped.groups)
    for j, (vi, op) in enumerate(zip(values, ops)):
        col = live.columns[vi]
        kw = {"min_count": 1} if op == OPS.SUM else {}
        ref = getattr(grouped[col], fn[op])(**kw).to_numpy(np.float64)
        mine = got.iloc[:, 1 + j].to_numpy(np.float64)
        np.testing.assert_allclose(mine, ref, rtol=1e-6, equal_nan=True)
        if col in want.get("nullable", ()) and op != OPS.COUNT:
            by = dict(zip(got["id6"], mine))
            assert np.isnan(by[ALL_NULL])
            assert by[HALF_NULL] == {OPS.SUM: 16, OPS.MEAN: 8, OPS.MIN: 7,
                                     OPS.MAX: 9}[op]


@pytest.mark.parametrize("block,row_bytes,plan", [
    (2 ** 22, 19, (2 ** 19, 8)), (2 ** 22, 16, (2 ** 20, 4)),
    (2_621_440, 19, (2 ** 19, 5)), (2_621_440, 16, (2 ** 20, 3))])
def test_the_cells_chunk_geometry_without_the_masks(monkeypatch, block,
                                                    row_bytes, plan):
    """`groupby-q5-w4`'s partial table crosses in blocks of 2,621,440
    slots a (source, target) pair (its 2.4955M rows on `util.capacity`'s
    grid, PR 52; 2^22 on the octave before), as ONE program since PR 48:
    its stacks (4 chips x 2^22 slots x 16 bytes, 4 buffers) were 1.07 GB
    of the 2.5 GB that a quarter of a v5e's free HBM is. Before, a
    64 MiB target cut it: at 4 x 4 bytes of data and 3 bool masks a row
    into 8 chunk programs of 2^19 rows, at 16 bytes into 4 of 2^20 (the
    grid block: 5 and 3, the last one ending with the block)."""
    import forced_paths
    from cylon_tpu import util
    from cylon_tpu.parallel import shuffle

    budget = 10_000_000_000 // 4
    assert block == util.capacity(block)
    assert shuffle._chunk_plan(block, 4, row_bytes, budget) == (block, 1)
    # the join cells' S side beside it: 4.81M rows a pair, two tables
    assert shuffle._chunk_plan(4_980_736, 4, 8, budget,
                               buffer_factor=8) == (4_980_736, 1)
    forced_paths.chunked(monkeypatch, 1 << 26)
    assert shuffle._chunk_plan(block, 4, row_bytes, budget) == plan
