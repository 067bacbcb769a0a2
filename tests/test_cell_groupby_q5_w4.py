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
against the `segment_*` path.
"""
import copy
import importlib.util
import json
import os
import sys

import numpy as np
import pytest

import jax.numpy as jnp

import cylon_tpu as ct
from cylon_tpu import plan, telemetry
from cylon_tpu.ops import groupby as G
from cylon_tpu.parallel import dist_ops, shard

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "benchmarks")


def _code(kind, name):
    spec = importlib.util.spec_from_file_location(
        f"q5w4_{kind}_{name}", os.path.join(BENCH, kind, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    path = list(sys.path)
    try:
        spec.loader.exec_module(mod)
    finally:
        sys.path[:] = path   # run.py puts its own directory first
    return mod


def _json(kind, name):
    with open(os.path.join(BENCH, kind, name + ".json")) as f:
        return json.load(f)


GENERATOR = _code("generators", "h2o_g1")
REFERENCE = _code("references", "groupby_sum_f64")
QUERY = _code("queries", "groupby_agg")
RUN = _code("", "run")          # host_result, as the harness reads a result
TRAFFIC = _json("traffic", "q5-4chip")
CONFIG = _json("configs", "h2o-groupby-1e9-f32")
EXACT = ("schema_diff", "nulls", "groups_diff", "int_sum_mismatches.v1",
         "int_sum_mismatches.v2")
FLOAT = "f32_sum_err_over_bound.v3"


def _data(rows, key_range, seed):
    """The cell's table at a test size: the configuration as committed,
    fewer rows, id6 over a literal range wider than a shard's rows."""
    config = copy.deepcopy(CONFIG)
    config["N"] = rows
    config["columns"]["id6"]["high"] = key_range
    return config, GENERATOR.generate(config, TRAFFIC, 4, 1.0,
                                      seed)["tables"]


def _numbers(out, tables, config):
    ref = REFERENCE.reference(tables, config, TRAFFIC)
    assert int(out.row_count) == REFERENCE.rows_out(ref)
    return {n["name"]: n["value"] for n in REFERENCE.compare(
        RUN.host_result(out), ref)}


def _planned(ctx, cols, mask=None):
    t = shard.distribute(ct.Table.from_pydict(ctx, cols), ctx)
    if mask is not None:
        live = np.zeros(t.capacity, bool)
        live[:len(mask)] = mask
        t = ct.Table(list(t.columns()), ctx, shard.pin(jnp.asarray(live),
                                                       ctx))
    return QUERY.build(plan, {TRAFFIC["table"]: t}, TRAFFIC)


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


READBACK = ('cylon_groupby_key_readback_total{path="lanes"}',
            'cylon_groupby_key_readback_total{path="gather"}',
            "cylon_groupby_sort_operands_total")


def _readback():
    snap = telemetry.metrics_snapshot()
    return [snap.get(k, 0) for k in READBACK]


def _as_on_a_tpu(monkeypatch):
    """The host's decision (`G.sort_carries_index`, which says "index" on
    a CPU backend) and `_groupby_fn`'s reduce step as a TPU backend takes
    them, under the interpreter."""
    real_agg, real_index = G.sorted_segment_aggregate, G.sort_carries_index
    monkeypatch.setattr(
        G, "sorted_segment_aggregate",
        lambda *a, **k: real_agg(*a, **k, interpret=True))
    monkeypatch.setattr(
        G, "sort_carries_index",
        lambda *a, **k: real_index(*a, **k, interpret=True))


def _with_columns(ctx, t, validity=None, mask=None):
    """``t`` spread over the chips, column i under ``validity[i]`` (host
    bool arrays over the capacity), rows under ``mask``."""
    t = shard.distribute(t, ctx)

    def pinned(a):
        return shard.pin(jnp.asarray(a), ctx)

    cols = [c if i not in (validity or {}) else ct.Column(
        c.data, c.dtype, pinned(validity[i]), c.dictionary, c.name)
        for i, c in enumerate(t.columns())]
    return ct.Table(cols, ctx, None if mask is None else pinned(mask))


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
# gather path, dispatches of jit_groupby): the dead flag always (after
# `distribute` the emit mask exists), then the key lanes, the values,
# their masks (the partial sums have one each), and the index. The
# cell's shape is 5 + 8 against 7 + 10
KEY_READBACK_CASES = {
    "no_nulls": (_case_no_nulls, 13, 17, 2),
    "nullable_key": (_case_nullable_key, 6 + 9, 17, 2),
    "two_keys": (_case_two_keys, 6 + 9, 9 + 12, 2),
    "masked": (_case_masked, 13, 17, 2),
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
