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
against the `segment_*` path. And (PR 43) what the first per-shard sort is
handed: no dead flag without a row mask, and integer columns inside the
key's word by a probe of the whole sharded table, as on one chip.
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
I32_MIN, I32_MAX = np.iinfo(np.int32).min, np.iinfo(np.int32).max
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
# across chips (PR 43): the distributed groupby's per-shard sort is handed
# what the one-chip sort is handed, by the same functions. The host probes
# the WHOLE sharded table before it dispatches the `shard_map` program
# (`dist_ops._sort_pack_probe` -> `table._sort_pack_probe`), the plan is a
# static argument of `_groupby_fn` and `params` a replicated operand
# --------------------------------------------------------------------------

ROWS4 = 2048        # four shards of 512: a multiple of the row quantum
DIST_SITES = ("groupby.packranges", "groupby.valuerange", "shuffle.count",
              "groupby.keyrange", "groupby.groups")


def _dist_counted():
    snap = telemetry.metrics_snapshot()
    out = {"operands": snap.get("cylon_groupby_sort_operands_total", 0),
           "packed": snap.get("cylon_groupby_sort_packed_columns_total", 0)}
    for phase in ("partial", "merge", "single"):
        out[phase] = snap.get(
            'cylon_groupby_phase_total{phase="%s"}' % phase, 0)
    for site in DIST_SITES:
        out[site] = snap.get('cylon_host_syncs_total{site="%s"}' % site, 0)
    return out


def _dist_delta(before):
    after = _dist_counted()
    return {k: after[k] - before[k] for k in after if after[k] != before[k]}


def _ints_of(rng, lo, hi, n=None):
    """int32 values over exactly [lo, hi]."""
    x = rng.integers(lo, hi + 1, n or ROWS4).astype(np.int32)
    x[:2] = (lo, hi)
    return x


def _q5_cols(rng, n=ROWS4, key=None, v1=None):
    """The cell's columns at a test size: id6 over a range wider than a
    shard's rows, v1 in [1, 5], v2 in [1, 15], v3 whole floats (their
    sums do not feel the order of a group's rows)."""
    return {"id6": _ints_of(rng, 1, 3000, n) if key is None else key,
            "v1": _ints_of(rng, 1, 5, n) if v1 is None else v1,
            "v2": _ints_of(rng, 1, 15, n),
            "v3": rng.integers(-64, 64, n).astype(np.float32)}


def _spread(ctx, cols, validity=None, mask=None):
    return _with_columns(ctx, ct.Table.from_pydict(ctx, cols), validity,
                         mask)


def _d_no_row_mask(ctx, rng):
    # (a) the cell's shape: no mask, so no dead flag; key + v1 + v2 in one
    # word and v3: 2 operands, then the merge's 5 (dead flag, key lane,
    # three partial sums and none of their masks: 8 before PR 44)
    return _spread(ctx, _q5_cols(rng)), {}, dict(
        operands=2 + 5, packed=2, site="groupby.packranges")


def _d_filtered(ctx, rng):
    # (b) a row mask (a third of the rows dead, as after a filter; dead
    # rows hold keys far outside the live rows' range): the flag rides
    cols = _q5_cols(rng)
    mask = rng.random(ROWS4) > 1 / 3
    mask[:2] = True
    cols["id6"] = np.where(mask, cols["id6"], rng.choice(
        [I32_MIN, I32_MAX, -1, 70_000], ROWS4)).astype(np.int32)
    return _spread(ctx, cols, mask=mask), {}, dict(
        operands=3 + 5, packed=2, site="groupby.packranges")


def _d_padded(ctx, rng):
    # (b) rows that `distribute` has to pad: the padding's mask and flag
    t = shard.distribute(
        ct.Table.from_pydict(ctx, _q5_cols(rng, ROWS4 - 3)), ctx)
    assert t.row_mask is not None
    return t, {}, dict(operands=3 + 5, packed=2, site="groupby.packranges")


def _d_nullable_key(ctx, rng):
    # (c) a nullable key rides as two lanes and is not observed: the
    # value columns share a word among themselves, probed alone
    t = _spread(ctx, _q5_cols(rng), validity={0: rng.random(ROWS4) > 0.05})
    return t, {}, dict(operands=4 + 6, packed=1, site="groupby.valuerange")


def _d_wide_range(ctx, rng):
    # (d) v1 over all of int32 does not fit and rides alone, whole; v2
    # still rides with the key
    v1 = rng.integers(I32_MIN, I32_MAX, ROWS4).astype(np.int32)
    v1[:2] = (I32_MIN, I32_MAX)
    return _spread(ctx, _q5_cols(rng, v1=v1)), {}, dict(
        operands=3 + 5, packed=1, site="groupby.packranges", exact_v1=False)


def _d_negative(ctx, rng):
    # (e) negative v1 and a key whose range starts below zero
    cols = _q5_cols(rng, key=_ints_of(rng, -9000, -4000),
                    v1=_ints_of(rng, -1000, -990))
    return _spread(ctx, cols), {}, dict(
        operands=2 + 5, packed=2, site="groupby.packranges")


def _d_dictionary_key(ctx, rng):
    # (e) dictionary codes are their own lanes
    words = np.array([f"w{i:04d}" for i in range(900)], object)
    cols = _q5_cols(rng, key=words[rng.integers(0, 900, ROWS4)])
    t = _spread(ctx, cols)
    assert t.get_column(0).is_string and not t.get_column(0).is_varbytes
    return t, {}, dict(operands=2 + 5, packed=2, site="groupby.packranges")


def _d_empty_shard(ctx, rng):
    # (f) a shard with no live row: the ranges are the whole table's
    mask = np.ones(ROWS4, bool)
    mask[ROWS4 // 2:3 * ROWS4 // 4] = False
    return _spread(ctx, _q5_cols(rng), mask=mask), {}, dict(
        operands=3 + 5, packed=2, site="groupby.packranges")


def _d_single_after_exchange(ctx, rng):
    # no pre-aggregation: the rows themselves cross, observed behind the
    # exchange (always a mask there)
    return _spread(ctx, _q5_cols(rng)), {"pre_aggregate": False}, dict(
        operands=3, packed=2, site="groupby.packranges", phases=("single",))


def _d_single_in_place(ctx, rng):
    # the elided groupby after a join on the same keys: every key's rows
    # on ONE shard already (here by construction), one step, no exchange,
    # no mask
    key = (np.arange(ROWS4) // (ROWS4 // 4) * 10_000
           + rng.integers(1, 700, ROWS4)).astype(np.int32)
    return _spread(ctx, _q5_cols(rng, key=key)), {
        "pre_partitioned": True}, dict(
            operands=2, packed=2, site="groupby.packranges",
            phases=("single",), exchange=False)


DIST_CASES = {f.__name__[3:]: f for f in (
    _d_no_row_mask, _d_filtered, _d_padded, _d_nullable_key, _d_wide_range,
    _d_negative, _d_dictionary_key, _d_empty_shard,
    _d_single_after_exchange, _d_single_in_place)}


def _by_key(frame):
    return frame.sort_values(frame.columns[0], na_position="last"
                             ).reset_index(drop=True)


@pytest.mark.parametrize("name", list(DIST_CASES))
def test_four_shard_groupby_packs_as_the_one_chip_sort_does(
        dist_ctx, monkeypatch, name):
    """The four-shard groupby with the packing on (as a TPU backend runs
    it: the key one lane, under the interpreter) equals the same query
    with the packing off (the CPU's own program) and the plain oracle of
    the same rows; and what the host counted: the operands of both
    phases, the packed columns, ONE fetch of the ranges under a sync span
    of its own, once a query."""
    t, kw, want = DIST_CASES[name](dist_ctx, np.random.default_rng(len(name)))
    SUM = [G.AggregationOp.SUM] * 3
    monkeypatch.setattr(G, "SORT_PACK_MIN_ROWS", 1 << 40)
    before = _dist_counted()
    plain = _by_key(dist_ops.distributed_groupby(
        t, 0, [1, 2, 3], SUM, **kw).to_pandas())
    plain_moved = _dist_delta(before)
    assert "packed" not in plain_moved and not (
        {"groupby.packranges", "groupby.valuerange"} & set(plain_moved))

    monkeypatch.setattr(G, "SORT_PACK_MIN_ROWS", 0)
    with monkeypatch.context() as m:
        _as_on_a_tpu(m)
        dist_ops._groupby_fn.cache_clear()
        before = _dist_counted()
        try:
            with telemetry.collect_phases() as cp:
                packed = _by_key(dist_ops.distributed_groupby(
                    t, 0, [1, 2, 3], SUM, **kw).to_pandas())
        finally:
            dist_ops._groupby_fn.cache_clear()
        moved = _dist_delta(before)

    phases = want.get("phases", ("partial", "merge"))
    assert {p: moved.get(p, 0) for p in ("partial", "merge", "single")} == {
        p: int(p in phases) for p in ("partial", "merge", "single")}
    assert moved["operands"] == want["operands"]
    assert moved["packed"] == want["packed"]
    # the probe's fetch and the exchange's count: two a query in the
    # cell's shape, and no other
    fetched = {k: v for k, v in moved.items() if k in DIST_SITES}
    assert fetched == {want["site"]: 1, **(
        {"shuffle.count": 1} if want.get("exchange", True) else {})}
    assert cp.count("sync." + want["site"]) == 1

    assert list(packed.columns) == list(plain.columns)
    assert len(packed) == len(plain)
    for col in plain.columns:
        g, w = packed[col].to_numpy(), plain[col].to_numpy()
        assert g.dtype == w.dtype
        # whole floats: a sum does not feel the order of a group's rows
        assert ((g == w) | ((g != g) & (w != w))).all(), col
    live = t.to_pandas()
    oracle = live.groupby(live.columns[0], dropna=False)
    assert len(packed) == oracle.ngroups
    if want.get("exact_v1", True):
        assert int(packed.iloc[:, 1].sum()) == int(live.iloc[:, 1].sum())
    assert int(packed.iloc[:, 2].sum()) == int(live.iloc[:, 2].sum())
    assert float(packed.iloc[:, 3].sum()) == float(live.iloc[:, 3].sum())


def test_the_planned_groupby_across_chips_probes_once_and_packs(
        dist_ctx, monkeypatch):
    """Through the plan, as the cell `groupby-q5-w4` runs it: the probe's
    span opens once, inside `distributed_groupby.pre_aggregate`, the
    operands are 2 + 5 with two packed columns (the partial sums carry no
    mask: PR 44), and the fetches a query are two (the ranges, the
    exchange's counts)."""
    monkeypatch.setattr(G, "SORT_PACK_MIN_ROWS", 0)
    _as_on_a_tpu(monkeypatch)
    rng = np.random.default_rng(43)
    t = _spread(dist_ctx, _q5_cols(rng))
    pipe = plan.scan(t).groupby("id6", ["v1", "v2", "v3"], ["sum"] * 3)
    dist_ops._groupby_fn.cache_clear()
    before = _dist_counted()
    try:
        with telemetry.collect_phases() as cp:
            out = pipe.execute()
    finally:
        dist_ops._groupby_fn.cache_clear()
    moved = _dist_delta(before)
    assert moved == {"operands": 7, "packed": 2, "partial": 1, "merge": 1,
                     "groupby.packranges": 1, "shuffle.count": 1}
    syncs = [s for s in cp.spans if s.name.startswith("sync.")]
    assert sorted(s.name for s in syncs) == ["sync.groupby.packranges",
                                             "sync.shuffle.count"]
    by_id = {s.span_id: s for s in cp.spans}
    probe = next(s for s in syncs if s.name == "sync.groupby.packranges")
    assert by_id[probe.parent_id].name == "distributed_groupby.pre_aggregate"
    live = t.to_pandas()
    assert out.row_count == live["id6"].nunique()


def test_a_shard_under_the_row_gate_pays_no_probe(dist_ctx):
    """Four shards of 512 rows are under SORT_PACK_MIN_ROWS a SHARD: the
    sorts carry what they always did, less the dead flag that a table
    without a row mask never needed and the masks that the partial sums
    of columns without nulls never needed (the CPU's gather path: 6 + 7;
    6 + 10 before PR 44)."""
    assert ROWS4 // 4 < G.SORT_PACK_MIN_ROWS
    t = _spread(dist_ctx, _q5_cols(np.random.default_rng(3)))
    before = _dist_counted()
    out = t.groupby(0, [1, 2, 3], ["sum"] * 3)
    assert out.row_count >= 2
    assert _dist_delta(before) == {"operands": 6 + 7, "partial": 1,
                                   "merge": 1, "shuffle.count": 1}


def test_the_gather_path_packs_the_values_among_themselves(
        dist_ctx, monkeypatch):
    """On the CPU the per-shard sort carries the row index and the key's
    mask lane beside its bits (two key lanes: the key is not observed),
    so v1 and v2 share a word of their own: 5 + 7 for 6 + 7 (the merge
    carries the dead flag, the key's two lanes, the three partial sums
    and the index: the sums' three masks rode too before PR 44)."""
    monkeypatch.setattr(G, "SORT_PACK_MIN_ROWS", 0)
    t = _spread(dist_ctx, _q5_cols(np.random.default_rng(4)))
    before = _dist_counted()
    out = t.groupby(0, [1, 2, 3], ["sum"] * 3)
    moved = _dist_delta(before)
    assert moved == {"operands": 5 + 7, "packed": 1, "partial": 1,
                     "merge": 1, "groupby.valuerange": 1, "shuffle.count": 1}
    live = t.to_pandas()
    got = out.to_pandas()
    assert len(got) == live["id6"].nunique()
    for name in ("v1", "v2", "v3"):
        assert float(got[name].sum()) == float(live[name].sum())


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


@pytest.mark.parametrize("row_bytes,plan", [(19, (2 ** 19, 8)),
                                            (16, (2 ** 20, 4))])
def test_the_cells_chunk_geometry_without_the_masks(row_bytes, plan):
    """`groupby-q5-w4`'s partial table crosses in blocks of 2^22 slots a
    (source, target) pair: at 4 x 4 bytes of data and 3 bool masks a row
    the exchange cut it into 8 chunk programs of 2^19 rows, at 16 bytes
    into 4 of 2^20."""
    from cylon_tpu.parallel import shuffle

    assert shuffle._chunk_plan(2 ** 22, 4, row_bytes) == plan
