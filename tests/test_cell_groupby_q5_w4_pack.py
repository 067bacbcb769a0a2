"""The cell `groupby-q5-w4` at a test size on the CPU mesh, second half
(PR 43): what the first per-shard sort of the distributed groupby is
handed: no dead flag without a row mask, and integer columns inside the
key's word by a probe of the whole sharded table, as on one chip. The
first half is `tests/test_cell_groupby_q5_w4.py`; what both use is
`tests/cell_groupby_q5_w4_cases.py`.
"""
import numpy as np
import pytest

import cylon_tpu as ct
from cylon_tpu import plan, telemetry
from cylon_tpu.ops import groupby as G
from cylon_tpu.parallel import dist_ops, shard

from cell_groupby_q5_w4_cases import (
    ROWS4, I32_MIN, I32_MAX, _as_on_a_tpu, _by_key, _ints_of, _q5_cols,
    _spread)


# --------------------------------------------------------------------------
# across chips (PR 43): the distributed groupby's per-shard sort is handed
# what the one-chip sort is handed, by the same functions. The host probes
# the WHOLE sharded table before it dispatches the `shard_map` program
# (`dist_ops._sort_pack_probe` -> `table._sort_pack_probe`), the plan is a
# static argument of `_groupby_fn` and `params` a replicated operand
# --------------------------------------------------------------------------

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
