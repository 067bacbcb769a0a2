"""Semi and anti joins (PR 49): SQL's ``EXISTS`` / ``NOT EXISTS``.

``JoinType.SEMI`` keeps a left row when at least one live right row has an
equal, non-null key, ``JoinType.ANTI`` when none has: a left row whose key
is null is kept by ANTI and dropped by SEMI, null keys on the right match
nothing, each kept row comes out once, and the result holds the left's
columns only. Every case is held to a plain numpy reference
(``left[left.key.isin(right.key)]`` and its complement, over live rows and
valid keys) as multisets of rows, on one device through the XLA plan and
through the stream path on the Pallas interpreter, and on the four-shard
CPU mesh through `distributed_join` and `LazyTable.execute()`.
"""
from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import cylon_tpu as ct
from cylon_tpu import plan, telemetry
from cylon_tpu.data.column import Column
from cylon_tpu.ops import join as _join
from cylon_tpu.parallel import shard
from cylon_tpu.plan import col
from cylon_tpu.status import CylonError, CylonPlanError

I32 = np.int32
KINDS = ("semi", "anti")


def _side(rng, n, lo, hi, key="int32", mask=False, nulls=False,
          payload="v"):
    """One side as host arrays: key(s), a payload, a row mask, a key
    validity (None where the case has none)."""
    if key == "int32":
        keys = [rng.integers(lo, hi, n).astype(I32)]
    elif key == "int64":
        # keys that differ in the high word alone, and in the low alone
        keys = [(rng.integers(lo, hi, n).astype(np.int64) << 32)
                + rng.integers(0, 3, n)]
    elif key == "str":
        keys = [np.array([f"k{v:03d}" for v in rng.integers(lo, hi, n)])]
    else:   # "two": a two-column key
        keys = [rng.integers(lo, hi, n).astype(I32),
                rng.integers(0, 3, n).astype(I32)]
    return {"keys": keys,
            "payload": rng.integers(-99, 99, n).astype(I32),
            "mask": rng.random(n) > 0.4 if mask else None,
            "valid": rng.random(n) > 0.3 if nulls else None,
            "name": payload}


def _table(ctx, side, distribute=False):
    cols = [Column.from_numpy(k, f"k{i}", side["valid"] if i == 0 else None)
            for i, k in enumerate(side["keys"])]
    cols.append(Column.from_numpy(side["payload"], side["name"]))
    mask = None if side["mask"] is None else jnp.asarray(side["mask"])
    t = ct.Table(cols, ctx, mask)
    return shard.distribute(t, ctx) if distribute else t


def _reference(left, right, how):
    """The rows a semi / anti join keeps, as a multiset of tuples (a null
    key reads None)."""
    n_l, n_r = len(left["payload"]), len(right["payload"])
    llive = np.ones(n_l, bool) if left["mask"] is None else left["mask"]
    rlive = np.ones(n_r, bool) if right["mask"] is None else right["mask"]
    lvalid = np.ones(n_l, bool) if left["valid"] is None else left["valid"]
    rvalid = np.ones(n_r, bool) if right["valid"] is None \
        else right["valid"]
    rkeys = {tuple(k[j].item() for k in right["keys"])
             for j in np.flatnonzero(rlive & rvalid)}
    rows = Counter()
    for i in np.flatnonzero(llive):
        key = tuple(k[i].item() for k in left["keys"])
        hit = bool(lvalid[i]) and key in rkeys
        if hit == (how == "semi"):
            shown = (None if not lvalid[i] else key[0],) + key[1:]
            rows[shown + (int(left["payload"][i]),)] += 1
    return rows


def _rows(table):
    df = table.to_pandas()
    return Counter(tuple(None if v is None or v != v else
                         (v if isinstance(v, str) else int(v))
                         for v in row)
                   for row in df.itertuples(index=False))


def _check(out, left, right, how):
    assert out.column_names == [f"lt-{i}" for i in
                                range(len(left["keys"]) + 1)]
    assert _rows(out) == _reference(left, right, how)


# the cases of Tentpole 2: name -> (key kind, masks, nulls, left key range,
# right key range)
CASES = {
    "a_duplicates": ("int32", False, False, (0, 40), (20, 60)),
    "b_row_masks": ("int32", True, False, (0, 40), (20, 60)),
    "c_null_keys": ("int32", True, True, (0, 40), (20, 60)),
    "d_int64_planes": ("int64", True, False, (-20, 20), (0, 40)),
    "e_dictionary_string": ("str", True, True, (0, 40), (20, 60)),
    "f_two_column_key": ("two", True, False, (0, 12), (6, 18)),
    "g_no_match": ("int32", False, False, (0, 40), (100, 140)),
    "g_all_match": ("int32", False, False, (10, 20), (0, 40)),
}


def _case(name, seed=5, n_left=300, n_right=220):
    key, masks, nulls, lrange, rrange = CASES[name]
    rng = np.random.default_rng([seed, sorted(CASES).index(name)])
    left = _side(rng, n_left, *lrange, key=key, mask=masks, nulls=nulls)
    right = _side(rng, n_right, *rrange, key=key, mask=masks, nulls=nulls,
                  payload="w")
    return left, right


def _on(left):
    return [f"k{i}" for i in range(len(left["keys"]))]


@pytest.fixture
def stream(monkeypatch):
    """The stream path off a TPU: the Pallas interpreter."""
    monkeypatch.setattr(_join, "STREAM_PLAN", True)


@pytest.mark.parametrize("how", KINDS)
@pytest.mark.parametrize("name", sorted(CASES))
def test_local_xla_plan(local_ctx, name, how):
    left, right = _case(name)
    x64 = name != "d_int64_planes"     # the chip's word planes: x64 off
    with jax.enable_x64(x64):
        out = _table(local_ctx, left).join(_table(local_ctx, right), how,
                                           on=_on(left))
        # the left table under a new row mask: its capacity, no gather
        assert out.capacity == len(left["payload"])
        _check(out, left, right, how)


@pytest.mark.parametrize("how", KINDS)
@pytest.mark.parametrize("name", sorted(CASES))
def test_local_stream_path(local_ctx, stream, name, how):
    left, right = _case(name, seed=6)
    x64 = name != "d_int64_planes"
    before = telemetry.metrics_snapshot()
    with jax.enable_x64(x64):
        lt, rt = _table(local_ctx, left), _table(local_ctx, right)
        if name == "d_int64_planes":
            assert lt._columns[0].is_planes
        out = lt.join(rt, how, on=_on(left))
        assert out.capacity == len(left["payload"])
        _check(out, left, right, how)
    now = telemetry.metrics_snapshot()

    def grew(prefix):
        return sum(v - before.get(k, 0) for k, v in now.items()
                   if k.startswith(prefix) and isinstance(v, (int, float)))

    assert grew("cylon_join_semi_total") == 1
    assert grew(f'cylon_join_semi_total{{kind="{how}"}}') == 1
    assert grew("cylon_join_plan_sort_rows_total") \
        == len(left["payload"]) + len(right["payload"])
    assert grew("cylon_join_expand_sweep_rows_total") == 0
    # the sort path fetches nothing; the hash path its collision count
    fetches = grew('cylon_host_syncs_total{site="join.count"}')
    assert fetches == (1 if name == "f_two_column_key" else 0)


@pytest.mark.parametrize("how", KINDS)
def test_stream_result_is_a_prefix_in_key_order(local_ctx, stream, how):
    """The stream path's result: the kept rows compacted in key order into
    the first slots of the LEFT side's capacity, the row mask a prefix."""
    left, right = _case("a_duplicates", seed=7)
    out = _table(local_ctx, left).join(_table(local_ctx, right), how,
                                       on=["k0"])
    mask = np.asarray(out.row_mask)
    n = int(mask.sum())
    assert mask[:n].all() and not mask[n:].any()
    keys = np.asarray(out._columns[0].data)[:n]
    assert (np.diff(keys) >= 0).all()
    assert out._columns[0].validity is None     # as the left's was


def test_key_rides_once_on_the_left_alone():
    """A semi join's build side has no lane: the key rides as the sort's
    key bits where the LEFT side alone allows it, whatever the right's
    validity; an inner join needs both."""
    k = jnp.zeros(8, jnp.int32)
    v = jnp.ones(8, bool)
    a, b = _join.plan_lane_descs((k, k), (None, None), (), (),
                                 _join.JoinType.SEMI, 0, None)
    assert a == ((0, "k"), (1, "d")) and b == ()
    a, b = _join.plan_lane_descs((k, k), (v, None), (), (),
                                 _join.JoinType.ANTI, 0, None)
    assert a == ((0, "d"), (0, "v"), (1, "d")) and b == ()
    a, b = _join.plan_lane_descs((k, k), (None, None), (k,), (v,),
                                 _join.JoinType.INNER, 0, 0)
    assert a[0] == (0, "d") and b[0] == (0, "d")
    # sort operands: key bits + tag + one slot; an inner join's is the
    # wider side's
    assert _join.plan_sort_operand_count(
        (k,), (False,), ((0, "k"), (1, "d")), ()) == 3


@pytest.mark.parametrize("how", KINDS)
def test_blocked_probe_side(local_ctx, how):
    left, right = _case("c_null_keys", seed=8)
    out = _table(local_ctx, left).join(_table(local_ctx, right), how,
                                       on=["k0"], probe_block_rows=64)
    _check(out, left, right, how)


@pytest.mark.parametrize("how", KINDS)
@pytest.mark.parametrize("name", ["a_duplicates", "b_row_masks",
                                  "c_null_keys", "f_two_column_key"])
def test_distributed_join(dist_ctx, name, how):
    left, right = _case(name, seed=9)
    lt = _table(dist_ctx, left, distribute=True)
    rt = _table(dist_ctx, right, distribute=True)
    before = telemetry.metrics_snapshot()
    out = lt.distributed_join(rt, how, on=_on(left))
    _check(out, left, right, how)
    now = telemetry.metrics_snapshot()
    site = 'cylon_host_syncs_total{site="join.plan"}'
    assert now.get(site, 0) == before.get(site, 0)   # no counts gather
    # the kept rows sit where the exchange put them: the left's witness
    if name != "c_null_keys":
        sig = shard.partition_signature(
            [out._columns[i] for i in range(len(left["keys"]))],
            tuple(range(len(left["keys"]))), 4)
        assert out._hash_partitioned == sig


@pytest.mark.parametrize("how", KINDS)
def test_broadcast_falls_back_to_the_shuffle_path(dist_ctx, how):
    """No side of a semi / anti join may be replicated in this PR."""
    from cylon_tpu.parallel import dist_ops
    from cylon_tpu.plan import optimizer, verify

    jt = _join.JoinType[how.upper()]
    assert jt not in dist_ops._BCAST_LEGAL_SIDES
    assert how not in optimizer._BROADCAST_SIDES
    assert how not in verify._BROADCAST_SIDES
    left, right = _case("b_row_masks", seed=10)
    lt = _table(dist_ctx, left, distribute=True)
    rt = _table(dist_ctx, right, distribute=True)
    with telemetry.collect_phases() as cp:
        out = lt.distributed_join(rt, how, on=["k0"], comm="broadcast")
    _check(out, left, right, how)
    names = [label.split("#")[0] for label in cp.labels]
    assert "distributed_join.plan" in names
    assert "distributed_join.materialize" not in names
    assert not any(name.startswith("broadcast_join") for name in names)


@pytest.mark.parametrize("world", [1, 4])
@pytest.mark.parametrize("how", KINDS)
@pytest.mark.parametrize("name", ["a_duplicates", "b_row_masks",
                                  "c_null_keys"])
def test_lazy_execute(local_ctx, dist_ctx, name, how, world):
    ctx = local_ctx if world == 1 else dist_ctx
    left, right = _case(name, seed=11)
    lt = _table(ctx, left, distribute=world > 1)
    rt = _table(ctx, right, distribute=world > 1)
    q = plan.scan(lt).join(plan.scan(rt), how, on="k0")
    assert q.schema == ["lt-0", "lt-1"]
    _check(q.execute(), left, right, how)
    _check(q.execute(optimize=False), left, right, how)


# ---------------------------------------------------------------------------
# the plan
# ---------------------------------------------------------------------------

def _sides(ctx, distribute=False):
    r = np.random.default_rng(12)
    left = ct.Table.from_pydict(ctx, {
        "k": r.integers(0, 40, 160).astype(I32),
        "x": r.integers(0, 10, 160).astype(I32),
        "y": r.integers(0, 10, 160).astype(I32)})
    right = ct.Table.from_pydict(ctx, {
        "k": r.integers(20, 60, 120).astype(I32),
        "u": r.integers(0, 10, 120).astype(I32),
        "w": r.integers(0, 10, 120).astype(I32)})
    if distribute:
        left, right = shard.distribute(left, ctx), shard.distribute(right,
                                                                    ctx)
    return left, right


def _int_rows(table):
    return sorted(tuple(int(v) for v in row)
                  for row in table.to_pandas().itertuples(index=False))


def _nodes(root, kind):
    return [root] * isinstance(root, kind) + [
        x for c in root.children for x in _nodes(c, kind)]


@pytest.mark.parametrize("world", [1, 4])
@pytest.mark.parametrize("how", KINDS)
def test_conjuncts_above_go_below_on_the_left_and_the_right_is_pruned(
        local_ctx, dist_ctx, how, world):
    ctx = local_ctx if world == 1 else dist_ctx
    left, right = _sides(ctx, distribute=world > 1)
    q = plan.scan(left).join(plan.scan(right), how, on="k").filter(
        (col("lt-1") < 7) & (col("lt-1") <= col("lt-2")))
    assert q.schema == ["lt-0", "lt-1", "lt-2"]
    root, stats = q.optimized()
    assert stats.filters_below_join == 2
    (join,) = _nodes(root, plan.Join)
    assert join.how == how
    # both conjuncts under the join's LEFT side, none above it or right
    assert not isinstance(root, plan.Filter)
    assert len(_nodes(join.children[0], plan.Filter)) == 1
    assert not _nodes(join.children[1], plan.Filter)
    # the right side reaches the join as its key column alone
    assert join.children[1].width == 1 and join.right_on == [0]
    assert how in q.explain()
    before = telemetry.metrics_snapshot()
    got = _int_rows(q.execute())
    now = telemetry.metrics_snapshot()
    name = "cylon_plan_filters_below_join_total"
    assert now.get(name, 0) - before.get(name, 0) == 2
    assert got == _int_rows(q.execute(optimize=False))
    lp, rp = left.to_pandas(), right.to_pandas()
    isin = lp.k.isin(rp.k)
    want = lp[(isin if how == "semi" else ~isin) & (lp.x < 7)
              & (lp.x <= lp.y)]
    assert 0 < len(want) < len(lp)
    assert got == sorted(map(tuple, want.values.tolist()))


@pytest.mark.parametrize("how", KINDS)
def test_a_right_column_cannot_be_named_above(local_ctx, how):
    left, right = _sides(local_ctx)
    q = plan.scan(left).join(plan.scan(right), how, on="k")
    assert q.column_count == 3
    with pytest.raises(CylonPlanError):
        q.filter(col("rt-4") > 6)
    with pytest.raises(CylonPlanError):
        q.project(["lt-0", "rt-3"])
    with pytest.raises(CylonPlanError):
        plan.scan(left).join(plan.scan(right), "right_semi", on="k")


@pytest.mark.parametrize("shape,world", [("union", 1), ("join", 1),
                                         ("union", 4)])
@pytest.mark.parametrize("how", KINDS)
def test_a_semi_join_under_two_parents_is_filtered_under_one_alone(
        local_ctx, dist_ctx, how, shape, world):
    """`j.filter(p).union(j)` holds the ONE `Join` node twice: the conjuncts
    pushed for the filtered branch may not reach the other (PR 46's HIGH
    finding, for the two new kinds)."""
    ctx = local_ctx if world == 1 else dist_ctx
    left, right = _sides(ctx, distribute=world > 1)
    j = plan.scan(left).join(plan.scan(right), how, on="k")
    f = j.filter((col("lt-1") < 3) & (col("lt-2") > 2))
    q = f.union(j) if shape == "union" else f.join(j, on="lt-0")
    root, stats = q.optimized()
    assert stats.filters_below_join == 2

    def filtered(n):
        return isinstance(n, plan.Filter) or any(
            filtered(c) for c in n.children)

    semis = [n for n in _nodes(root, plan.Join) if n.how == how]
    assert len(semis) == 2, q.explain()
    assert sorted(filtered(n) for n in semis) == [False, True], q.explain()
    assert _int_rows(q.execute()) == _int_rows(q.execute(optimize=False))
    lp, rp = left.to_pandas(), right.to_pandas()
    isin = lp.k.isin(rp.k)
    m = lp[isin if how == "semi" else ~isin]
    kept = m[(m.x < 3) & (m.y > 2)]
    assert 0 < len(kept) < len(m)
    n_rows = len(_int_rows(q.execute()))
    if shape == "union":
        assert n_rows == len(m.drop_duplicates())
    else:
        assert n_rows == len(kept.merge(m, on="k"))


def test_estimate_and_witness_follow_the_left_side(dist_ctx):
    from cylon_tpu.plan import report

    left, right = _sides(dist_ctx, distribute=True)
    q = plan.scan(left).join(plan.scan(right), "semi", on="k")
    est = report.preflight_estimates(q._node)
    assert est[id(q._node)]["rows"] == left.capacity
    # a groupby on the join key above a semi join needs no exchange of
    # its own, as above an inner or left join
    g = q.groupby("lt-0", ["lt-1"], ["sum"])
    root, stats = g.optimized()
    assert stats.groupbys_localized == 1
    got = g.execute().to_pandas()
    lp, rp = left.to_pandas(), right.to_pandas()
    want = lp[lp.k.isin(rp.k)].groupby("k").x.sum()
    assert dict(zip(got.iloc[:, 0], got.iloc[:, 1])) == want.to_dict()


def test_the_reference_has_neither_kind():
    assert [t.name for t in _join.JoinType] == [
        "INNER", "LEFT", "RIGHT", "FULL_OUTER", "SEMI", "ANTI"]
    assert "join_config.hpp" in _join.JoinType.__doc__
    cfg = _join.JoinConfig.SemiJoin(0, 1)
    assert cfg.type == _join.JoinType.SEMI and _join.is_semi(cfg.type)
    assert _join.JoinConfig.AntiJoin(0, 1).type == _join.JoinType.ANTI
    assert not _join.is_semi(_join.JoinType.LEFT)


def _long_key_table(ctx, lo, hi, seed):
    from cylon_tpu.data.strings import VarBytes

    rng = np.random.default_rng(seed)
    pool = [f"key-{i:04d}-" + "q" * 24 for i in range(64)]   # > 20 bytes
    draws = rng.integers(lo, hi, 200)
    t = ct.Table([
        Column.from_varbytes(VarBytes.from_host([pool[i] for i in draws]),
                             None, "k"),
        Column.from_numpy(np.arange(200).astype(I32), "v")], ctx)
    return t, draws


@pytest.mark.parametrize("how", KINDS)
def test_long_varbytes_key_local_exact_and_not(local_ctx, how):
    """A key past the word lanes joins on its content hash; exact=True
    joins on shared dictionary codes instead (no matched pair comes out of
    a semi join to byte-verify afterwards). The payload rides along."""
    left, ldraws = _long_key_table(local_ctx, 0, 48, 13)
    right, rdraws = _long_key_table(local_ctx, 16, 64, 14)
    isin = np.isin(ldraws, rdraws)
    want = sorted(np.flatnonzero(isin if how == "semi" else ~isin).tolist())
    for exact in (False, True):
        out = left.join(right, how, on=["k"], exact=exact)
        assert sorted(out.to_pandas().iloc[:, 1].tolist()) == want


def test_exact_long_varbytes_key_is_refused_across_chips_by_name(dist_ctx):
    left, _ = _long_key_table(dist_ctx, 0, 48, 13)
    right, _ = _long_key_table(dist_ctx, 16, 64, 14)
    with pytest.raises(CylonError, match="semi join with exact=True"):
        left.distributed_join(right, "semi", on=["k"], exact=True)
    # without exact it joins on the content hash, across chips too
    out = left.distributed_join(right, "semi", on=["k"])
    assert out.column_names == ["lt-0", "lt-1"]
