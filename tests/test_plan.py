"""Lazy query-plan subsystem tests: optimizer rewrites, shuffle counts
observed through telemetry phase spans, and bit-identity of planned
execution against the eager dist_ops path. Plus the value-deterministic
hash_partition property the shuffle-elision witness depends on."""
import logging

import numpy as np
import pandas as pd
import pytest

import cylon_tpu as ct
from cylon_tpu import plan, table_api, telemetry
from cylon_tpu.plan import col, ir
from cylon_tpu.parallel import dist_ops
from conftest import assert_rows_equal


def canon(t):
    df = t.to_pandas()
    df.columns = range(df.shape[1])
    return df.sort_values(list(df.columns)).reset_index(drop=True)


def make_tables(ctx, n=4000, seed=0):
    rng = np.random.default_rng(seed)
    left = ct.Table.from_pydict(ctx, {
        "k": rng.integers(0, n // 4, n).astype(np.int32),
        "v": rng.normal(size=n).astype(np.float32),
        "z": rng.integers(0, 50, n).astype(np.int32)})
    right = ct.Table.from_pydict(ctx, {
        "k": rng.integers(0, n // 4, n).astype(np.int32),
        "w": rng.integers(0, 100, n).astype(np.int32)})
    return left, right


# ---------------------------------------------------------------------------
# hash_partition value-determinism (the witness's hard prerequisite)
# ---------------------------------------------------------------------------


def _placement(parts, col_name):
    """key value -> set of partition ids that hold it."""
    out = {}
    for pid, t in parts.items():
        for v in t.to_pydict()[col_name]:
            out.setdefault(v, set()).add(pid)
    return out


def _varbytes_table(ctx, values, extra=None):
    """Build a table whose string column is FORCED to varbytes storage
    (ingest would dictionary-encode low-cardinality pools, which is not
    the path under test)."""
    from cylon_tpu.data.column import Column
    from cylon_tpu.data.strings import VarBytes
    from cylon_tpu.data.table import Table

    validity = np.array([v is not None for v in values])
    vb = VarBytes.from_host(list(values))
    cols = [Column.from_varbytes(
        vb, None if validity.all() else validity, "k")]
    for name, arr in (extra or {}).items():
        cols.append(Column.from_numpy(np.asarray(arr), name))
    return Table(cols, ctx)


@pytest.mark.parametrize("world", [3, 8])
def test_hash_partition_long_varbytes_value_deterministic(local_ctx, world):
    """Equal long-string keys (host-fallback path) must land on the same
    partition regardless of which table they came from — the old
    table-local np.unique-code hashing broke this (ADVICE r5 medium)."""
    rng = np.random.default_rng(1)
    # >32 bytes => beyond LANE_WORDS_MAX, forcing the host partitioner
    pool = [f"user-{i:05d}-" + "x" * 40 for i in range(64)]
    k1 = [pool[i] for i in rng.integers(0, 48, 500)]        # keys 0..47
    k2 = [pool[i] for i in rng.integers(16, 64, 700)]       # keys 16..63
    t1 = _varbytes_table(local_ctx, k1, {"v": np.arange(500)})
    t2 = _varbytes_table(local_ctx, k2, {"w": np.arange(700.0)})
    assert t1.get_column(0).is_varbytes
    p1 = _placement(ct.hash_partition(t1, ["k"], world), "k")
    p2 = _placement(ct.hash_partition(t2, ["k"], world), "k")
    assert all(len(s) == 1 for s in p1.values())
    assert all(len(s) == 1 for s in p2.values())
    common = set(p1) & set(p2)
    assert len(common) >= 16  # overlap region actually exercised
    for key in common:
        assert p1[key] == p2[key], key


def test_hash_partition_host_matches_device_path(local_ctx):
    """The same short-string keys route through the DEVICE partitioner
    alone, and through the HOST fallback when a long-varbytes payload
    column rides along — placements must agree (both hash content)."""
    rng = np.random.default_rng(2)
    keys = [f"id-{i:04d}" for i in rng.integers(0, 40, 300)]
    dev = _varbytes_table(local_ctx, keys, {"v": np.arange(300)})
    host = _varbytes_table(local_ctx, keys, {"v": np.arange(300)})
    # a long-varbytes payload column forces the whole table through the
    # host partitioner
    from cylon_tpu.data.column import Column
    from cylon_tpu.data.strings import VarBytes
    from cylon_tpu.data.table import Table
    long_vb = VarBytes.from_host(["p" * 48] * 300)
    host = Table(host._columns
                 + [Column.from_varbytes(long_vb, None, "long")],
                 local_ctx)
    assert dev.get_column(0).is_varbytes
    pd_dev = _placement(ct.hash_partition(dev, ["k"], 8), "k")
    pd_host = _placement(ct.hash_partition(host, ["k"], 8), "k")
    for key in pd_dev:
        assert pd_dev[key] == pd_host[key], key


def test_hash_partition_varbytes_nulls_and_multikey(local_ctx):
    from cylon_tpu.data.column import Column
    from cylon_tpu.data.table import Table

    rng = np.random.default_rng(3)
    vals = np.array([None if i % 7 == 0 else f"row-{i % 23}-" + "y" * 40
                     for i in range(200)], object)
    nums = rng.integers(0, 9, 200).astype(np.int64)

    def make(svals, nvals):
        t = _varbytes_table(local_ctx, list(svals))
        return Table([t._columns[0].rename("s"),
                      Column.from_numpy(np.asarray(nvals), "n")],
                     local_ctx)

    t1 = make(vals, nums)
    t2 = make(vals[::-1].copy(), nums[::-1].copy())
    p1 = {}
    for pid, t in ct.hash_partition(t1, ["s", "n"], 5).items():
        d = t.to_pydict()
        for s, nv in zip(d["s"], d["n"]):
            p1.setdefault((s, int(nv)), set()).add(pid)
    for pid, t in ct.hash_partition(t2, ["s", "n"], 5).items():
        d = t.to_pydict()
        for s, nv in zip(d["s"], d["n"]):
            assert pid in p1[(s, int(nv))], (s, nv)


# ---------------------------------------------------------------------------
# plan-level shuffle counting via telemetry phase spans
# ---------------------------------------------------------------------------


def test_join_groupby_same_keys_one_shuffle(dist_ctx, caplog):
    left, right = make_tables(dist_ctx)
    pipe = plan.scan(left).join(plan.scan(right), on="k") \
        .groupby("lt-0", ["rt-4"], ["sum"])
    with caplog.at_level(logging.INFO, logger="cylon_tpu"):
        with telemetry.collect_phases() as cp:
            out = pipe.execute()
    # exactly ONE exchange stage for the whole pipeline: the join's
    # fused two-table shuffle; the groupby aggregates in place
    assert cp.count("plan.shuffle") == 1, cp.labels
    msgs = [r.message for r in caplog.records]
    assert sum(m.startswith("plan.shuffle") for m in msgs) == 1, msgs
    assert any(m.startswith("plan.groupby#") for m in msgs), msgs

    # bit-identical to the eager dist_ops composition
    ej = left.distributed_join(right, "inner", on="k")
    eg = dist_ops.distributed_groupby(ej, [0], [4],
                                      [ct.AggregationOp.SUM])
    pd.testing.assert_frame_equal(canon(out), canon(eg), check_dtype=False)


def test_join_groupby_changed_keys_two_shuffles(dist_ctx):
    left, right = make_tables(dist_ctx)
    pipe = plan.scan(left).join(plan.scan(right), on="k") \
        .groupby("lt-2", ["rt-4"], ["sum"])
    with telemetry.collect_phases() as cp:
        out = pipe.execute()
    assert cp.count("plan.shuffle") == 2, cp.labels
    ej = left.distributed_join(right, "inner", on="k")
    eg = dist_ops.distributed_groupby(ej, [2], [4],
                                      [ct.AggregationOp.SUM])
    pd.testing.assert_frame_equal(canon(out), canon(eg), check_dtype=False)


def test_copartitioned_ingest_elides_all_shuffles(dist_ctx):
    """distribute_by_key-ingested tables carry the placement witness;
    the planner elides BOTH join-side shuffles and the groupby runs in
    place — a 3-op pipeline with ZERO exchanges."""
    left, right = make_tables(dist_ctx, seed=5)
    lp = ct.distribute_by_key(left, dist_ctx, ["k"])
    rp = ct.distribute_by_key(right, dist_ctx, ["k"])
    pipe = plan.scan(lp).join(plan.scan(rp), on="k") \
        .groupby("lt-0", ["rt-4"], ["sum"])
    root, stats = pipe.optimized()
    assert stats.shuffles_elided == 2, stats
    assert stats.groupbys_localized == 1, stats
    with telemetry.collect_phases() as cp:
        out = pipe.execute()
    assert cp.count("plan.shuffle") == 0, cp.labels
    ej = left.distributed_join(right, "inner", on="k")
    eg = dist_ops.distributed_groupby(ej, [0], [4],
                                      [ct.AggregationOp.SUM])
    pd.testing.assert_frame_equal(canon(out), canon(eg), check_dtype=False)


def test_string_keys_never_claim_elision(dist_ctx):
    """String keys carry no placement witness (vocabulary/lane-count
    re-coding) — the optimizer must not elide, and results still match
    eager."""
    rng = np.random.default_rng(7)
    n = 800
    ks = np.array([f"a{v:03d}" for v in rng.integers(0, 60, n)], object)
    left = ct.Table.from_pydict(dist_ctx, {"k": ks, "v": np.arange(n)})
    right = ct.Table.from_pydict(dist_ctx, {
        "k": np.array([f"a{v:03d}" for v in rng.integers(0, 80, n)],
                      object),
        "w": np.arange(n) * 2})
    pipe = plan.scan(left).join(plan.scan(right), on="k") \
        .groupby("lt-0", ["rt-3"], ["count"])
    root, stats = pipe.optimized()
    assert stats.shuffles_elided == 0
    assert stats.groupbys_localized == 0
    out = pipe.execute()
    ej = left.distributed_join(right, "inner", on="k")
    eg = dist_ops.distributed_groupby(ej, [0], [3],
                                      [ct.AggregationOp.COUNT])
    pd.testing.assert_frame_equal(canon(out), canon(eg), check_dtype=False)


# ---------------------------------------------------------------------------
# optimizer rewrites
# ---------------------------------------------------------------------------


def test_filter_pushdown_below_shuffle(dist_ctx):
    left, right = make_tables(dist_ctx, seed=9)
    pipe = plan.scan(left).shuffle("k").filter(col("z") < 25) \
        .join(plan.scan(right), on="k")
    root, stats = pipe.optimized()
    assert stats.filters_pushed >= 1
    # in the optimized tree every Filter sits BELOW every Shuffle on
    # its path (rows drop in transit)
    def no_filter_above_shuffle(node, seen_filter=False):
        if isinstance(node, ir.Shuffle):
            assert not seen_filter, "filter stayed above a shuffle"
        seen = seen_filter or isinstance(node, ir.Filter)
        for c in node.children:
            no_filter_above_shuffle(c, seen)
    no_filter_above_shuffle(root)
    out = pipe.execute()
    es = dist_ops.shuffle(left, ["k"])
    ef = es.filter_mask(es.get_column(2).data < 25)
    ej = ef.distributed_join(right, "inner", on="k")
    pd.testing.assert_frame_equal(canon(out), canon(ej), check_dtype=False)


def test_projection_pruning_drops_unused_columns(dist_ctx):
    left, right = make_tables(dist_ctx, seed=11)
    pipe = plan.scan(left).join(plan.scan(right), on="k") \
        .groupby("lt-0", ["rt-4"], ["mean"])
    root, stats = pipe.optimized()
    assert stats.columns_pruned >= 2, stats  # v and z never referenced
    out = pipe.execute()
    ej = left.distributed_join(right, "inner", on="k")
    eg = dist_ops.distributed_groupby(ej, [0], [4],
                                      [ct.AggregationOp.MEAN])
    pd.testing.assert_frame_equal(canon(out), canon(eg), check_dtype=False)


def test_filter_only_columns_pruned_before_exchange(dist_ctx):
    """A column only the (pushed-down) filter reads must not cross the
    mesh: the optimizer projects it away between the filter and the
    shuffle."""
    left, right = make_tables(dist_ctx, seed=27)
    pipe = plan.scan(left).filter(col("z") < 25) \
        .join(plan.scan(right), on="k").groupby("lt-0", ["lt-1"], ["sum"])
    root, _stats = pipe.optimized()
    for node in ir.walk(root):
        if isinstance(node, ir.Shuffle):
            # exchange payloads carry only key + aggregate columns
            assert node.width <= 2, ir.format_plan(root)
    out = pipe.execute()
    ef = left.filter_mask(left.get_column(2).data < 25)
    ej = ef.distributed_join(right, "inner", on="k")
    eg = dist_ops.distributed_groupby(ej, [0], [1],
                                      [ct.AggregationOp.SUM])
    pd.testing.assert_frame_equal(canon(out), canon(eg),
                                  check_dtype=False, atol=1e-5,
                                  rtol=1e-4)


def test_unoptimized_execution_matches(dist_ctx):
    left, right = make_tables(dist_ctx, seed=13)
    pipe = plan.scan(left).join(plan.scan(right), on="k") \
        .groupby("lt-0", ["rt-4"], ["sum"])
    a = pipe.execute(optimize=False)
    b = pipe.execute(optimize=True)
    pd.testing.assert_frame_equal(canon(a), canon(b), check_dtype=False)


def test_plan_reexecution_is_stable(dist_ctx):
    """optimize/execute must not mutate the logical plan the LazyTable
    holds (deepcopy discipline)."""
    left, right = make_tables(dist_ctx, seed=15)
    pipe = plan.scan(left).join(plan.scan(right), on="k")
    w1 = pipe._node.children[0].width
    a = pipe.execute()
    assert pipe._node.children[0].width == w1
    assert not isinstance(pipe._node.children[0], ir.Shuffle)
    b = pipe.execute()
    pd.testing.assert_frame_equal(canon(a), canon(b), check_dtype=False)


# ---------------------------------------------------------------------------
# other operators through the plan
# ---------------------------------------------------------------------------


def test_plan_setop_and_sort_match_eager(dist_ctx):
    rng = np.random.default_rng(17)
    n = 1000
    a = ct.Table.from_pydict(dist_ctx, {
        "k": rng.integers(0, n, n).astype(np.int32),
        "g": rng.integers(0, 1 << 10, n).astype(np.int32)})
    b = ct.Table.from_pydict(dist_ctx, {
        "k": rng.integers(0, n, n).astype(np.int32),
        "g": rng.integers(0, 1 << 10, n).astype(np.int32)})
    got = plan.scan(a).union(plan.scan(b)).execute()
    exp = a.distributed_union(b)
    pd.testing.assert_frame_equal(canon(got), canon(exp),
                                  check_dtype=False)
    got_s = plan.scan(a).sort("k").execute()
    exp_s = dist_ops.distributed_sort(a, "k")
    # sort guarantees order: compare compacted rows in order
    pd.testing.assert_frame_equal(
        got_s.to_pandas().reset_index(drop=True).iloc[:, :1],
        exp_s.to_pandas().reset_index(drop=True).iloc[:, :1],
        check_dtype=False)


def test_plan_local_world1_matches_local(local_ctx):
    left, right = make_tables(local_ctx, seed=19)
    with telemetry.collect_phases() as cp:
        out = plan.scan(left).join(plan.scan(right), on="k") \
            .groupby("lt-0", ["rt-4"], ["sum"]).execute()
    assert cp.count("plan.shuffle") == 0, cp.labels
    ej = left.join(right, "inner", on="k")
    eg = ej.groupby(0, [4], ["sum"])
    pd.testing.assert_frame_equal(canon(out), canon(eg), check_dtype=False)


def test_table_api_lazy_roundtrip(dist_ctx):
    left, right = make_tables(dist_ctx, seed=21)
    table_api.put_table("plan-left", left)
    table_api.put_table("plan-right", right)
    lazy = table_api.lazy_table("plan-left").join(
        table_api.lazy_table("plan-right"), on="k")
    table_api.execute_plan(lazy, "plan-out")
    got = table_api.get_table("plan-out")
    exp = left.distributed_join(right, "inner", on="k")
    pd.testing.assert_frame_equal(canon(got), canon(exp),
                                  check_dtype=False)
    for tid in ("plan-left", "plan-right", "plan-out"):
        table_api.remove_table(tid)


def test_pre_partitioned_groupby_dist_ops_level(dist_ctx):
    """The dist_ops building block under the planner: a table shuffled
    by key aggregates per shard (pre_partitioned=True) to the exact
    global result."""
    left, _ = make_tables(dist_ctx, seed=23)
    shuffled = dist_ops.shuffle(left, ["k"])
    got = dist_ops.distributed_groupby(
        shuffled, [0], [1, 2], [ct.AggregationOp.SUM,
                                ct.AggregationOp.COUNT],
        pre_partitioned=True)
    exp = dist_ops.distributed_groupby(
        left, [0], [1, 2], [ct.AggregationOp.SUM, ct.AggregationOp.COUNT])
    # float32 sums reduce in different row orders on the two paths —
    # tolerance, not bit-identity, is the honest check here
    pd.testing.assert_frame_equal(canon(got), canon(exp),
                                  check_dtype=False, atol=1e-5,
                                  rtol=1e-4)


def test_nested_collect_phases(local_ctx):
    """Nested collectors with equal contents must unregister by
    identity, not by value."""
    with telemetry.collect_phases() as outer:
        with telemetry.collect_phases() as inner:
            with telemetry.phase("a"):
                pass
        with telemetry.phase("b"):
            pass
    assert inner.labels == ["a"]
    assert outer.labels == ["a", "b"]


def test_scan_does_not_register_tables(dist_ctx):
    """plan.scan(Table) must not pin the table in the process-global
    table_api registry (unbounded growth in long-running services)."""
    left, right = make_tables(dist_ctx, seed=29)
    before = set(table_api.registered_ids())
    pipe = plan.scan(left).join(plan.scan(right), on="k")
    pipe.execute()
    assert set(table_api.registered_ids()) == before


def test_standalone_shuffle_survives_registry_rebind(dist_ctx):
    """A standalone Shuffle is never plan-deleted on the scan-time
    witness snapshot: rebinding the registry id to an UNPARTITIONED
    table between plan build and execute must still shuffle."""
    left, _ = make_tables(dist_ctx, seed=33)
    pre = ct.distribute_by_key(left, dist_ctx, ["k"])
    table_api.put_table("rebind-me", pre)
    lazy = table_api.lazy_table("rebind-me").shuffle("k")
    # witnessed input: the executor skips the exchange at run time
    with telemetry.collect_phases() as cp:
        lazy.execute()
    assert cp.count("plan.shuffle") == 0, cp.labels
    # rebind to a fresh (unplaced) table: the kept node must exchange
    fresh, _ = make_tables(dist_ctx, seed=35)
    table_api.put_table("rebind-me", fresh)
    with telemetry.collect_phases() as cp2:
        out = lazy.execute()
    assert cp2.count("plan.shuffle") == 1, cp2.labels
    sig = out._hash_partitioned
    assert sig is not None and sig[0] == (0,)
    table_api.remove_table("rebind-me")


def test_explain_mentions_elision(dist_ctx):
    left, right = make_tables(dist_ctx, seed=25)
    lp = ct.distribute_by_key(left, dist_ctx, ["k"])
    txt = plan.scan(lp).join(plan.scan(right), on="k").explain()
    assert "elided" in txt and "Shuffle" in txt
    assert "partitioned_by" in txt


# ---------------------------------------------------------------------------
# EXPLAIN ANALYZE (per-query PlanReport)
# ---------------------------------------------------------------------------


def test_explain_analyze_pipeline_shuffle_counts(dist_ctx):
    """The acceptance pin: on the join→groupby pipeline
    (join on k → groupby on k), explain(analyze=True) shows per-node
    measured rows/bytes/ms, and its reported shuffle count equals
    collect_phases.count("plan.shuffle") — 1 optimized vs 2 eager."""
    left, right = make_tables(dist_ctx, seed=41)
    pipe = plan.scan(left).join(plan.scan(right), on="k") \
        .groupby("lt-0", ["rt-4"], ["sum"])

    with telemetry.collect_phases() as cp:
        txt = pipe.explain(analyze=True)
    rep = pipe.last_report
    assert rep.shuffle_count == cp.count("plan.shuffle") == 1
    assert "actual time=" in txt and "rows=" in txt and "bytes=" in txt
    assert "folded into parent exchange" in txt  # join-side markers

    with telemetry.collect_phases() as cp2:
        pipe.explain(optimize=False, analyze=True)
    rep2 = pipe.last_report
    assert rep2.shuffle_count == cp2.count("plan.shuffle") == 2
    assert rep2.stats is None  # unoptimized run carries no PlanStats


def test_explain_analyze_report_measures(dist_ctx):
    left, right = make_tables(dist_ctx, seed=43)
    pipe = plan.scan(left).join(plan.scan(right), on="k") \
        .groupby("lt-0", ["rt-4"], ["sum"])
    out = pipe.execute(analyze=True)
    rep = pipe.last_report

    # root measure mirrors the executed result exactly
    assert rep.root.kind == "groupby"
    assert rep.root.rows == out.row_count
    assert rep.root.bytes == out.nbytes > 0
    assert rep.root.ms is not None and rep.root.ms > 0
    assert rep.world == 4
    # inclusive timing: the root's wall time bounds its child's
    join_m = rep.root.children[0]
    assert join_m.kind == "join" and join_m.ms <= rep.root.ms
    assert join_m.shuffles == 1  # plan.shuffle.join is the join's own
    # the span tree of the whole query, rooted at plan.query
    assert rep.span.name == "plan.query"
    names = [s.name for s in rep.span.walk()]
    assert "plan.shuffle.join" in names and "shuffle.exchange_pair" in names
    # machine-comparable form round-trips through JSON
    import json

    d = json.loads(json.dumps(rep.to_dict()))
    assert d["shuffle_count"] == 1
    assert d["plan"]["kind"] == "groupby"
    assert d["optimizer"]["groupbys_localized"] == 1
    # analyze result matches the plain execution bit-for-bit
    import pandas as pd

    pd.testing.assert_frame_equal(canon(out), canon(pipe.execute()),
                                  check_dtype=False)


def test_execute_default_path_records_no_report(dist_ctx):
    left, right = make_tables(dist_ctx, seed=45)
    pipe = plan.scan(left).join(plan.scan(right), on="k")
    before = getattr(pipe, "last_report", None)
    pipe.execute()
    assert getattr(pipe, "last_report", None) is before


def test_explain_analyze_world1(local_ctx):
    """EXPLAIN ANALYZE on a local context: zero exchanges reported,
    measures still populated."""
    left, right = make_tables(local_ctx, seed=47)
    pipe = plan.scan(left).join(plan.scan(right), on="k")
    txt = pipe.explain(analyze=True)
    rep = pipe.last_report
    assert rep.shuffle_count == 0 and rep.world == 1
    assert "rows=" in txt


def test_promoting_join_labels_count_only_promoted_side(dist_ctx):
    """Label honesty under promoting alignment (review fix): a side
    already at the promoted common dtype keeps its witness and is
    skipped by distributed_join — the span must count ONE exchanged
    side, not two."""
    rng = np.random.default_rng(51)
    n = 2000
    left = ct.Table.from_pydict(dist_ctx, {
        "k": rng.integers(0, n // 4, n).astype(np.int64),
        "v": rng.normal(size=n).astype(np.float32)})
    right = ct.Table.from_pydict(dist_ctx, {
        "k": rng.integers(0, n // 4, n).astype(np.int32),
        "w": rng.normal(size=n).astype(np.float32)})
    lp = ct.distribute_by_key(left, dist_ctx, ["k"])  # int64 witness
    pipe = plan.scan(lp).join(plan.scan(right), on="k")
    pipe.execute(analyze=True)
    joins = [s for s in pipe.last_report.span.walk()
             if s.name in ("plan.shuffle.join", "plan.join")]
    assert len(joins) == 1
    # right promotes int32->int64 and must exchange; the witnessed
    # int64 left side is skipped (mirrors dist_ops' aligned-sig check)
    assert joins[0].name == "plan.shuffle.join"
    assert joins[0].attrs["sides_exchanged"] == 1, joins[0].attrs
