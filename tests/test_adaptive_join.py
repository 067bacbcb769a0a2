"""Adaptive join execution (PR 15): broadcast-hash joins + hot-key
salting. Covers the acceptance matrix — broadcast bit-identity with the
shuffle join across join types / dtypes (incl. varbytes keys) / world
sizes / empty build side / exact byte threshold; salted exchange
bit-identity (post-unsalt) with measured max-shard reduction under
Zipfian keys; verifier rejection of hand-mutated broadcast claims; the
pre-adaptive program (no rewrite: `broadcast_choice` patched to
None; factory-reuse pinned); the stats-driven learn →
broadcast → drift → revert closed loop; and the observability surface
(counters, span attrs, EXPLAIN `algo=`, digest v3)."""
import numpy as np
import pytest

import cylon_tpu as ct
import forced_paths
from cylon_tpu import plan, telemetry
from cylon_tpu.data import strings as _strings
from cylon_tpu.parallel import dist_ops
from cylon_tpu.plan import ir
from cylon_tpu.plan import optimizer as optimizer_mod
from cylon_tpu.plan.fingerprint import join_decision_fingerprint
from cylon_tpu.plan.optimizer import (BROADCAST_MIN_RATIO,
                                      broadcast_choice, optimize)
from cylon_tpu.plan.verify import check_plan, verify_plan
from cylon_tpu.resilience import inject
from cylon_tpu.service import plancache
from cylon_tpu.status import CylonPlanError
from cylon_tpu.telemetry import querylog
from cylon_tpu.telemetry import stats as stats_mod

import jax


@pytest.fixture(autouse=True)
def _clean():
    stats_mod.reset()
    plancache.global_cache().clear()
    yield
    inject.disarm()
    stats_mod.reset()
    plancache.global_cache().clear()
    querylog.reset()


def _counter(name):
    return telemetry.metrics_snapshot().get(name, 0)


def _canon(table):
    """Order-insensitive exact row multiset (NaN/None canonicalized).
    Values are gathered, never recomputed, so equality is exact."""
    d = table.to_pandas()
    rows = []
    for t in d.itertuples(index=False):
        rows.append(tuple(
            "<null>" if v is None or v != v else str(v) for v in t))
    return sorted(rows)


def _tables(ctx, n, m, seed=0, dtype=np.int32, key_space=64):
    rng = np.random.default_rng(seed)
    left = ct.Table.from_pydict(ctx, {
        "k": rng.integers(0, key_space, n).astype(dtype),
        "v": rng.normal(size=n).astype(np.float32)})
    right = ct.Table.from_pydict(ctx, {
        "k": rng.integers(0, key_space, m).astype(dtype),
        "w": rng.normal(size=m).astype(np.float32)})
    return left, right


# ---------------------------------------------------------------------------
# broadcast-hash join: bit-identity with the shuffle join
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("how", ["inner", "left"])
@pytest.mark.parametrize("dtype", [np.int32, np.int64])
def test_broadcast_bit_identity_matrix(dist_ctx, how, dtype):
    left, right = _tables(dist_ctx, 2048, 64, seed=7, dtype=dtype)
    got = left.distributed_join(right, how, on="k", comm="broadcast",
                                build_side=1)
    want = left.distributed_join(right, how, on="k")
    assert _canon(got) == _canon(want)


def test_broadcast_build_side_left_inner(dist_ctx):
    left, right = _tables(dist_ctx, 64, 2048, seed=8)
    got = left.distributed_join(right, "inner", on="k",
                                comm="broadcast", build_side=0)
    want = left.distributed_join(right, "inner", on="k")
    assert _canon(got) == _canon(want)


def test_broadcast_right_join_build_left(dist_ctx):
    left, right = _tables(dist_ctx, 64, 2048, seed=9)
    got = left.distributed_join(right, "right", on="k",
                                comm="broadcast", build_side=0)
    want = left.distributed_join(right, "right", on="k")
    assert _canon(got) == _canon(want)


def test_broadcast_world8(dist_ctx8):
    left, right = _tables(dist_ctx8, 4096, 32, seed=10)
    got = left.distributed_join(right, "inner", on="k",
                                comm="broadcast", build_side=1)
    want = left.distributed_join(right, "inner", on="k")
    assert _canon(got) == _canon(want)


def test_broadcast_world1_is_local_join(local_ctx):
    left, right = _tables(local_ctx, 512, 32, seed=11)
    got = left.join(right, "inner", on="k")
    bc = left.distributed_join(right, "inner", on="k",
                               comm="broadcast", build_side=1)
    assert _canon(got) == _canon(bc)


def test_broadcast_empty_build_side(dist_ctx):
    left, _ = _tables(dist_ctx, 512, 8, seed=12)
    empty = ct.Table.from_pydict(dist_ctx, {
        "k": np.array([], np.int32), "w": np.array([], np.float32)})
    for how in ("inner", "left"):
        got = left.distributed_join(empty, how, on="k",
                                    comm="broadcast", build_side=1)
        want = left.distributed_join(empty, how, on="k")
        assert _canon(got) == _canon(want)


def test_broadcast_varbytes_keys(dist_ctx, monkeypatch):
    monkeypatch.setattr(_strings, "DICT_MAX_VOCAB", 0)
    rng = np.random.default_rng(13)
    lt = ct.Table.from_pydict(dist_ctx, {
        "k": np.array([f"key{int(x):03d}"
                       for x in rng.integers(0, 40, 768)], object),
        "v": rng.normal(size=768).astype(np.float32)})
    rt = ct.Table.from_pydict(dist_ctx, {
        "k": np.array([f"key{int(x):03d}"
                       for x in rng.integers(0, 40, 48)], object),
        "w": rng.normal(size=48).astype(np.float32)})
    for how in ("inner", "left"):
        got = lt.distributed_join(rt, how, on="k", comm="broadcast",
                                  build_side=1)
        want = lt.distributed_join(rt, how, on="k")
        assert _canon(got) == _canon(want)


def test_broadcast_illegal_side_falls_back_correct(dist_ctx):
    """A LEFT join may never replicate its left input — the runtime
    falls back to the shuffle composition and stays correct."""
    left, right = _tables(dist_ctx, 512, 64, seed=14)
    got = left.distributed_join(right, "left", on="k",
                                comm="broadcast", build_side=0)
    want = left.distributed_join(right, "left", on="k")
    assert _canon(got) == _canon(want)


def test_broadcast_moves_zero_exchange_bytes(dist_ctx):
    left, right = _tables(dist_ctx, 2048, 64, seed=15)
    b0 = _counter("cylon_shuffle_bytes_total")
    a0 = _counter('cylon_join_algorithm_total{algo="broadcast"}')
    left.distributed_join(right, "inner", on="k", comm="broadcast",
                          build_side=1)
    assert _counter("cylon_shuffle_bytes_total") == b0
    assert _counter('cylon_join_algorithm_total{algo="broadcast"}') \
        == a0 + 1


def test_broadcast_preserves_probe_witness(dist_ctx):
    """The probe side's hash-placement witness survives the broadcast
    join unchanged — probe rows never move."""
    left, right = _tables(dist_ctx, 1024, 32, seed=16)
    placed = dist_ops.shuffle(left, ["k"])
    sig = placed._hash_partitioned
    assert sig is not None
    out = placed.distributed_join(right, "inner", on="k",
                                  comm="broadcast", build_side=1)
    assert out._hash_partitioned == sig
    # ...and the shuffle-join's own witness semantics are unchanged
    left2, right2 = _tables(dist_ctx, 1024, 32, seed=16)
    out2 = left2.distributed_join(right2, "inner", on="k")
    assert out2._hash_partitioned is not None


# ---------------------------------------------------------------------------
# the stats-driven planner loop
# ---------------------------------------------------------------------------


def _feed_join_inputs(node, world, left_bytes, right_bytes, n=None):
    """Qualify a join's decision fingerprint with synthetic measured
    input sizes (min_obs observations each)."""
    fp = join_decision_fingerprint(node, world)
    for i in range(n or stats_mod.min_obs()):
        stats_mod.STORE._observe_node(
            "pfp", fp, "join_input",
            {"left_bytes": float(left_bytes),
             "right_bytes": float(right_bytes)},
            ("left_bytes", "right_bytes"), None, float(i))
    return fp


def test_exploratory_first_then_broadcast(dist_ctx):
    """First sight of a shape stays shuffle; once the build side is
    measured small (and the probe large), the rewrite fires."""
    left, right = _tables(dist_ctx, 1024, 16, seed=17)
    lt = plan.scan(left).join(plan.scan(right), on="k")
    root, stats = optimize(lt._plan_copy(), 4)
    join = next(n for n in ir.walk(root) if n.kind == "join")
    assert join.algorithm == "auto" and stats.joins_broadcast == 0
    _feed_join_inputs(lt._node, 4, left_bytes=1 << 20,
                      right_bytes=1 << 10)
    root, stats = optimize(lt._plan_copy(), 4)
    join = next(n for n in ir.walk(root) if n.kind == "join")
    assert join.algorithm == "broadcast" and join.build_side == 1
    assert stats.joins_broadcast == 1
    # no Shuffle markers survive under a broadcast join
    assert all(c.kind != "shuffle" for c in join.children)
    # ...and the verifier accepts the rewritten plan
    assert verify_plan(root, 4) == []


def test_broadcast_threshold_exact_byte_boundary(dist_ctx,
                                                 monkeypatch):
    """A build side measured EXACTLY at the byte budget (EWMA x safety
    == CYLON_BROADCAST_MAX_BYTES) broadcasts; one byte past it does
    not."""
    monkeypatch.setenv("CYLON_STATS_SAFETY", "1.0")
    monkeypatch.setenv("CYLON_BROADCAST_MAX_BYTES", str(1 << 16))
    left, right = _tables(dist_ctx, 1024, 16, seed=18)
    node = plan.scan(left).join(plan.scan(right), on="k")._node
    _feed_join_inputs(node, 4, left_bytes=(1 << 16) * BROADCAST_MIN_RATIO,
                      right_bytes=1 << 16)
    assert broadcast_choice(node, 4) == 1
    stats_mod.reset()
    _feed_join_inputs(node, 4, left_bytes=(1 << 16) * BROADCAST_MIN_RATIO,
                      right_bytes=(1 << 16) + 1)
    assert broadcast_choice(node, 4) is None


def test_equal_sized_sides_never_broadcast(dist_ctx):
    """Two same-sized small tables stay shuffle: under the
    BROADCAST_MIN_RATIO probe/build guard there is no exchange win,
    and warmed-cache pipelines must not be perturbed mid-stream."""
    left, right = _tables(dist_ctx, 512, 512, seed=19)
    node = plan.scan(left).join(plan.scan(right), on="k")._node
    _feed_join_inputs(node, 4, left_bytes=1 << 12, right_bytes=1 << 12)
    assert broadcast_choice(node, 4) is None


def test_learned_loop_end_to_end_bit_identity(dist_ctx, monkeypatch):
    """The full closed loop, library mode: 3 shuffle executions learn
    the shape, the next optimize goes broadcast, results stay
    bit-identical throughout, and the digest/EXPLAIN/metrics surface
    names the algorithm."""
    monkeypatch.setenv("CYLON_STATS_MIN_OBS", "2")
    left, right = _tables(dist_ctx, 1 << 13, 16, seed=20)

    def pipe():
        return plan.scan(left).join(
            plan.scan(right), on="k")

    base = None
    for _ in range(3):
        r = pipe().execute()
        base = base or _canon(r)
        assert _canon(r) == base
    txt = pipe().explain()
    assert "algo=broadcast" in txt and "build=1" in txt
    b0 = _counter("cylon_shuffle_bytes_total")
    p = pipe()
    atxt = p.explain(analyze=True)
    assert "algo=broadcast" in atxt
    assert _counter("cylon_shuffle_bytes_total") == b0
    d = querylog.recent()[-1]
    # v3 brought join_algorithms; v4 (PR 51) a served query's stage times
    assert d["v"] == 4
    assert d["join_algorithms"] == ["broadcast"]
    assert d["shuffles"] == 0
    rep = p.last_report.to_dict()
    assert rep["plan"]["join_algorithm"] == "broadcast"


def test_join_algorithm_shuffle_restores_pre_adaptive_program(
        dist_ctx, monkeypatch):
    """With no adaptive choice (`forced_paths.shuffle_joins_only`: no
    knob does it since PR 45) the plan is the exact pre-adaptive program:
    learned statistics are ignored, the plan renders identically to a
    fresh-stats optimize, and NO broadcast kernel factory is ever
    built (the broadcast path lives in factories of its own, keyed
    apart from every shuffle-path program)."""
    left, right = _tables(dist_ctx, 1 << 12, 16, seed=21)

    def pipe():
        return plan.scan(left).join(
            plan.scan(right), on="k")

    fresh_txt = pipe().explain()
    _feed_join_inputs(pipe()._node, 4, left_bytes=1 << 20,
                      right_bytes=1 << 8)
    assert "algo=broadcast" in pipe().explain()
    forced_paths.shuffle_joins_only(monkeypatch)
    assert pipe().explain() == fresh_txt
    builds0 = {k: v for k, v in telemetry.metrics_snapshot().items()
               if "_bcast_join" in k}
    r = pipe().execute()
    builds1 = {k: v for k, v in telemetry.metrics_snapshot().items()
               if "_bcast_join" in k}
    assert builds0 == builds1
    forced_paths.shuffle_joins_only(monkeypatch, False)
    rb = pipe().execute()
    assert _canon(r) == _canon(rb)


def test_forced_broadcast_choice(dist_ctx, monkeypatch):
    monkeypatch.setattr(
        optimizer_mod, "broadcast_choice",
        lambda node, world: 1 if isinstance(node, ir.Join) else None)
    left, right = _tables(dist_ctx, 512, 64, seed=22)
    lt = plan.scan(left).join(plan.scan(right), on="k")
    root, stats = optimize(lt._plan_copy(), 4)
    join = next(n for n in ir.walk(root) if n.kind == "join")
    assert join.algorithm == "broadcast" and join.build_side == 1
    r = lt.execute()
    want = left.distributed_join(right,
                                              "inner", on="k")
    assert _canon(r) == _canon(want)


def test_mislearn_drifts_evicts_and_reverts(dist_ctx, monkeypatch):
    """A poisoned (100x-understated) build-side estimate fires the
    broadcast rewrite; the first broadcast run measures the true input
    sizes under the SAME decision fingerprint, drift fires, the cached
    plan evicts, and the shape reverts to shuffle — bit-identical
    results at every step."""
    monkeypatch.setenv("CYLON_STATS_MIN_OBS", "2")
    left, right = _tables(dist_ctx, 1 << 12, 1 << 12, seed=23)

    def pipe():
        return plan.scan(left).join(
            plan.scan(right), on="k")

    with plancache.disabled():
        base = _canon(pipe().execute())
    # poisoning REPLACES the learned evidence (the baseline's genuine
    # observation is dropped — the store's memory IS the lie)
    stats_mod.reset()
    real_bytes = int(right.nbytes)
    fp = _feed_join_inputs(pipe()._node, 4,
                           left_bytes=real_bytes * BROADCAST_MIN_RATIO
                           * 2,
                           right_bytes=max(real_bytes // 100, 1), n=2)
    assert "algo=broadcast" in pipe().explain()
    d0 = _counter("cylon_stats_drift_total")
    r = pipe().execute()          # broadcast runs; measures the truth
    assert _canon(r) == base
    assert _counter("cylon_stats_drift_total") > d0
    # the decision entry reset: the next optimize reverts to shuffle
    assert stats_mod.join_input_bytes(fp) == (None, None) or \
        stats_mod.join_input_bytes(fp)[1] is None
    assert "algo=broadcast" not in pipe().explain()
    r2 = pipe().execute()
    assert _canon(r2) == base


def test_plancache_epoch_staleness(dist_ctx, monkeypatch):
    """A warmed cache entry re-optimizes when the warehouse's adaptive
    evidence changes its decision — and keeps hitting when an epoch
    bump concerns OTHER shapes."""
    monkeypatch.setenv("CYLON_STATS_MIN_OBS", "2")
    left, right = _tables(dist_ctx, 1 << 12, 16, seed=24)

    def pipe():
        return plan.scan(left).join(
            plan.scan(right), on="k")

    pipe().optimized()                       # insert (shuffle shape)
    h0 = _counter("cylon_plan_cache_hits_total")
    pipe().optimized()
    assert _counter("cylon_plan_cache_hits_total") == h0 + 1
    # an UNRELATED adaptive qualification bumps the epoch; this
    # shape's decisions are unchanged -> still a hit
    stats_mod.STORE._observe_node(
        "pfp", "other-fp", "join_input",
        {"left_bytes": 1.0, "right_bytes": 1.0},
        ("left_bytes", "right_bytes"), None, 0.0)
    stats_mod.STORE._observe_node(
        "pfp", "other-fp", "join_input",
        {"left_bytes": 1.0, "right_bytes": 1.0},
        ("left_bytes", "right_bytes"), None, 1.0)
    h1 = _counter("cylon_plan_cache_hits_total")
    pipe().optimized()
    assert _counter("cylon_plan_cache_hits_total") == h1 + 1
    # THIS shape's decision flips -> stale, re-optimized as broadcast
    _feed_join_inputs(pipe()._node, 4, left_bytes=1 << 20,
                      right_bytes=1 << 8, n=2)
    s0 = _counter("cylon_plan_cache_stale_total")
    root, _ = pipe().optimized()
    assert _counter("cylon_plan_cache_stale_total") == s0 + 1
    join = next(n for n in ir.walk(root) if n.kind == "join")
    assert join.algorithm == "broadcast"
    # ...and the broadcast template hits again afterwards
    h2 = _counter("cylon_plan_cache_hits_total")
    pipe().optimized()
    assert _counter("cylon_plan_cache_hits_total") == h2 + 1


def test_broadcast_rewrite_keeps_downstream_claims_sound(dist_ctx,
                                                         monkeypatch):
    """Regression (caught live by the debug verifier): join→groupby on
    the join keys, build side learned small. The broadcast rewrite
    changes the join's output witness to the PROBE side's placement,
    so the groupby must not keep a ``local_ok`` claim justified by the
    dead shuffle-join witness — the adaptive pass runs BEFORE elision
    precisely so every downstream claim derives from the rewritten
    tree. The optimized plan must verify clean (conftest runs the
    verifier on every optimize) and stay bit-identical."""
    monkeypatch.setenv("CYLON_STATS_MIN_OBS", "2")
    left, right = _tables(dist_ctx, 4096, 16, seed=31)

    def pipe():
        return plan.scan(left).join(plan.scan(right), on="k") \
            .groupby("lt-0", ["rt-3"], ["sum"])

    def agg(t):
        # float32 group sums are shard-order-sensitive: the broadcast
        # plan aggregates in a different physical order, so compare
        # keys exactly and sums with a tolerance (not _canon)
        d = t.to_pandas()
        return d.set_index(d.columns[0]).iloc[:, 0].sort_index()

    base = agg(pipe().execute())
    agg(pipe().execute())      # second learning run
    txt = pipe().explain()     # verifier-gated optimize
    assert "algo=broadcast" in txt
    # the groupby is NOT localized: the probe scan carries no witness
    assert ", local" not in txt
    got = agg(pipe().execute())
    assert list(got.index) == list(base.index)
    np.testing.assert_allclose(got.to_numpy(dtype=float),
                               base.to_numpy(dtype=float), rtol=1e-3)


def test_broadcast_side_tables_agree():
    """The three deliberately-independent copies of the broadcast
    build-side legality invariant (optimizer choice table, verifier
    soundness table, runtime gate) must agree AS SETS per join type —
    layering forbids sharing them, so this pin is what keeps planner
    choice, verifier acceptance and runtime eligibility from silently
    desynchronizing when a join type is added."""
    from cylon_tpu.ops import join as _join
    from cylon_tpu.plan import optimizer as opt_mod
    from cylon_tpu.plan import verify as verify_mod

    runtime = {jt.name.lower(): set(sides) for jt, sides in
               dist_ops._BCAST_LEGAL_SIDES.items()}
    planner = {how: set(sides) for how, sides in
               opt_mod._BROADCAST_SIDES.items()}
    verifier = {how: set(sides) for how, sides in
                verify_mod._BROADCAST_SIDES.items()}
    assert planner == verifier == runtime
    # every OTHER join type is illegal everywhere
    for jt in _join.JoinType:
        if jt.name.lower() not in runtime:
            assert dist_ops._BCAST_LEGAL_SIDES.get(jt, ()) == ()


def test_broadcast_fires_when_only_probe_pays(dist_ctx, monkeypatch):
    """Review finding pin: a build side already co-partitioned on the
    join keys (its exchange would elide) must NOT block the rewrite —
    the probe side still pays the dominant all-to-all, which is
    exactly what broadcast elides. Only a fully co-partitioned join
    (both sides exchange-free) skips the rewrite."""
    monkeypatch.setenv("CYLON_STATS_MIN_OBS", "2")
    left, right = _tables(dist_ctx, 4096, 16, seed=32)
    placed_build = dist_ops.shuffle(right, ["k"])   # witnessed on k
    assert placed_build._hash_partitioned is not None

    def pipe():
        return plan.scan(left).join(plan.scan(placed_build), on="k")

    base = _canon(pipe().execute())
    assert _canon(pipe().execute()) == base
    txt = pipe().explain()
    assert "algo=broadcast" in txt, txt
    assert _canon(pipe().execute()) == base
    # ...while a FULLY co-partitioned join keeps the (free) shuffle
    # plan: both sides elide, broadcast would trade nothing for a
    # gather
    placed_probe = dist_ops.shuffle(
        _tables(dist_ctx, 4096, 16, seed=32)[0], ["k"])

    def pipe2():
        return plan.scan(placed_probe).join(plan.scan(placed_build),
                                            on="k")

    for _ in range(2):
        pipe2().execute()
    assert "algo=broadcast" not in pipe2().explain()


# ---------------------------------------------------------------------------
# verifier: broadcast claims
# ---------------------------------------------------------------------------


def _optimized_broadcast_plan(left, right, world=4):
    lt = plan.scan(left).join(plan.scan(right), on="k")
    _feed_join_inputs(lt._node, world, left_bytes=1 << 20,
                      right_bytes=1 << 8)
    root, _ = optimize(lt._plan_copy(), world)
    return root


def test_verifier_rejects_mutated_broadcast_claims(dist_ctx):
    left, right = _tables(dist_ctx, 512, 16, seed=25)
    root = _optimized_broadcast_plan(left, right)
    join = next(n for n in ir.walk(root) if n.kind == "join")
    assert join.algorithm == "broadcast"
    assert verify_plan(root, 4) == []
    # (a) build side stripped: no replication witness at all
    join.build_side = None
    problems = verify_plan(root, 4)
    assert problems and "replication witness" in problems[0]
    with pytest.raises(CylonPlanError):
        check_plan(root, 4)
    # (b) a LEFT join claiming to replicate its LEFT input
    join.build_side = 0
    join.how = "left"
    problems = verify_plan(root, 4)
    assert problems and "not replicable" in problems[0]
    # (c) restored claim verifies clean again
    join.how = "inner"
    join.build_side = 1
    assert verify_plan(root, 4) == []


def test_verifier_rejects_witness_claim_above_salted_shuffle(dist_ctx):
    """A salted shuffle provides no placement witness: a groupby
    marked local over one is an unjustified elision."""
    left, _ = _tables(dist_ctx, 512, 16, seed=26)
    lt = plan.scan(left).shuffle(["k"]).groupby("k", ["v"], ["sum"])
    root, _ = optimize(lt._plan_copy(), 4)
    gb = next(n for n in ir.walk(root) if n.kind == "groupby")
    sh = next(n for n in ir.walk(root) if n.kind == "shuffle")
    gb.local_ok = True
    assert verify_plan(root, 4) == []      # unsalted: justified
    sh.salted = True
    problems = verify_plan(root, 4)
    assert problems and "local_ok" in problems[0]


# ---------------------------------------------------------------------------
# hot-key salting
# ---------------------------------------------------------------------------


def _zipf_table(ctx, n, seed=0):
    rng = np.random.default_rng(seed)
    k = np.where(rng.random(n) < 0.7, 7,
                 rng.integers(0, 1000, n)).astype(np.int32)
    return ct.Table.from_pydict(ctx, {
        "k": k, "v": np.arange(n, dtype=np.float32)})


def _shard_rows(ctx, table):
    em = np.asarray(jax.device_get(table.emit_mask()))
    w = ctx.get_world_size()
    per = em.shape[0] // w
    return [int(em[i * per:(i + 1) * per].sum()) for i in range(w)]


@pytest.mark.parametrize("world_fixture", ["dist_ctx", "dist_ctx8"])
def test_salted_exchange_bit_identity_and_max_shard(world_fixture,
                                                    request):
    ctx = request.getfixturevalue(world_fixture)
    n = 8192
    plain = dist_ops.shuffle(_zipf_table(ctx, n, seed=27), ["k"])
    s0 = _counter("cylon_salted_exchanges_total")
    salted = dist_ops.shuffle(_zipf_table(ctx, n, seed=27), ["k"],
                              salted=True)
    assert _counter("cylon_salted_exchanges_total") == s0 + 1
    # bit-identity post-unsalt: the global row multiset is unchanged
    # (the salt lives only in the routing, never in the payload)
    assert _canon(plain) == _canon(salted)
    # ...and the hot destination's load measurably spread
    assert max(_shard_rows(ctx, salted)) < max(_shard_rows(ctx, plain))
    # salted placement carries NO witness
    assert salted._hash_partitioned is None
    assert plain._hash_partitioned is not None


def test_salted_uniform_keys_are_untouched(dist_ctx):
    """No hot destination -> the salt program changes nothing (the
    spread applies only to destinations past the warn factor)."""
    rng = np.random.default_rng(28)
    t0 = ct.Table.from_pydict(dist_ctx, {
        "k": rng.integers(0, 4096, 4096).astype(np.int32),
        "v": np.arange(4096, dtype=np.float32)})
    t1 = ct.Table.from_pydict(dist_ctx, {
        "k": np.asarray(t0.to_pydict()["k"]),
        "v": np.arange(4096, dtype=np.float32)})
    plain = dist_ops.shuffle(t0, ["k"])
    salted = dist_ops.shuffle(t1, ["k"], salted=True)
    assert _shard_rows(dist_ctx, plain) == _shard_rows(dist_ctx, salted)
    assert _canon(plain) == _canon(salted)


def test_salting_learned_from_measured_skew(dist_ctx, monkeypatch):
    """The planner loop: a Zipfian standalone shuffle records its raw
    skew; once qualified, the next optimize salts the exchange, spans
    carry salted=True, the digest counts it, and results stay
    bit-identical to the unsalted baseline."""
    monkeypatch.setenv("CYLON_STATS_MIN_OBS", "2")
    src = _zipf_table(dist_ctx, 4096, seed=29)

    def pipe():
        return plan.scan(src).shuffle(["k"])

    base = _canon(pipe().execute())
    r = pipe().execute()
    assert _canon(r) == base
    root, stats = optimize(pipe()._plan_copy(), 4)
    sh = next(n for n in ir.walk(root) if n.kind == "shuffle")
    assert sh.salted and stats.shuffles_salted == 1
    assert verify_plan(root, 4) == []
    p = pipe()
    txt = p.explain(analyze=True)
    assert ", salted" in txt
    d = querylog.recent()[-1]
    assert d["salted_exchanges"] >= 1
    assert _canon(pipe().execute()) == base


def test_skew_threshold_crossing_bumps_epoch(monkeypatch):
    """Review finding pin: a qualified skew EWMA crossing the warn
    threshold (either direction) must bump the adaptive epoch — skew
    is deliberately not drift-checked, so the crossing is what lets a
    cached unsalted template re-decide when keys turn Zipfian (and a
    salted one when they flatten)."""
    monkeypatch.setenv("CYLON_STATS_MIN_OBS", "2")
    monkeypatch.setenv("CYLON_SKEW_WARN_FACTOR", "2.0")
    s = stats_mod.StatsStore()

    def feed(v):
        s._observe_node("p", "fp", "exchange", {"skew": v}, (), None,
                        0.0)

    feed(1.0)
    e0 = s.epoch()
    feed(1.0)                 # qualification crossing
    assert s.epoch() == e0 + 1
    feed(1.1)                 # still cold: no flip
    assert s.epoch() == e0 + 1
    for _ in range(8):        # EWMA climbs past the warn factor
        feed(8.0)
    assert s.epoch() == e0 + 2
    for _ in range(12):       # ...and back under it
        feed(1.0)
    assert s.epoch() == e0 + 3


def test_decision_vector_ignores_join_side_markers(dist_ctx,
                                                   monkeypatch):
    """Review finding pin: join-side Shuffle markers can never salt
    (adapt_from_stats excludes them), so the decision vector must not
    include them — a cross-plan skew qualification on a shared shape
    would otherwise evict templates it could not change."""
    from cylon_tpu.plan.optimizer import (PlanStats, decision_vector,
                                          insert_shuffles)

    left, right = _tables(dist_ctx, 512, 64, seed=33)
    root = plan.scan(left).join(plan.scan(right), on="k")._plan_copy()
    root = insert_shuffles(root, 4, PlanStats())
    shuffles = [n for n in ir.walk(root) if n.kind == "shuffle"]
    assert len(shuffles) == 2      # both are join-side markers
    vec = decision_vector(root, 4)
    assert [v for v in vec if v[0] == "shuffle"] == []
    assert [v for v in vec if v[0] == "join"] == [("join", None)]


def test_salt_factor_zero_disables(dist_ctx, monkeypatch):
    monkeypatch.setenv("CYLON_SALT_FACTOR", "0")
    n = 4096
    plain = dist_ops.shuffle(_zipf_table(dist_ctx, n, seed=30), ["k"])
    salted = dist_ops.shuffle(_zipf_table(dist_ctx, n, seed=30), ["k"],
                              salted=True)
    assert _shard_rows(dist_ctx, plain) == _shard_rows(dist_ctx, salted)
    # a disabled salt keeps the witness (it IS the plain exchange)
    assert salted._hash_partitioned is not None
