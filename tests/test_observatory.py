"""Performance-observatory tests: shuffle skew metrics (span attrs,
registry histograms, EXPLAIN ANALYZE columns), the host-fetch choke point
(one counted, spanned sync per decision fetch) and the jax.monitoring
listener (trace/lower/compile/cache-load seconds by stage and span)."""
import numpy as np
import pytest


# ---------------------------------------------------------------------------
# skew statistics (unit level)
# ---------------------------------------------------------------------------


def test_skew_stats_uniform_matrix():
    from cylon_tpu.telemetry import SkewStats

    counts = np.full((4, 4), 100)
    s = SkewStats.from_counts(counts, bytes_per_row=8)
    assert s.imbalance == 1.0
    assert s.rows_min == s.rows_med == s.rows_max == 400
    assert s.recv_bytes == [3200] * 4
    assert not s.warn
    attrs = s.span_attrs()
    assert attrs["skew_imbalance"] == 1.0
    assert attrs["skew_warn"] is False


def test_skew_stats_hot_destination():
    from cylon_tpu.telemetry import SkewStats

    # every source sends everything to shard 0
    counts = np.zeros((4, 4), int)
    counts[:, 0] = 100
    s = SkewStats.from_counts(counts)
    assert s.imbalance == 4.0          # max 400 / mean 100
    assert s.rows_min == 0 and s.rows_max == 400
    assert s.warn                      # default threshold 2.0
    assert s.send_rows == [100] * 4


def test_skew_stats_degenerate_cases():
    from cylon_tpu.telemetry import SkewStats

    # 1-wide mesh: skew undefined, never measured
    assert SkewStats.from_counts(np.array([[7]])) is None
    assert SkewStats.from_counts(np.zeros((0, 0))) is None
    # empty exchange: nothing is hot
    s = SkewStats.from_counts(np.zeros((4, 4), int))
    assert s.imbalance == 1.0 and not s.warn


def test_skew_warn_factor_env(monkeypatch):
    from cylon_tpu.telemetry import SkewStats, skew

    counts = np.zeros((4, 4), int)
    counts[:, 0] = 10
    counts[:, 1] = 5  # imbalance = 40 / 15 ≈ 2.67
    assert SkewStats.from_counts(counts).warn
    monkeypatch.setenv("CYLON_SKEW_WARN_FACTOR", "3.5")
    assert skew.warn_factor() == 3.5
    assert not SkewStats.from_counts(counts).warn


def test_skew_record_feeds_histograms():
    from cylon_tpu.telemetry import MetricsRegistry, skew

    reg = MetricsRegistry()
    counts = np.full((4, 4), 10)
    stats = skew.observe_exchange(counts, bytes_per_row=16, slots=4 * 64,
                                  registry=reg)
    assert stats is not None
    snap = reg.snapshot()
    assert snap["cylon_shuffle_imbalance_factor"]["count"] == 1
    assert snap["cylon_shuffle_shard_rows"]["count"] == 4
    assert snap["cylon_shuffle_shard_rows"]["max"] == 40
    assert snap["cylon_shuffle_shard_bytes"]["max"] == 640


# ---------------------------------------------------------------------------
# skew end to end: Zipfian shuffle on the 8-wide virtual mesh
# ---------------------------------------------------------------------------


def _zipf_tables(ctx, n=4096, hot=0.9, seed=0):
    """LEFT keys are Zipf-like (one hot key → one hot destination
    shard); RIGHT keys stay uniform so the join output is linear, not
    quadratic — the skew under test lives in the EXCHANGE, and a
    hot-on-both-sides join would make the test pay a many-million-row
    materialize for nothing."""
    import cylon_tpu as ct

    rng = np.random.default_rng(seed)
    k = rng.integers(0, n // 4, n).astype(np.int32)
    k[rng.random(n) < hot] = 7  # one hot key → one hot destination shard
    left = ct.Table.from_pydict(ctx, {
        "k": k, "v": rng.normal(size=n).astype(np.float32)})
    right = ct.Table.from_pydict(ctx, {
        "k": rng.integers(0, n // 4, n).astype(np.int32),
        "w": rng.normal(size=n).astype(np.float32)})
    return left, right


def test_zipf_shuffle_records_imbalance(dist_ctx8):
    from cylon_tpu import telemetry
    from cylon_tpu.parallel import dist_ops

    left, _right = _zipf_tables(dist_ctx8)
    h = telemetry.REGISTRY.histogram("cylon_shuffle_imbalance_factor",
                                     buckets=telemetry.skew.IMBALANCE_BUCKETS)
    n0 = h.count
    with telemetry.collect_phases() as cp:
        dist_ops.shuffle(left, ["k"])
    # the collector carries the Span OBJECTS index-aligned with labels
    assert len(cp.spans) == len(cp.labels)
    ex = [s for s in cp.spans if s.name.startswith("shuffle.exchange")]
    assert ex, cp.labels
    attrs = ex[0].attrs
    # ~90% of rows hash to one shard of 8: imbalance far above warn
    assert attrs["skew_imbalance"] > 2.0
    assert attrs["skew_warn"] is True
    assert attrs["shard_rows_max"] > 8 * attrs["shard_rows_med"] / 2
    assert h.count > n0
    snap = telemetry.metrics_snapshot()
    assert snap["cylon_shuffle_shard_rows"]["count"] >= 8


def test_zipf_explain_analyze_skew_columns(dist_ctx8):
    from cylon_tpu import plan

    left, right = _zipf_tables(dist_ctx8)
    pipe = plan.scan(left).join(plan.scan(right), on="k") \
        .groupby("lt-0", ["rt-3"], ["sum"])
    txt = pipe.explain(analyze=True)
    assert "skew(imb=" in txt
    assert "[SKEW]" in txt, txt
    rep = pipe.last_report
    skewed = [m for m in _walk_measures(rep.root) if m.skew is not None]
    assert skewed
    worst = max(m.skew["imbalance"] for m in skewed)
    assert worst > 2.0
    d = rep.to_dict()
    node_skews = _walk_dict_skews(d["plan"])
    assert any(s and s["warn"] for s in node_skews)


def test_uniform_explain_analyze_no_warn(dist_ctx8):
    """A uniform-hash pipeline shows skew columns near 1.0 and never
    the [SKEW] marker."""
    import cylon_tpu as ct
    from cylon_tpu import plan

    rng = np.random.default_rng(3)
    n = 4096
    left = ct.Table.from_pydict(dist_ctx8, {
        "k": rng.integers(0, n, n).astype(np.int32),
        "v": rng.normal(size=n).astype(np.float32)})
    right = ct.Table.from_pydict(dist_ctx8, {
        "k": rng.integers(0, n, n).astype(np.int32),
        "w": rng.normal(size=n).astype(np.float32)})
    pipe = plan.scan(left).join(plan.scan(right), on="k")
    txt = pipe.explain(analyze=True)
    assert "skew(imb=" in txt
    assert "[SKEW]" not in txt, txt


def _walk_measures(m):
    yield m
    for c in m.children:
        yield from _walk_measures(c)


def _walk_dict_skews(d):
    yield d.get("skew")
    for c in d.get("children", []):
        yield from _walk_dict_skews(c)


# ---------------------------------------------------------------------------
# host-sync counter
# ---------------------------------------------------------------------------


def test_host_sync_counter_at_shuffle_count(dist_ctx):
    import cylon_tpu as ct
    from cylon_tpu import telemetry
    from cylon_tpu.parallel import dist_ops

    def site(name):
        return telemetry.metrics_snapshot().get(
            f'cylon_host_syncs_total{{site="{name}"}}', 0)

    s0 = site("shuffle.count")
    t = ct.Table.from_pydict(dist_ctx, {
        "k": np.arange(512, dtype=np.int32) % 32,
        "v": np.arange(512.0).astype(np.float32)})
    dist_ops.shuffle(t, ["k"])
    assert site("shuffle.count") == s0 + 1


def test_host_sync_counter_pair_and_plan(dist_ctx):
    import cylon_tpu as ct
    from cylon_tpu import telemetry

    def site(name):
        return telemetry.metrics_snapshot().get(
            f'cylon_host_syncs_total{{site="{name}"}}', 0)

    rng = np.random.default_rng(1)
    n = 512
    t1 = ct.Table.from_pydict(dist_ctx, {
        "k": rng.integers(0, 64, n).astype(np.int32),
        "v": rng.normal(size=n).astype(np.float32)})
    t2 = ct.Table.from_pydict(dist_ctx, {
        "k": rng.integers(0, 64, n).astype(np.int32),
        "w": rng.normal(size=n).astype(np.float32)})
    p0 = site("shuffle.count_pair")
    j0 = site("join.plan")
    t1.distributed_join(t2, "inner", on="k")
    assert site("shuffle.count_pair") == p0 + 1
    assert site("join.plan") == j0 + 1


# ---------------------------------------------------------------------------
# the host-fetch choke point (telemetry.host_fetch): every fetch that
# decides the next dispatch is one sync.<site> span and one count
# ---------------------------------------------------------------------------


def _syncs():
    from cylon_tpu import telemetry

    return sum(v for k, v in telemetry.metrics_snapshot().items()
               if k.startswith("cylon_host_syncs_total"))


def _fresh_pair(ctx, seed, n=2048):
    import cylon_tpu as ct

    rng = np.random.default_rng(seed)
    left = ct.Table.from_pydict(ctx, {
        "k": rng.permutation(n).astype(np.int32),
        "v": rng.normal(size=n).astype(np.float32)})
    right = ct.Table.from_pydict(ctx, {
        "k": rng.integers(0, n, n).astype(np.int32),
        "w": rng.normal(size=n).astype(np.float32)})
    return left, right


def _planned(kind, left, right):
    from cylon_tpu import plan

    if kind == "join":
        return plan.scan(left).join(plan.scan(right), on="k")
    if kind == "groupby-dense":     # 16 groups: the key's range is small
        return plan.scan(right).filter(
            plan.col("k") < 16).groupby("k", ["w"], ["sum"])
    if kind == "groupby-packed":    # an integer column beside the float
        return plan.scan(right).groupby("k", ["k", "w"], ["sum", "sum"])
    return plan.scan(right).groupby("k", ["w"], ["sum"])


@pytest.mark.parametrize("kind,sites,operator", [
    ("join", ["sync.join.count"], "join.plan"),
    # the key's range (2,048 values: past DENSE_MAX_SLOTS) is probed,
    # then the rows are sorted and the group count fetched
    ("groupby", ["sync.groupby.keyrange", "sync.groupby.groups"],
     "plan.groupby"),
    # few groups: the probe's fetch is the only one
    ("groupby-dense", ["sync.groupby.keyrange"], "plan.groupby"),
    # a sort that could carry an integer column in the key's spare bits
    # (a table of SORT_PACK_MIN_ROWS rows or more) fetches its range too
    ("groupby-packed", ["sync.groupby.keyrange", "sync.groupby.valuerange",
                        "sync.groupby.groups"], "plan.groupby"),
])
def test_planned_local_operator_is_one_counted_spanned_sync(
        local_ctx, monkeypatch, kind, sites, operator):
    """A planned local join / groupby on fresh inputs: the counter grows
    by exactly 1 a fetch and exactly that many ``sync.*`` spans open, as
    children of the operator's span (what the benchmark's
    ``host_syncs_per_query`` and ``sync_idle_ms_per_query`` read in a
    trace): one for the join and for a groupby over few groups, two for
    a groupby that sorts, three for one whose sort packs."""
    from cylon_tpu import telemetry
    from cylon_tpu.ops import groupby

    monkeypatch.setattr(groupby, "SORT_PACK_MIN_ROWS", 0)

    pipe = _planned(kind, *_fresh_pair(local_ctx, 31))
    s0 = _syncs()
    with telemetry.collect_phases() as cp:
        out = pipe.execute()
    assert _syncs() == s0 + len(sites)
    sync_spans = [sp for sp in cp.spans if sp.name.startswith("sync.")]
    assert [sp.name for sp in sync_spans] == sites
    for sync in sync_spans:
        parent = [sp for sp in cp.spans if sp.span_id == sync.parent_id]
        assert [sp.name for sp in parent] == [operator]
        assert sync.elapsed_ms is not None
    assert out.row_count > 0


def test_repeat_join_on_the_same_buffers_counts_no_sync(local_ctx):
    """The count memo answers a repeat join of the same arrays: no
    fetch, so no count and no span (the fetch sits INSIDE the memo's
    compute)."""
    from cylon_tpu import telemetry

    left, right = _fresh_pair(local_ctx, 32)
    _planned("join", left, right).execute()
    s0 = _syncs()
    with telemetry.collect_phases() as cp:
        _planned("join", left, right).execute()
    assert _syncs() == s0
    assert cp.count("sync.") == 0


def test_row_count_of_a_masked_table_is_one_counted_sync(local_ctx):
    from cylon_tpu import telemetry
    from cylon_tpu.telemetry import flight

    def site():
        return telemetry.metrics_snapshot().get(
            'cylon_host_syncs_total{site="row_count"}', 0)

    left, _right = _fresh_pair(local_ctx, 33)
    t = left.filter_mask(left.get_column(0).data % 2 == 0)
    ring0 = [sp.span_id for sp in flight.recent()]
    s0 = site()
    assert t.row_count == 1024
    assert site() == s0 + 1
    assert t.row_count == 1024       # cached: no second fetch
    assert site() == s0 + 1
    # a parentless sync span is no query tree: the flight ring keeps
    # its query history
    assert [sp.span_id for sp in flight.recent()] == ring0


def test_host_fetch_spans_the_blocking_call_and_counts_once():
    import jax.numpy as jnp

    from cylon_tpu import telemetry

    key = 'cylon_host_syncs_total{site="probe.fetch"}'
    c0 = telemetry.metrics_snapshot().get(key, 0)
    with telemetry.collect_phases() as cp, \
            telemetry.span("probe.operator") as op:
        got = telemetry.host_fetch(
            "probe.fetch", (jnp.arange(4), {"n": jnp.int32(7)}, None))
    # a pytree comes back as host values, in one round trip
    assert isinstance(got[0], np.ndarray) and got[0].tolist() == [0, 1, 2, 3]
    assert int(got[1]["n"]) == 7 and got[2] is None
    assert telemetry.metrics_snapshot()[key] == c0 + 1
    assert cp.labels == ["probe.operator", "sync.probe.fetch"]
    assert cp.spans[1].parent_id == op.span_id
    assert op.children == [cp.spans[1]]


# ---------------------------------------------------------------------------
# trace / lower / compile / cache-load seconds (telemetry/profiler.py:
# the always-on jax.monitoring listener)
# ---------------------------------------------------------------------------


def _jit_seconds(phase):
    from cylon_tpu import telemetry

    snap = telemetry.metrics_snapshot()
    return {st: snap.get(
        f'cylon_jit_seconds_total{{phase="{phase}",stage="{st}"}}', 0.0)
        for st in ("trace", "lower", "compile", "cache_load")}


def _jit_events():
    from cylon_tpu import telemetry

    snap = telemetry.metrics_snapshot()
    return {st: snap.get(f'cylon_jit_events_total{{stage="{st}"}}', 0)
            for st in ("trace", "lower", "compile", "cache_load")}


def test_listener_bills_a_first_call_to_the_open_span_and_no_second():
    import jax
    import jax.numpy as jnp

    from cylon_tpu import telemetry

    @jax.jit
    def _listener_probe(x):
        return jnp.cumsum(x * 3) + 1

    x = jnp.arange(32.0)
    e0 = _jit_events()
    with telemetry.span("probe.first_call"):
        _listener_probe(x).block_until_ready()
    first = _jit_seconds("probe.first_call")
    assert first["trace"] > 0 and first["lower"] > 0
    assert first["compile"] > 0
    e1 = _jit_events()
    for st in ("trace", "lower", "compile"):
        assert e1[st] > e0[st]
    # the same shapes again: no trace, no lowering, no compile
    with telemetry.span("probe.second_call"):
        _listener_probe(x).block_until_ready()
    assert _jit_seconds("probe.second_call") == {
        "trace": 0.0, "lower": 0.0, "compile": 0.0, "cache_load": 0.0}
    assert _jit_events() == e1
    # outside any span the seconds go to phase="none"
    n0 = _jit_seconds("none")
    jax.jit(lambda v: v - 2)(x).block_until_ready()
    assert _jit_seconds("none")["compile"] > n0["compile"]


def test_listener_counts_self_time_once():
    """Nested events (a helper traced inside an outer trace) and the
    cache retrieval inside a backend compile are taken out of the
    enclosing event's seconds: the stages add up to the wall time."""
    from cylon_tpu import telemetry
    from cylon_tpu.telemetry import profiler

    trace = "/jax/core/compile/jaxpr_trace_duration"
    compile_ = "/jax/core/compile/backend_compile_duration"
    t = 4e9   # later than any real event of this process
    with telemetry.span("probe.nesting"):
        profiler._on_time_span(trace, t + 1, t + 2, fun_name="inner")
        profiler._on_time_span(trace, t + 3, t + 5, fun_name="inner2")
        profiler._on_time_span(trace, t, t + 10, fun_name="outer")
        # a sibling after it absorbs nothing
        profiler._on_time_span(trace, t + 10, t + 11, fun_name="next")
        profiler._on_duration(
            "/jax/compilation_cache/cache_retrieval_time_sec", 0.25)
        profiler._on_time_span(compile_, t + 11, t + 12, fun_name="outer")
        # an event that is none of the stages is ignored
        profiler._on_time_span("/jax/other", 0.0, 50.0)
        profiler._on_duration("/jax/other_duration", 50.0)
    del profiler._local.done[-3:]   # outer, next, compile: not real
    got = _jit_seconds("probe.nesting")
    assert got["trace"] == pytest.approx(1 + 2 + 7 + 1)
    assert got["cache_load"] == pytest.approx(0.25)
    assert got["compile"] == pytest.approx(0.75)
    assert got["lower"] == 0.0


def test_profiler_summary_reads_the_counters_back():
    import jax
    import jax.numpy as jnp

    from cylon_tpu import telemetry
    from cylon_tpu.telemetry import profiler

    with telemetry.span("probe.summary"):
        jax.jit(lambda v: v * 5 - 1)(jnp.arange(8.0)).block_until_ready()
    s = profiler.summary()
    assert set(s) == set(profiler.STAGES)
    assert s["compile"]["events"] >= 1
    assert s["compile"]["by_phase"]["probe.summary"] > 0
    assert s["trace"]["seconds"] == pytest.approx(
        sum(s["trace"]["by_phase"].values()))


def test_counted_cache_returns_the_bare_build_result():
    """No proxy any more: a factory hands back what it built."""
    from cylon_tpu.telemetry import counted_cache

    built = []

    @counted_cache
    def _bare_probe_fn():
        built.append(lambda: 41)
        return built[-1]

    assert _bare_probe_fn() is built[0]
    assert _bare_probe_fn()() == 41 and len(built) == 1


def test_explain_analyze_node_time_waits_for_the_device(local_ctx,
                                                        monkeypatch):
    """A node's ``actual time`` is taken after block_until_ready on the
    node's buffers, not at dispatch."""
    import jax

    from cylon_tpu.plan import executor

    order = []
    real = jax.block_until_ready
    monkeypatch.setattr(executor.jax, "block_until_ready",
                        lambda x: (order.append("ready"), real(x))[1])
    real_clock = executor.time.perf_counter

    def clock():
        order.append("clock")
        return real_clock()

    monkeypatch.setattr(executor.time, "perf_counter", clock)
    pipe = _planned("join", *_fresh_pair(local_ctx, 34))
    pipe.explain(analyze=True)
    # for every recorded node: clock, ... , ready, clock
    assert "ready" in order
    for i, what in enumerate(order):
        if what == "ready":
            assert order[i + 1] == "clock"


