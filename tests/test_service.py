"""Service-tier tests: the plan/fingerprint cache (determinism,
collision sensitivity, cross-process stability, poisoned-entry
rejection), the library-mode optimize memo, and the concurrent query
scheduler (fair-share DRR, backpressure, outcomes, tenant forensics)."""
import gc
import os
import subprocess
import sys
import textwrap
import threading

import numpy as np
import pytest

import cylon_tpu as ct
from cylon_tpu import plan, telemetry
from cylon_tpu.plan import ir
from cylon_tpu.resilience import inject
from cylon_tpu.service import plancache
from cylon_tpu.service.plancache import fingerprint, global_cache
from cylon_tpu.service.scheduler import QueryService
from cylon_tpu.status import (CylonPlanError, CylonResourceExhausted,
                              CylonTimeoutError)
from cylon_tpu.telemetry import flight, ledger


@pytest.fixture(autouse=True)
def _clean():
    yield
    inject.disarm()
    global_cache().clear()


def _tables(ctx, n=512, seed=0, kdtype=np.int32):
    rng = np.random.default_rng(seed)
    left = ct.Table.from_pydict(ctx, {
        "k": rng.integers(0, max(n // 4, 1), n).astype(kdtype),
        "v": rng.normal(size=n).astype(np.float32),
        "z": rng.integers(0, 50, n).astype(np.int32)})
    right = ct.Table.from_pydict(ctx, {
        "k": rng.integers(0, max(n // 4, 1), n).astype(kdtype),
        "w": rng.normal(size=n).astype(np.float32)})
    return left, right


def _pipe(left, right):
    return plan.scan(left).join(plan.scan(right), on="k") \
        .groupby("lt-2", ["rt-4"], ["sum"])


def _rows(table):
    d = table.to_pydict()
    ks = sorted(d)
    return ks, sorted(zip(*(np.asarray(d[k]).tolist() for k in ks)))


def _counter(prefix):
    return sum(v for k, v in telemetry.metrics_snapshot().items()
               if k.startswith(prefix) and isinstance(v, int))


# ---------------------------------------------------------------------------
# fingerprint determinism + collision sensitivity
# ---------------------------------------------------------------------------


def test_fingerprint_equal_shape_different_tables_hits(dist_ctx):
    l0, r0 = _tables(dist_ctx, seed=1)
    l1, r1 = _tables(dist_ctx, seed=2)
    assert fingerprint(_pipe(l0, r0)._node, 4) == \
        fingerprint(_pipe(l1, r1)._node, 4)


def test_fingerprint_misses_on_semantic_changes(dist_ctx):
    left, right = _tables(dist_ctx, seed=3)
    base = fingerprint(_pipe(left, right)._node, 4)

    # dtype change on a key column
    l64, r64 = _tables(dist_ctx, seed=3, kdtype=np.int64)
    assert fingerprint(_pipe(l64, r64)._node, 4) != base

    # different join keys
    lt, rt = plan.scan(left), plan.scan(right)
    other = lt.join(rt, left_on="z", right_on="k") \
        .groupby("lt-2", ["rt-4"], ["sum"])
    assert fingerprint(other._node, 4) != base

    # world size
    assert fingerprint(_pipe(left, right)._node, 8) != base

    # projection order
    p01 = plan.scan(left).project(["k", "v"])
    p10 = plan.scan(left).project(["v", "k"])
    assert fingerprint(p01._node, 4) != fingerprint(p10._node, 4)

    # filter expression: operator AND literal both count
    f_gt3 = plan.scan(left).filter(plan.col("v") > 3.0)
    f_gt4 = plan.scan(left).filter(plan.col("v") > 4.0)
    f_lt3 = plan.scan(left).filter(plan.col("v") < 3.0)
    fps = {fingerprint(f._node, 4) for f in (f_gt3, f_gt4, f_lt3)}
    assert len(fps) == 3

    # witness shape is part of the key (the optimizer elides on it)
    sh = ct.shuffle(left, [0])
    assert fingerprint(plan.scan(sh).sort("k")._node, 4) != \
        fingerprint(plan.scan(left).sort("k")._node, 4)

    # column NAMES are part of the key — a hit must never render
    # another query's names in EXPLAIN trees or admission forensics
    arr = np.arange(16, dtype=np.int32)
    named_k = ct.Table.from_pydict(dist_ctx, {"k": arr})
    named_q = ct.Table.from_pydict(dist_ctx, {"q": arr})
    assert fingerprint(plan.scan(named_k)._node, 4) != \
        fingerprint(plan.scan(named_q)._node, 4)


def test_fingerprint_stable_across_processes(dist_ctx):
    """No id()/hash-seed dependence: two fresh interpreters with
    different PYTHONHASHSEED values derive the identical fingerprint
    for the canonical pipeline."""
    left, right = _tables(dist_ctx, seed=5)
    here = fingerprint(_pipe(left, right)._node, 4)
    prog = textwrap.dedent("""
        import numpy as np
        import cylon_tpu as ct
        from cylon_tpu import plan
        from cylon_tpu.service.plancache import fingerprint
        ctx = ct.CylonContext.InitDistributed(ct.TPUConfig(world_size=4))
        rng = np.random.default_rng(99)
        n = 512
        left = ct.Table.from_pydict(ctx, {
            "k": rng.integers(0, n // 4, n).astype(np.int32),
            "v": rng.normal(size=n).astype(np.float32),
            "z": rng.integers(0, 50, n).astype(np.int32)})
        right = ct.Table.from_pydict(ctx, {
            "k": rng.integers(0, n // 4, n).astype(np.int32),
            "w": rng.normal(size=n).astype(np.float32)})
        p = plan.scan(left).join(plan.scan(right), on="k") \\
            .groupby("lt-2", ["rt-4"], ["sum"])
        print(fingerprint(p._node, 4))
    """)
    outs = []
    for seed in ("0", "1"):
        env = dict(os.environ, PYTHONHASHSEED=seed,
                   JAX_PLATFORMS="cpu")
        env["XLA_FLAGS"] = env.get("XLA_FLAGS", "") + \
            " --xla_force_host_platform_device_count=8"
        r = subprocess.run([sys.executable, "-c", prog],
                           capture_output=True, text=True, timeout=600,
                           env=env)
        assert r.returncode == 0, r.stderr
        outs.append(r.stdout.strip().splitlines()[-1])
    assert outs[0] == outs[1]
    # data/seed differences don't perturb the fingerprint either: the
    # subprocess used different table CONTENT than this process
    assert outs[0] == here


# ---------------------------------------------------------------------------
# plan cache semantics
# ---------------------------------------------------------------------------


def test_cache_hit_skips_optimize_and_matches_eager(dist_ctx):
    l0, r0 = _tables(dist_ctx, seed=7)
    l1, r1 = _tables(dist_ctx, seed=8)
    global_cache().clear()
    m0, h0 = _counter("cylon_plan_cache_misses_total"), \
        _counter("cylon_plan_cache_hits_total")
    a = _pipe(l0, r0).execute()
    assert _counter("cylon_plan_cache_misses_total") == m0 + 1
    b = _pipe(l1, r1).execute()          # same shape, other tables
    assert _counter("cylon_plan_cache_hits_total") == h0 + 1
    # the cached physical plan must execute IDENTICALLY to a fresh one
    with plancache.disabled():
        fresh = _pipe(l1, r1).execute()
    assert _rows(b) == _rows(fresh)
    # uncached eager agreement for the first query too
    p = _pipe(l0, r0)
    with plancache.disabled():
        assert _rows(a) == _rows(p.execute())


def test_cache_hit_preserves_stats_and_explain(dist_ctx):
    left, right = _tables(dist_ctx, seed=9)
    global_cache().clear()
    p = _pipe(left, right)
    root1, stats1 = p.optimized()
    root2, stats2 = p.optimized()        # hit
    assert stats2 is not stats1          # callers own their stats copy
    assert stats1.shuffles_inserted == stats2.shuffles_inserted
    assert stats1.shuffles_elided == stats2.shuffles_elided
    assert ir.format_plan(root1) == ir.format_plan(root2)


def test_cache_does_not_pin_tables(dist_ctx):
    """Cached templates must hold NO table references — the cache must
    never extend device-buffer lifetimes (the ledger discipline)."""
    left, right = _tables(dist_ctx, seed=10)
    global_cache().clear()
    _pipe(left, right).optimized()
    cache = global_cache()
    with cache._lock:
        entries = list(cache._entries.values())
    assert entries
    for tmpl, _stats, _epoch, _vec in entries:
        for node in ir.walk(tmpl):
            if node.kind == "scan":
                assert node.table is None and node.table_id is None


def test_cache_bounded_lru_evicts(dist_ctx, monkeypatch):
    monkeypatch.setenv("CYLON_PLAN_CACHE_MAX", "2")
    left, right = _tables(dist_ctx, seed=11)
    global_cache().clear()
    e0 = _counter("cylon_plan_cache_evictions_total")
    for cols in (["k"], ["v"], ["z"], ["k", "v"]):
        plan.scan(left).project(cols).optimized()
    assert len(global_cache()) == 2
    assert _counter("cylon_plan_cache_evictions_total") == e0 + 2
    del right


def test_cache_disabled_by_env(dist_ctx, monkeypatch):
    monkeypatch.setenv("CYLON_PLAN_CACHE_MAX", "0")
    left, right = _tables(dist_ctx, seed=12)
    global_cache().clear()
    h0 = _counter("cylon_plan_cache_hits_total")
    _pipe(left, right).optimized()
    _pipe(left, right).optimized()
    assert _counter("cylon_plan_cache_hits_total") == h0
    assert len(global_cache()) == 0


def test_poisoned_cache_entry_rejected_on_hit(dist_ctx):
    """A cache must never launder an unverified plan: hand-poison the
    stored template (an unjustified GroupBy.local_ok claim) and the
    next equal-shape query must be REJECTED by the witness verifier —
    typed CylonPlanError — and the entry evicted, after which a fresh
    optimize repopulates cleanly."""
    assert os.environ.get("CYLON_TPU_VERIFY_PLANS") == "1"
    left, right = _tables(dist_ctx, seed=13)
    global_cache().clear()
    _pipe(left, right).execute()         # insert (verified)
    cache = global_cache()
    with cache._lock:
        assert len(cache._entries) == 1
        (tmpl, _stats, _epoch, _vec), = cache._entries.values()
    poisoned = False
    for node in ir.walk(tmpl):
        if node.kind == "groupby" and not node.local_ok:
            node.local_ok = True         # a witness-free local claim
            poisoned = True
    assert poisoned
    with pytest.raises(CylonPlanError):
        _pipe(left, right).execute()
    # the poisoned entry was dropped; the shape re-optimizes cleanly
    assert len(cache) == 0
    res = _pipe(left, right).execute()
    with plancache.disabled():
        assert _rows(res) == _rows(_pipe(left, right).execute())


def test_library_mode_execute_memoized(dist_ctx):
    """Plain repeated collect() on an equal-shape query skips
    re-optimization — no service object anywhere."""
    left, right = _tables(dist_ctx, seed=14)
    global_cache().clear()
    h0 = _counter("cylon_plan_cache_hits_total")
    _pipe(left, right).execute()
    _pipe(left, right).execute()
    _pipe(left, right).execute()
    assert _counter("cylon_plan_cache_hits_total") == h0 + 2


# ---------------------------------------------------------------------------
# the scheduler
# ---------------------------------------------------------------------------


def test_service_results_match_direct_execution(dist_ctx):
    tabs = {t: _tables(dist_ctx, seed=20 + i)
            for i, t in enumerate(("a", "b"))}
    direct = {t: _rows(_pipe(*tabs[t]).execute()) for t in tabs}
    svc = QueryService(start=False)
    tickets = [(t, svc.submit(_pipe(*tabs[t]), tenant=t))
               for t in tabs for _ in range(2)]
    svc.drain(timeout=600)
    for t, tk in tickets:
        assert tk.outcome == "ok"
        assert tk.wait_s is not None and tk.wait_s >= 0
        assert _rows(tk.result(timeout=60)) == direct[t]
    svc.close()


def test_service_backpressure_typed_before_enqueue(dist_ctx,
                                                   monkeypatch):
    monkeypatch.setenv("CYLON_SERVICE_QUEUE_MAX", "2")
    left, right = _tables(dist_ctx, seed=22)
    svc = QueryService(start=False)      # paused: nothing drains
    svc.submit(_pipe(left, right), tenant="a")
    svc.submit(_pipe(left, right), tenant="a")
    with pytest.raises(CylonResourceExhausted, match="queue full"):
        svc.submit(_pipe(left, right), tenant="b")
    # the rejection left a tenant-labeled forensic record
    last = flight.admissions()[-1]
    assert last["action"] == "shed" and last["tenant"] == "b"
    assert "queue full" in last["reason"]
    # the rejected tenant's depth never moved
    assert svc.depth("b") == 0 and svc.depth() == 2
    monkeypatch.setenv("CYLON_SERVICE_QUEUE_MAX", "256")
    svc.drain(timeout=600)
    svc.close()


def test_service_drr_fair_share(dist_ctx):
    """A tenant flooding the queue cannot starve another: six cheap
    queries from tenant a are submitted BEFORE tenant b's one; DRR
    dispatches b's within the first two slots."""
    left, right = _tables(dist_ctx, seed=23)
    svc = QueryService(start=False)
    a_tickets = [svc.submit(plan.scan(left).sort("k"), tenant="a")
                 for _ in range(6)]
    b_ticket = svc.submit(plan.scan(right).sort("k"), tenant="b")
    svc.drain(timeout=600)
    assert b_ticket.dispatch_seq <= 2
    # FIFO within a tenant: a's queries dispatched in submission order
    seqs = [t.dispatch_seq for t in a_tickets]
    assert seqs == sorted(seqs)
    svc.close()


def test_service_drr_cost_weighted(dist_ctx, monkeypatch):
    """Deficit round-robin is BYTE-weighted: with a tiny quantum, a
    tenant whose head query is 'expensive' accumulates deficit over
    several sweeps while the cheap tenant keeps being served."""
    monkeypatch.setenv("CYLON_SERVICE_QUANTUM_BYTES", "1024")
    big_l, big_r = _tables(dist_ctx, n=4096, seed=24)
    small_l, _ = _tables(dist_ctx, n=64, seed=25)
    svc = QueryService(start=False)
    exp = svc.submit(_pipe(big_l, big_r), tenant="expensive")
    cheap = [svc.submit(plan.scan(small_l).sort("k"), tenant="cheap")
             for _ in range(3)]
    svc.drain(timeout=600)
    # the expensive query needed many quanta; every cheap one (cost ~
    # a few KiB) overtakes it despite later submission
    assert exp.dispatch_seq == 4
    assert [c.dispatch_seq for c in cheap] == [1, 2, 3]
    svc.close()


def test_service_shed_typed_others_unaffected(dist_ctx):
    left, right = _tables(dist_ctx, seed=26)
    big_l, big_r = _tables(dist_ctx, n=1 << 16, seed=27)
    direct = _rows(_pipe(left, right).execute())
    marker_spans = []

    roots = {}

    def sink(s):
        if s.name == "plan.admission":
            marker_spans.append(s)
        if s.name == "service.query":
            roots[s.span_id] = s

    svc = QueryService(start=False)
    inject.arm("pool:262144:oom")
    telemetry.add_sink(sink)
    try:
        ok_t = svc.submit(_pipe(left, right), tenant="good")
        shed_t = svc.submit(
            plan.scan(big_l).join(plan.scan(big_r), on="k"),
            tenant="greedy")
        svc.drain(timeout=600)
    finally:
        telemetry.remove_sink(sink)
        inject.disarm()
    assert ok_t.outcome == "ok"
    assert _rows(ok_t.result(timeout=60)) == direct
    assert shed_t.outcome == "shed"
    with pytest.raises(CylonResourceExhausted,
                       match="shed by admission controller"):
        shed_t.result(timeout=60)
    sheds = [d for d in flight.admissions()
             if d.get("action") == "shed"]
    assert sheds and sheds[-1]["tenant"] == "greedy"
    # the service-dispatch shed emits the documented plan.admission
    # marker span, under the tenant-stamped service.query root (PR 51:
    # a served query is ONE tree; the marker was a root of its own)
    assert marker_spans
    m = marker_spans[-1]
    assert m.attrs["decision"] == "shed"
    assert roots[m.root_id].attrs["tenant"] == "greedy"
    assert roots[m.root_id].attrs["outcome"] == "shed"
    svc.close()


def test_service_deadline_timeout_outcome(dist_ctx):
    left, right = _tables(dist_ctx, seed=28)
    svc = QueryService(start=False)
    tk = svc.submit(_pipe(left, right), tenant="late",
                    deadline_s=1e-6)
    svc.drain(timeout=600)
    assert tk.outcome == "timeout"
    with pytest.raises(CylonTimeoutError):
        tk.result(timeout=60)
    svc.close()


def test_service_error_outcome_typed(dist_ctx):
    """A persistently faulted query fails TYPED on its own ticket;
    queries after it still complete."""
    left, right = _tables(dist_ctx, seed=29)
    direct = _rows(_pipe(left, right).execute())
    svc = QueryService(start=False)
    inject.arm("exchange:1+:transient")
    try:
        bad = svc.submit(_pipe(left, right), tenant="t")
        svc.drain(timeout=600)
    finally:
        inject.disarm()
    assert bad.outcome == "error"
    with pytest.raises(ct.CylonTransientError):
        bad.result(timeout=60)
    good = svc.submit(_pipe(left, right), tenant="t")
    svc.drain(timeout=600)
    assert good.outcome == "ok"
    assert _rows(good.result(timeout=60)) == direct
    svc.close()


def test_service_tenant_rides_root_spans_and_report(dist_ctx):
    left, right = _tables(dist_ctx, seed=30)
    flight.reset()
    svc = QueryService(name="svc-test", start=False)
    tk = svc.submit(_pipe(left, right), tenant="acme", analyze=True)
    svc.drain(timeout=600)
    rep = tk.report()
    assert rep is not None
    assert rep.span.attrs["tenant"] == "acme"
    assert rep.span.attrs["query_id"] == tk.query_id
    assert rep.span.attrs["service"] == "svc-test"
    # the flight ring's completed-query entry is the served query's
    # root (PR 51: service.query, with the report's plan.query span
    # inside its tree) and carries the labels
    ring = [s for s in flight.recent() if s.name == "service.query"]
    assert ring and rep.span.root_id == ring[-1].span_id
    assert rep.span in list(ring[-1].walk())
    assert ring[-1].attrs["tenant"] == "acme"
    assert ring[-1].attrs["query_id"] == tk.query_id
    assert ring[-1].attrs["service"] == "svc-test"
    svc.close()


def test_service_queue_gauges_and_outcome_counters(dist_ctx):
    left, right = _tables(dist_ctx, seed=31)
    ok0 = telemetry.metrics_snapshot().get(
        'cylon_queries_total{outcome="ok",tenant="gauge-t"}', 0)
    svc = QueryService(start=False)
    for _ in range(3):
        svc.submit(_pipe(left, right), tenant="gauge-t")
    snap = telemetry.metrics_snapshot()
    assert snap['cylon_service_queue_depth{tenant="gauge-t"}'] == 3
    svc.drain(timeout=600)
    snap = telemetry.metrics_snapshot()
    assert snap['cylon_service_queue_depth{tenant="gauge-t"}'] == 0
    assert snap['cylon_queries_total{outcome="ok",tenant="gauge-t"}'] \
        == ok0 + 3
    svc.close()


def test_service_close_paused_fails_queued_tickets(dist_ctx):
    """close() on a never-started service must not strand its queued
    tickets — they finish typed instead of hanging result() forever."""
    left, right = _tables(dist_ctx, seed=36)
    svc = QueryService(start=False)
    tk = svc.submit(_pipe(left, right), tenant="orphan")
    svc.close()
    assert tk.done()
    assert tk.outcome == "error"
    assert svc.depth() == 0
    with pytest.raises(CylonPlanError, match="closed before"):
        tk.result(timeout=1)


def test_service_submit_after_close_and_bad_arg(dist_ctx):
    left, right = _tables(dist_ctx, seed=32)
    svc = QueryService()
    with pytest.raises(CylonPlanError, match="LazyTable"):
        svc.submit(left)                 # an eager Table is not a plan
    svc.close()
    with pytest.raises(CylonPlanError, match="closed"):
        svc.submit(_pipe(left, right))


def test_service_concurrent_submitters_hammer(dist_ctx):
    """Dynamic corroboration of the static ``concurrency`` analysis
    family: N barrier-started submitter threads hammer ONE
    QueryService — racing the plan/fingerprint cache (all queries
    share one shape), the DRR queues, the metrics registry and the
    ledger from every thread at once. Results must be bit-identical
    to sequential execution, the per-tenant outcome counters must
    balance exactly (no lost updates), the queues must drain to zero,
    and the ledger must end leak-free."""
    n_threads, per_thread = 4, 3
    tabs = {i: _tables(dist_ctx, seed=40 + i) for i in range(n_threads)}
    direct = {i: _rows(_pipe(*tabs[i]).execute())
              for i in range(n_threads)}
    gc.collect()
    held = ledger.leak_count()
    snap0 = telemetry.metrics_snapshot()
    ok0 = {i: snap0.get(
        f'cylon_queries_total{{outcome="ok",tenant="t{i}"}}', 0)
        for i in range(n_threads)}
    global_cache().clear()
    h0, m0 = _counter("cylon_plan_cache_hits_total"), \
        _counter("cylon_plan_cache_misses_total")
    svc = QueryService(name="hammer")
    barrier = threading.Barrier(n_threads)
    results, errors = {}, []

    def submitter(i):
        try:
            barrier.wait(timeout=60)
            tickets = [svc.submit(_pipe(*tabs[i]), tenant=f"t{i}")
                       for _ in range(per_thread)]
            results[i] = [_rows(t.result(timeout=600))
                          for t in tickets]
        except Exception as e:  # pragma: no cover - failure detail
            errors.append((i, e))

    threads = [threading.Thread(target=submitter, args=(i,))
               for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=600)
    svc.drain(timeout=600)
    svc.close()
    assert not errors, errors
    # bit-identical to sequential execution, per tenant
    for i in range(n_threads):
        assert len(results[i]) == per_thread
        for got in results[i]:
            assert got == direct[i]
    # per-tenant counters balance exactly: concurrent submitters and
    # the worker never lose an increment (the metric-mutation locks)
    snap = telemetry.metrics_snapshot()
    for i in range(n_threads):
        assert snap[
            f'cylon_queries_total{{outcome="ok",tenant="t{i}"}}'] \
            == ok0[i] + per_thread
        assert snap[f'cylon_service_queue_depth{{tenant="t{i}"}}'] == 0
    # the shared plan cache absorbed the one query shape under the
    # race: every optimize was a hit or a miss (no lost counts), with
    # at most one miss per racing submitter before the entry lands
    total = n_threads * per_thread
    dh = _counter("cylon_plan_cache_hits_total") - h0
    dm = _counter("cylon_plan_cache_misses_total") - m0
    assert dh + dm == total
    assert 1 <= dm <= n_threads
    # zero ledger leaks once the results are dropped
    del results
    gc.collect()
    assert ledger.leak_count() == held


def test_service_no_ledger_leaks(dist_ctx):
    left, right = _tables(dist_ctx, seed=33)
    gc.collect()
    held = ledger.leak_count()
    svc = QueryService(start=False)
    tickets = [svc.submit(_pipe(left, right), tenant="leakcheck")
               for _ in range(3)]
    svc.drain(timeout=600)
    for tk in tickets:
        tk.result(timeout=60)
    svc.close()
    del tickets, tk, svc
    gc.collect()
    assert ledger.leak_count() == held
