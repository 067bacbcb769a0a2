"""A served query is timed to the device's last buffer (PR 51).

The ticket of an ``ok`` query is set only after ``jax.block_until_ready``
on every buffer of its result has returned, on the service's completion
thread, while the worker goes on to the next query; the query's life is
ONE span tree (``service.query`` over ``service.submit``,
``.queue_wait``, ``.dispatch`` with ``plan.query`` inside, ``.drain``)
with stamps on one clock; the query log writes one line a served query,
at completion, with the four stage times and their sum; the SLO tracker
observes that sum; ``cylon_service_stage_seconds_total{stage=}`` grows
before the ticket is set.

The device's tail is held by patching ``jax.block_until_ready`` for the
completion thread alone (as tests/test_observatory.py patches it for the
executor): every other caller, the executor's analyze mode and these
tests included, goes straight through.
"""
import gc
import json
import threading
import time
import weakref

import numpy as np
import pytest

import cylon_tpu as ct
from cylon_tpu import plan, telemetry
from cylon_tpu.resilience import inject
from cylon_tpu.service import scheduler
from cylon_tpu.service.plancache import global_cache
from cylon_tpu.service.scheduler import STAGES, QueryService
from cylon_tpu.status import (CylonPlanError, CylonResourceExhausted,
                              CylonTimeoutError)
from cylon_tpu.telemetry import querylog, slo, spans

WAIT_S = 120     # every wait of a test is bounded
SERVED = ("service.query", "service.submit", "service.queue_wait",
          "service.dispatch", "service.drain")


@pytest.fixture(autouse=True)
def _clean():
    querylog.reset()
    slo.reset()
    yield
    inject.disarm()
    global_cache().clear()


def _tables(ctx, n=512, seed=0):
    rng = np.random.default_rng(seed)
    left = ct.Table.from_pydict(ctx, {
        "k": rng.integers(0, n // 4, n).astype(np.int32),
        "v": rng.normal(size=n).astype(np.float32)})
    right = ct.Table.from_pydict(ctx, {
        "k": rng.integers(0, n // 4, n).astype(np.int32),
        "w": rng.normal(size=n).astype(np.float32)})
    return left, right


def _join(left, right):
    return plan.scan(left).join(plan.scan(right), on="k")


def _dist_pipe(left, right):
    return _join(left, right).groupby("lt-1", ["rt-3"], ["sum"])


class _HeldDrain:
    """``jax.block_until_ready`` held for the completion thread until
    ``release()``; ``entered`` counts the drains that have started."""

    def __init__(self, monkeypatch):
        self._open = threading.Event()
        self.entered = threading.Semaphore(0)
        real = scheduler.jax.block_until_ready

        def held(x):
            if threading.current_thread().name.endswith("-completions"):
                self.entered.release()
                assert self._open.wait(WAIT_S), "the drain was never released"
            return real(x)

        monkeypatch.setattr(scheduler.jax, "block_until_ready", held)

    def release(self):
        self._open.set()


@pytest.fixture
def held(monkeypatch):
    gate = _HeldDrain(monkeypatch)
    yield gate
    gate.release()      # whatever a failing test left waiting


class _Trees:
    """Every closed span by name, and the served roots, off a sink."""

    def __init__(self):
        self.closed = []

    def __enter__(self):
        telemetry.add_sink(self.closed.append)
        return self

    def __exit__(self, *exc):
        telemetry.remove_sink(self.closed.append)

    def roots(self):
        return [s for s in self.closed if s.name == "service.query"]

    def names(self):
        return [s.name for s in self.closed]


def _stage_seconds():
    snap = telemetry.metrics_snapshot()
    return {name: snap.get(
        'cylon_service_stage_seconds_total{stage="%s"}' % name, 0.0)
        for name in STAGES}


# ---------------------------------------------------------------------------
# A. the ticket tells the truth, and the worker is not held
# ---------------------------------------------------------------------------


def test_ticket_is_done_only_after_the_result_is_ready(local_ctx, held):
    left, right = _tables(local_ctx, seed=1)
    expected = _join(left, right).execute().row_count
    with QueryService(name="t-ready") as svc:
        tk = svc.submit(_join(left, right))
        assert held.entered.acquire(timeout=WAIT_S)   # dispatch has returned
        time.sleep(0.05)
        assert not tk.done() and tk.outcome is None
        with pytest.raises(CylonTimeoutError):
            tk.result(timeout=0.05)
        held.release()
        assert tk.result(timeout=WAIT_S).row_count == expected
        assert tk.done() and tk.outcome == "ok"


def test_worker_dispatches_the_next_query_while_a_drain_is_held(
        local_ctx, held):
    left, right = _tables(local_ctx, seed=2)
    with QueryService(name="t-overlap") as svc:
        first = svc.submit(_join(left, right))
        assert held.entered.acquire(timeout=WAIT_S)
        second = svc.submit(_join(left, right))
        # the second query is picked and its dispatch returns while the
        # first is still not complete: it becomes a pending completion
        deadline = time.monotonic() + WAIT_S
        while second.dispatch_seq is None or svc.health()["active"]:
            assert time.monotonic() < deadline, "query 2 never dispatched"
            time.sleep(0.005)
        assert second.dispatch_seq == first.dispatch_seq + 1
        assert not first.done() and not second.done()
        h = svc.health()
        assert h["in_flight"] == 2 and h["queue_depth"] == 0
        held.release()
        first.result(timeout=WAIT_S)
        second.result(timeout=WAIT_S)
        assert svc.health()["in_flight"] == 0


def test_completions_arrive_in_dispatch_order(local_ctx, held):
    left, right = _tables(local_ctx, seed=3)
    order = []
    real_finish = scheduler.QueryTicket._finish

    def finish(self, *a, **k):
        order.append(self.dispatch_seq)
        return real_finish(self, *a, **k)

    with QueryService(name="t-order") as svc:
        tickets = [svc.submit(_join(left, right), tenant=f"t{i % 2}")
                   for i in range(5)]
        scheduler.QueryTicket._finish = finish
        try:
            assert held.entered.acquire(timeout=WAIT_S)
            held.release()
            svc.drain(timeout=WAIT_S)
        finally:
            scheduler.QueryTicket._finish = real_finish
    assert order == sorted(order) and len(order) == 5
    assert sorted(t.dispatch_seq for t in tickets) == order


def test_drain_waits_for_a_held_completion(local_ctx, held):
    left, right = _tables(local_ctx, seed=4)
    with QueryService(name="t-drain") as svc:
        tk = svc.submit(_join(left, right))
        assert held.entered.acquire(timeout=WAIT_S)
        with pytest.raises(CylonTimeoutError, match="1 in flight"):
            svc.drain(timeout=0.1)
        assert not tk.done()
        held.release()
        svc.drain(timeout=WAIT_S)
        assert tk.done()


def test_close_waits_for_a_held_drain_and_joins_the_completion_thread(
        local_ctx, held):
    left, right = _tables(local_ctx, seed=5)
    svc = QueryService(name="t-close")
    tk = svc.submit(_join(left, right))
    assert held.entered.acquire(timeout=WAIT_S)
    closed = threading.Event()
    closer = threading.Thread(target=lambda: (svc.close(), closed.set()))
    closer.start()
    assert not closed.wait(0.2) and not tk.done()
    held.release()
    closer.join(WAIT_S)
    assert closed.is_set() and tk.outcome == "ok"
    assert not svc._worker.is_alive() and not svc._completer.is_alive()
    assert svc.health()["in_flight"] == 0


def test_close_of_a_paused_service_finishes_its_orphans_typed(local_ctx):
    left, right = _tables(local_ctx, seed=6)
    svc = QueryService(name="t-paused", start=False)
    with _Trees() as trees:
        tk = svc.submit(_join(left, right), tenant="orphan")
        svc.close()
    assert tk.done() and tk.outcome == "error"
    with pytest.raises(CylonPlanError, match="closed before query"):
        tk.result(timeout=1)
    (root,) = trees.roots()
    assert root.error and root.attrs["outcome"] == "error"
    assert root.attrs["dispatch_ms"] == 0 and root.attrs["drain_ms"] == 0
    assert [c.name for c in root.children] == ["service.submit"]
    (d,) = [d for d in querylog.recent() if d["tenant"] == "orphan"]
    assert d["outcome"] == "error" and d["exec_ms"] is None


def test_an_idle_service_keeps_no_finished_result_alive(local_ctx):
    """Neither thread holds the last query's result (or its plan, hence
    its input tables) while it sleeps: what the client drops is freed."""
    left, right = _tables(local_ctx, seed=15)
    with QueryService(name="t-idle") as svc:
        tk = svc.submit(_join(left, right))
        result = weakref.ref(tk.result(timeout=WAIT_S))
        inputs = weakref.ref(left)
        svc.drain(timeout=WAIT_S)     # the completion has been popped
        del tk, left
        deadline = time.monotonic() + 10
        while (result() is not None or inputs() is not None) \
                and time.monotonic() < deadline:
            gc.collect()
            time.sleep(0.01)
        assert result() is None, "a service thread still holds the result"
        assert inputs() is None, "a service thread still holds the plan"


def test_a_fault_that_surfaces_at_the_drain_fails_the_ticket(local_ctx,
                                                             monkeypatch):
    left, right = _tables(local_ctx, seed=7)
    real = scheduler.jax.block_until_ready

    def faulty(x):
        if threading.current_thread().name.endswith("-completions"):
            raise RuntimeError("device fault at the sync")
        return real(x)

    monkeypatch.setattr(scheduler.jax, "block_until_ready", faulty)
    with _Trees() as trees, QueryService(name="t-fault") as svc:
        tk = svc.submit(_join(left, right))
        with pytest.raises(RuntimeError, match="device fault"):
            tk.result(timeout=WAIT_S)
    assert tk.outcome == "error"
    (root,) = trees.roots()
    assert root.error and root.attrs["outcome"] == "error"
    assert [c.name for c in root.children][-1] == "service.drain"
    assert root.children[-1].error


# ---------------------------------------------------------------------------
# B. one request, one tree, stamps on one clock
# ---------------------------------------------------------------------------


def test_a_served_query_is_five_spans_on_one_tree(local_ctx):
    left, right = _tables(local_ctx, seed=8)
    with _Trees() as trees, QueryService(name="t-tree") as svc:
        tk = svc.submit(_join(left, right), tenant="acme")
        tk.result(timeout=WAIT_S)
    (root,) = trees.roots()
    a = root.attrs
    assert (a["query_id"], a["tenant"], a["service"]) == \
        (tk.query_id, "acme", "t-tree")
    assert a["outcome"] == "ok" and a["admission"] == "admit"
    assert a["dispatch_seq"] == tk.dispatch_seq == 1
    for key in ("est_bytes", "est_source", "plan_fp", "plan_cache"):
        assert key in a, key
    kids = root.children
    assert tuple(c.name for c in kids) == SERVED[1:]
    submit, waited, dispatch, drain = kids
    # every span of the tree knows its root, hence the query
    for s in root.walk():
        assert s.root_id == root.span_id
        assert s.end_s is not None and s.start_s <= s.end_s
        assert s.elapsed_ms == pytest.approx(
            (s.end_s - s.start_s) * 1e3, abs=1e-6)
    # the executor's root is the dispatch's child in served mode
    assert [c.name for c in dispatch.children
            if c.name == "plan.query"] == ["plan.query"]
    assert "cost" in submit.attrs and waited.attrs["depth_at_enqueue"] == 0
    assert dispatch.attrs["admission"] == "admit"
    assert drain.attrs["buffers"] == len(tk.result().buffers())
    # the four tile the root: no overlap, no gap, on one clock
    assert root.start_s <= submit.start_s
    assert submit.end_s == waited.start_s
    assert waited.end_s <= dispatch.start_s
    assert dispatch.end_s == drain.start_s
    assert drain.end_s == root.end_s
    # ... and their _ms attributes add up to total_ms
    stages = [a[f"{name}_ms"] for name in STAGES]
    assert all(ms >= 0 for ms in stages)
    assert sum(stages) == pytest.approx(a["total_ms"], abs=0.01)
    assert a["total_ms"] == pytest.approx(root.elapsed_ms, abs=0.01)
    assert a["queue_wait_ms"] == pytest.approx(tk.wait_s * 1e3, abs=0.01)


def test_stage_counters_grow_before_result_returns(local_ctx):
    left, right = _tables(local_ctx, seed=9)
    with _Trees() as trees, QueryService(name="t-counters") as svc:
        before = _stage_seconds()
        n0 = telemetry.metrics_snapshot().get(
            'cylon_queries_total{outcome="ok",tenant="counted"}', 0)
        svc.submit(_join(left, right), tenant="counted").result(
            timeout=WAIT_S)
        # read at once: the client's own query is already in the registry
        after = _stage_seconds()
        n1 = telemetry.metrics_snapshot()[
            'cylon_queries_total{outcome="ok",tenant="counted"}']
    assert n1 == n0 + 1
    (root,) = trees.roots()
    for name in STAGES:
        assert (after[name] - before[name]) * 1e3 == pytest.approx(
            root.attrs[f"{name}_ms"], abs=0.01), name
    assert after["dispatch"] > before["dispatch"]


def test_a_library_execute_opens_no_service_span(local_ctx):
    left, right = _tables(local_ctx, seed=10)
    with _Trees() as trees:
        _join(left, right).execute()
    assert not [n for n in trees.names() if n.startswith("service.")]
    roots = [s for s in trees.closed if not s.parent_id]
    assert [s.name for s in roots] == ["plan.query"]


# ---------------------------------------------------------------------------
# the query log and the SLO tracker read the whole of it
# ---------------------------------------------------------------------------


def test_a_served_digest_is_one_line_at_completion(local_ctx, held,
                                                   tmp_path):
    left, right = _tables(local_ctx, seed=11)
    qlog = str(tmp_path / "q.jsonl")
    querylog.enable(qlog)
    try:
        with _Trees() as trees, QueryService(name="t-log") as svc:
            tk = svc.submit(_join(left, right), tenant="logged")
            assert held.entered.acquire(timeout=WAIT_S)
            # dispatched, the executor's plan.query closed: no line yet
            assert "plan.query" in trees.names()
            assert querylog.lines_written() == 0
            assert not querylog.recent()
            held.release()
            tk.result(timeout=WAIT_S)
            assert querylog.lines_written() == 1    # before result() returned
    finally:
        querylog.disable()
    (line,) = [json.loads(ln) for ln in open(qlog)]
    (root,) = trees.roots()
    plan_query = next(s for s in root.walk() if s.name == "plan.query")
    assert line["root"] == "service.query" and line["outcome"] == "ok"
    assert line["query_id"] == tk.query_id and line["tenant"] == "logged"
    for name in querylog.SERVED_FIELDS:
        assert line[name] == root.attrs[name], name
    # exec_ms keeps its meaning: the host's time in the executor
    assert line["exec_ms"] == pytest.approx(plan_query.elapsed_ms, abs=0.001)
    assert line["exec_ms"] <= line["dispatch_ms"] + 0.001
    assert line["wait_s"] == pytest.approx(tk.wait_s, abs=1e-6)
    assert line["total_ms"] > line["exec_ms"]


def test_a_library_digest_has_the_served_fields_null(local_ctx):
    left, right = _tables(local_ctx, seed=12)
    _join(left, right).execute()
    (d,) = querylog.recent()
    assert d["root"] == "plan.query" and d["outcome"] == "ok"
    assert d["exec_ms"] > 0 and d["wait_s"] is None
    assert [d[name] for name in querylog.SERVED_FIELDS] == [None] * 5


def test_slo_observes_total_ms_served_and_exec_ms_library(local_ctx,
                                                          monkeypatch):
    left, right = _tables(local_ctx, seed=13)
    seen = []
    real = slo.observe
    monkeypatch.setattr(querylog._slo, "observe",
                        lambda tenant, ms, error=False:
                        (seen.append((tenant, ms, error)),
                         real(tenant, ms, error=error))[1])
    _join(left, right).execute()
    with QueryService(name="t-slo") as svc:
        svc.submit(_join(left, right), tenant="slo-t").result(
            timeout=WAIT_S)
    lib, served = querylog.recent()
    assert seen == [("default", lib["exec_ms"], False),
                    ("slo-t", served["total_ms"], False)]
    assert served["total_ms"] > served["exec_ms"]
    assert slo.state()["slo-t"]["count"] == 1


def _shed(svc, ctx):
    big_l, big_r = _tables(ctx, n=1 << 16, seed=27)
    inject.arm("pool:262144:oom")
    return svc.submit(_join(big_l, big_r), tenant="fated"), \
        CylonResourceExhausted


def _error(svc, ctx):
    left, right = _tables(ctx, seed=28)
    inject.arm("exchange:1+:transient")
    return svc.submit(_dist_pipe(left, right), tenant="fated"), \
        ct.CylonTransientError


def _timeout(svc, ctx):
    left, right = _tables(ctx, seed=29)
    return svc.submit(_dist_pipe(left, right), tenant="fated",
                      deadline_s=1e-6), CylonTimeoutError


@pytest.mark.parametrize("outcome,cause", [
    ("shed", _shed), ("error", _error), ("timeout", _timeout)])
def test_a_query_without_a_result_has_no_drain_and_one_digest(
        dist_ctx, outcome, cause):
    with _Trees() as trees:
        svc = QueryService(name="t-fate", start=False)
        tk, raised = cause(svc, dist_ctx)
        svc.drain(timeout=600)
        svc.close()
    assert tk.outcome == outcome
    with pytest.raises(raised):
        tk.result(timeout=1)
    (root,) = trees.roots()
    assert root.error and root.attrs["outcome"] == outcome
    assert root.attrs["drain_ms"] == 0
    assert [c.name for c in root.children] == list(SERVED[1:4])
    assert root.children[-1].error           # the dispatch it failed in
    assert root.end_s == root.children[-1].end_s
    stages = [root.attrs[f"{name}_ms"] for name in STAGES]
    assert sum(stages) == pytest.approx(root.attrs["total_ms"], abs=0.01)
    (d,) = [d for d in querylog.recent() if d["tenant"] == "fated"]
    assert d["outcome"] == outcome and d["drain_ms"] == 0
    assert d["total_ms"] == root.attrs["total_ms"]
    # a shed query never reached the executor
    assert (d["exec_ms"] is None) == (outcome == "shed")


def test_a_rejected_submission_closes_its_root_at_once(local_ctx,
                                                       monkeypatch):
    left, right = _tables(local_ctx, seed=14)
    monkeypatch.setenv("CYLON_SERVICE_QUEUE_MAX", "1")
    svc = QueryService(name="t-full", start=False)
    with _Trees() as trees:
        kept = svc.submit(_join(left, right), tenant="full")
        with pytest.raises(CylonResourceExhausted, match="queue full"):
            svc.submit(_join(left, right), tenant="full")
        (root,) = trees.roots()           # the kept one is still queued
        assert root.error and root.attrs["outcome"] == "shed"
        assert root.attrs["total_ms"] == root.attrs["submit_ms"] > 0
        assert [c.name for c in root.children] == ["service.submit"]
        assert root.children[0].error
        (d,) = querylog.recent()
        assert d["outcome"] == "shed" and d["queue_wait_ms"] == 0
        svc.drain(timeout=WAIT_S)
        svc.close()
    assert kept.outcome == "ok" and len(trees.roots()) == 2


# ---------------------------------------------------------------------------
# the span primitive: stamps, one close, a tree across threads
# ---------------------------------------------------------------------------


def test_every_span_gives_its_stamps():
    t0 = time.perf_counter()
    with telemetry.span("stamped", rows=3) as s:
        assert s.end_s is None and s.to_dict()["end_s"] is None
    t1 = time.perf_counter()
    d = s.to_dict()
    assert t0 <= d["start_s"] <= d["end_s"] <= t1
    assert d["elapsed_ms"] == pytest.approx(
        (d["end_s"] - d["start_s"]) * 1e3)


def test_a_body_may_stamp_an_edge_and_close_keeps_it():
    with telemetry.span("stamped.early") as s:
        s.end_s = s.start_s + 0.25
    assert s.end_s == s.start_s + 0.25
    assert s.elapsed_ms == pytest.approx(250.0)


def test_a_tree_crosses_threads_and_closes_in_one_place():
    closed, hooked = [], []
    telemetry.add_sink(closed.append)
    telemetry.add_root_hook(hooked.append)
    try:
        root = telemetry.open_span("cross.root", query_id=7)
        assert telemetry.current_span() is None      # not current here
        seen = {}

        def other():
            seen["before"] = telemetry.current_span()
            with telemetry.attach(root):
                with telemetry.span("cross.child") as c:
                    seen["child"] = c
            seen["after"] = telemetry.current_span()
            telemetry.close_span(root)               # closed where it ends

        t = threading.Thread(target=other)
        t.start()
        t.join(WAIT_S)
        assert not t.is_alive()
    finally:
        telemetry.remove_sink(closed.append)
        telemetry.remove_root_hook(hooked.append)
    assert seen["before"] is None and seen["after"] is None
    child = seen["child"]
    assert child.parent_id == root.span_id and child.root_id == root.span_id
    assert root.children == [child]
    assert [s.name for s in closed] == ["cross.child", "cross.root"]
    assert hooked == [root]                          # the root's hooks ran
    assert root.elapsed_ms is not None and root.end_s >= child.end_s
    snap = telemetry.metrics_snapshot()
    assert snap['cylon_phase_latency_ms{phase="cross.root"}']["count"] >= 1


def test_span_closes_through_close_span_alone(monkeypatch):
    """``span()``'s own exit and a cross-thread close are ONE function:
    what it records, every span records."""
    calls = []
    real = spans.close_span
    monkeypatch.setattr(spans, "close_span",
                        lambda s: (calls.append(s.name), real(s))[1])
    with telemetry.span("one.place"):
        pass
    with pytest.raises(ValueError):
        with telemetry.span("one.place.raising") as s:
            raise ValueError("x")
    assert calls == ["one.place", "one.place.raising"]
    assert s.error and s.attrs["error"] is True and s.elapsed_ms is not None
