"""Concurrent query scheduler: multi-tenant fair-share queueing over
LazyTable queries.

Everything below this module runs ONE blocking ``collect()`` at a
time; this is the tier that turns the library into a service (ROADMAP
item 2, the "millions of users" tier). Submitted queries enter
per-tenant FIFO queues; a **deficit-round-robin** sweep over tenants
picks the next query (cost = the planner's pre-flight byte estimate,
so one tenant's huge joins cannot starve another's cheap lookups);
a single executor worker thread drains the pick.

Pipelining discipline: **device execution stays serialized** — JAX
dispatch through one mesh is not concurrency-safe, and interleaving
two queries' collectives would deadlock the virtual mesh — but the
expensive HOST work pipelines around it: ``submit()`` runs
optimization (through the plan/fingerprint cache, service/plancache)
and the pre-flight estimates on the CALLER's thread, concurrently with
whatever the worker is executing. Admission is decided by the worker
at DISPATCH time, so it sees the ledger-tracked live HBM of the
queries that actually ran before it (the pool's ``comm_budget_bytes``
nets out ``ledger.live_bytes()`` — held results shrink the budget the
next query is admitted against), not a static snapshot from submit
time.

Backpressure before queueing: once the total queue depth reaches
``CYLON_SERVICE_QUEUE_MAX`` (default 256), ``submit()`` raises a typed
:class:`CylonResourceExhausted` BEFORE enqueue and records the
rejection — with its tenant — in the flight recorder's admission ring,
so a load-shedding service leaves the same forensic trail as an
admission-controller shed.

Every query's fate is observable, and timed to the device's last
buffer (``QueryTicket``): a served query is ONE span tree —
``service.query`` opened in ``submit()`` and closed where the query
completes, over ``service.submit`` (caller's thread),
``service.queue_wait`` (two stamps across threads),
``service.dispatch`` (worker's; the executor's ``plan.query`` is its
child) and ``service.drain`` (completion thread's: the wait for the
result's buffers) — whose four stages add up to the root's
``total_ms``:

* ``cylon_service_queue_depth{tenant=}``   live queue depth gauges
* ``cylon_service_wait_seconds``           enqueue→dispatch histogram
* ``cylon_service_stage_seconds_total{stage=}`` seconds a stage, added
  once a finished query, before its ticket is set
* ``cylon_queries_total{tenant=,outcome=}`` ok / shed / error / timeout
* the tenant (+ query id + service name) rides the query's root span,
  so the EXPLAIN ANALYZE tree under it, the flight-ring entry, the
  query-log digest (written at completion) and crash dumps all say
  whose query it was;
* admission decisions are recorded with the tenant label
  (``resilience.admission.record(decision, tenant=)``).

Env knobs: ``CYLON_SERVICE_QUEUE_MAX`` (queue bound),
``CYLON_SERVICE_QUANTUM_BYTES`` (DRR quantum, default 1 MiB). See
docs/service.md for the full catalog and semantics.
"""
from __future__ import annotations

import itertools
import threading
import time
from collections import OrderedDict, deque
from typing import Deque, Dict, Optional

import jax

from ..plan import ir
from ..plan.executor import (execute as _execute,
                             execute_analyzed as _execute_analyzed)
from ..plan.report import calibrate_estimates, preflight_estimates
from ..resilience import admission as _admission
from ..resilience import retry as _retry
from ..status import (Code, CylonPlanError, CylonResourceExhausted,
                      CylonTimeoutError)
from ..telemetry import flight as _flight
from ..telemetry import knobs as _knobs
from ..telemetry import logger as _logger
from ..telemetry import metrics as _metrics
from ..telemetry import stats as _stats
from ..telemetry import attach as _attach
from ..telemetry import close_span as _close_span
from ..telemetry import open_span as _open_span
from ..telemetry import span as _span
from . import plancache as _plancache

# submit→dispatch wait histogram bounds, in SECONDS (the default
# bucket set is ms-scaled for span latencies; queue waits span
# sub-millisecond drains to multi-second backlogs)
WAIT_BUCKETS_S = (0.0005, 0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1.0,
                  5.0, 30.0, 120.0)

# the four stages a served query's life is cut into, in order: between
# five stamps on one clock (submit() called, job enqueued, picked by the
# worker, dispatch returned, result ready), so they add up to the root
# span's ``total_ms``
STAGES = ("submit", "queue_wait", "dispatch", "drain")

_query_ids = itertools.count(1)


def queue_max() -> int:
    return _knobs.get("CYLON_SERVICE_QUEUE_MAX")


def quantum_bytes() -> int:
    return _knobs.get("CYLON_SERVICE_QUANTUM_BYTES")


class QueryTicket:
    """Future-style handle for one submitted query.

    ``result()`` blocks until the query is COMPLETE and either returns
    its Table or re-raises the query's TYPED error (a shed raises
    :class:`CylonResourceExhausted`, a deadline expiry
    :class:`CylonTimeoutError` — the same taxonomy a direct
    ``collect()`` surfaces). A query is complete when the DEVICE has
    finished: an ``ok`` ticket is set only after
    ``jax.block_until_ready`` on every buffer of its result has
    returned, on the service's completion thread (JAX dispatch is
    asynchronous: the worker's executor returns when the last program
    is queued, and goes on to the next query meanwhile). ``outcome``
    is one of ``ok | shed | error | timeout`` once done; ``wait_s``
    the measured enqueue→dispatch queue wait; ``dispatch_seq`` the
    service-wide dispatch order (the scheduler-fairness observable the
    DRR tests pin)."""

    def __init__(self, query_id: int, tenant: str):
        self.query_id = query_id
        self.tenant = tenant
        self.outcome: Optional[str] = None
        self.wait_s: Optional[float] = None
        self.dispatch_seq: Optional[int] = None
        self._done = threading.Event()
        self._result = None
        self._error: Optional[BaseException] = None
        self._report = None

    def done(self) -> bool:
        return self._done.is_set()

    def result(self, timeout: Optional[float] = None):
        if not self._done.wait(timeout):
            raise CylonTimeoutError(
                f"query {self.query_id} (tenant {self.tenant!r}) not "
                f"finished within {timeout} s")
        if self._error is not None:
            raise self._error
        return self._result

    def report(self, timeout: Optional[float] = None):
        """The EXPLAIN ANALYZE ``PlanReport`` (``analyze=True``
        submissions only; None otherwise). Blocks like ``result`` but
        never raises the query error — forensics stay readable for
        failed queries too."""
        self._done.wait(timeout)
        return self._report

    def _finish(self, outcome: str, result=None, error=None,
                report=None) -> None:
        self.outcome = outcome
        self._result = result
        self._error = error
        self._report = report
        self._done.set()

    def __repr__(self):
        state = self.outcome or ("queued" if not self._done.is_set()
                                 else "done")
        return (f"QueryTicket(id={self.query_id}, "
                f"tenant={self.tenant!r}, {state})")


class _Job:
    __slots__ = ("ticket", "tenant", "root", "stats", "est", "cost",
                 "ctx", "analyze", "deadline_s", "span", "submitted", "depth_at_enqueue", "t_enqueued",
                 "t_picked", "t_dispatched")

    def __init__(self, ticket, tenant, root, stats, est, cost, ctx,
                 analyze, deadline_s, span):
        self.ticket = ticket
        self.tenant = tenant
        self.root = root
        self.stats = stats
        self.est = est
        self.cost = cost
        self.ctx = ctx
        self.analyze = analyze
        self.deadline_s = deadline_s
        # the query's ``service.query`` root span, opened in submit()
        # (it carries the plan-cache fate of the submit thread's
        # optimize(), ``plan_fp`` / ``plan_cache``, for the query log)
        # and closed where the query completes; ``submitted`` is set
        # once the caller's thread has closed ``service.submit`` under
        # it, so that the root never closes over an open child
        self.span = span
        self.submitted = threading.Event()
        # the stamps between the stages (``time.perf_counter``, the
        # spans' clock): enqueued under the lock, picked by the worker,
        # dispatch returned
        self.depth_at_enqueue = 0
        self.t_enqueued = self.t_picked = self.t_dispatched = 0.0


def _job_cost(est: dict, root: ir.PlanNode) -> int:
    """A query's DRR cost: the sum of its ALLOCATING node estimates
    (Scans excluded — borrowed inputs are history, not work), floored
    at 1 so estimate-free plans still round-robin."""
    total = 0
    for n in ir.walk(root):
        if n.kind == "scan":
            continue
        b = est.get(id(n), {}).get("bytes")
        if b:
            total += int(b)
    return max(total, 1)


class QueryService:
    """The concurrent query service: submit many LazyTable queries,
    get :class:`QueryTicket` futures back; one worker thread drains
    the per-tenant queues under deficit round-robin, and one
    completion thread waits for each dispatched query's result on the
    device, records the query and sets its ticket, in dispatch order.

    ``start=False`` builds the service paused (submissions queue but
    nothing executes) — the chaos drill uses it to make dispatch order
    a pure function of the submission sequence. ``close()`` drains the
    remaining queue and joins the worker; the service is also a
    context manager (``with QueryService() as svc: ...``)."""

    def __init__(self, name: str = "cylon", start: bool = True):
        self.name = name
        self._cv = threading.Condition()
        self._queues: "OrderedDict[str, Deque[_Job]]" = OrderedDict()
        self._deficit: Dict[str, float] = {}
        self._last_served: Optional[str] = None
        self._depth = 0
        self._dispatched = 0
        self._active: Optional[_Job] = None
        # dispatched, result not yet ready: (job, result, report), in
        # dispatch order; the head is the one the completion thread
        # waits for (it stays here until its ticket is set, so drain()
        # and health() count it). None is the worker's "no more" mark.
        self._completions: Deque[Optional[tuple]] = deque()
        self._closed = False
        self._worker: Optional[threading.Thread] = None
        self._completer: Optional[threading.Thread] = None
        self._obs = None               # obs_http.ObsServer when armed
        if start:
            self.start()

    # -- lifecycle ------------------------------------------------------

    def start(self) -> None:
        """Start the executor worker and the completion thread
        (idempotent) — and, when
        ``CYLON_OBS_PORT`` is nonzero, the observability HTTP endpoint
        (``service/obs_http.py``) serving this service's /metrics,
        /healthz, /queries, /slo and /stats on a daemon thread. When
        ``CYLON_STATS_PATH`` names a saved statistics snapshot, the
        warehouse warm-starts from it BEFORE the first dispatch, so a
        fresh replica's repeat-shape queries get measured-calibrated
        admission from query 1 (a corrupt snapshot is quarantined —
        never blocks startup)."""
        with self._cv:
            if self._worker is not None or self._closed:
                return
        # warm-start outside the lock (file IO must not block
        # submitters); the worker is not running yet, so no dispatch
        # precedes the load — and load() merges via setdefault, so a
        # racing second start() loading again is harmless
        _stats.load()
        obs = None
        with self._cv:
            if self._worker is not None or self._closed:
                return
            self._worker = threading.Thread(
                target=self._run, name=f"cylon-service-{self.name}",
                daemon=True)
            self._completer = threading.Thread(
                target=self._run_completions,
                name=f"cylon-service-{self.name}-completions",
                daemon=True)
            self._worker.start()
            self._completer.start()
            port = _knobs.get("CYLON_OBS_PORT")
            if port and self._obs is None:
                from . import obs_http as _obs_http

                obs = self._obs = _obs_http.ObsServer(service=self,
                                                      port=port)
        if obs is not None:
            # bind+serve OUTSIDE the lock: a bad port must not wedge
            # the scheduler, and the obs thread scrapes health() which
            # takes this same lock
            try:
                obs.start()
            except OSError:
                _logger.exception(
                    "service %s: observability endpoint failed to "
                    "bind port %s — continuing without it",
                    self.name, obs.requested_port)
                with self._cv:
                    self._obs = None
                return
            # a close() may have raced this start() and discarded the
            # handle before the bind — it had nothing to stop then, so
            # stop the now-live endpoint here or it outlives close()
            with self._cv:
                leaked = obs if self._closed or self._obs is not obs \
                    else None
            if leaked is not None:
                leaked.close()

    def close(self, timeout: Optional[float] = None) -> None:
        """Drain the remaining queue, wait for every dispatched query
        to complete, stop the worker and the completion thread, reject
        further submissions. Closing a PAUSED service (built with
        ``start=False``, never started) has no worker to drain the
        queue — its still-queued tickets finish typed
        (:class:`CylonPlanError`, outcome ``error``) instead of
        hanging their waiters forever."""
        orphans = []
        with self._cv:
            already_closed = self._closed
            self._closed = True
            worker, completer = self._worker, self._completer
            obs, self._obs = self._obs, None
            if worker is None:
                for t, q in self._queues.items():
                    orphans.extend(q)
                    q.clear()
                    self._depth_gauge(t).set(0)
                self._depth = 0
            self._cv.notify_all()
        for job in orphans:
            # never picked: the wait ends here, nothing was dispatched
            job.t_picked = job.t_dispatched = time.perf_counter()
            self._complete(job, "error", error=CylonPlanError(
                f"service {self.name!r} closed before query "
                f"{job.ticket.query_id} (tenant {job.tenant!r}) was "
                f"dispatched", code=Code.Invalid))
        if worker is not None:
            # the worker leaves its "no more" mark behind the last
            # dispatched query, so the completion thread ends after it
            worker.join(timeout)
            completer.join(timeout)
        if obs is not None:
            # after the worker: the endpoint stays scrapeable while
            # the drain finishes, then shuts down with its thread
            # joined (no leaked obs thread past close())
            obs.close(timeout)
        # snapshot the statistics warehouse AFTER the drain: every
        # query this service ran has fed its digest by now, so the
        # file the next replica warm-starts from carries the full run
        # (no-op unless CYLON_STATS_PATH is set; never raises). Only
        # a STARTED service saves — start() is what merged the
        # existing snapshot into the store, so a never-started (or
        # re-)close() must not rotate a learned warm-start file aside
        # and replace it with a near-empty one
        if worker is not None and not already_closed:
            _stats.save()

    def __enter__(self) -> "QueryService":
        self.start()
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # -- submission -----------------------------------------------------

    def submit(self, query, tenant: str = "default",
               analyze: bool = False,
               deadline_s: Optional[float] = None) -> QueryTicket:
        """Queue one LazyTable query for the ``tenant``; returns its
        ticket immediately.

        The host-side heavy lifting happens HERE, on the caller's
        thread — optimization through the plan/fingerprint cache and
        the pre-flight byte estimates — pipelined against whatever the
        worker is executing. Raises :class:`CylonResourceExhausted`
        (typed backpressure) when the service queue is full, BEFORE
        the query is queued or any device work happens.

        The query's ``service.query`` root span opens here and closes
        where the query completes (the completion thread for an ``ok``
        query); all of this method is its ``service.submit`` child. A
        submission that raises closes the root at once, errored."""
        if not hasattr(query, "optimized"):
            raise CylonPlanError(
                f"submit() takes a LazyTable-style query (got "
                f"{type(query).__name__})")
        with self._cv:
            if self._closed:
                raise CylonPlanError(
                    f"service {self.name!r} is closed",
                    code=Code.Invalid)
        qid = next(_query_ids)
        ticket = QueryTicket(qid, tenant)
        root = _open_span("service.query", query_id=qid, tenant=tenant,
                          service=self.name)
        job = None
        try:
            with _attach(root), _span("service.submit") as sub:
                job = self._prepare(query, ticket, root, analyze,
                                    deadline_s)
                sub.set(cost=job.cost)
                self._enqueue(job, sub)
        except BaseException as e:
            self._reject(root, tenant,
                         "shed" if isinstance(e, CylonResourceExhausted)
                         else "error")
            raise
        finally:
            if job is not None:
                job.submitted.set()
        return ticket

    def _prepare(self, query, ticket, root, analyze, deadline_s) -> _Job:
        """Host-side prepare (no lock, no device work): optimize via
        the fingerprint cache + pre-flight estimates over the result.
        The cache fate (fp, hit/miss) is read back thread-locally —
        this thread's optimize, not a racing submitter's — and rides
        the root span into the query-log digest."""
        _plancache.clear_last_event()
        plan, stats = query.optimized()
        cache_doc = dict(_plancache.last_event() or {})
        if not cache_doc.get("plan_fp"):
            # cache disabled/bypassed: derive the LOGICAL-plan
            # fingerprint directly so the digest and the statistics
            # warehouse still key this query (same key space as the
            # cache — drift eviction must match it)
            fp_fn = getattr(query, "plan_fingerprint", None)
            if fp_fn is not None:
                cache_doc["plan_fp"] = fp_fn()
        root.set(**cache_doc)
        est = preflight_estimates(plan)
        return _Job(ticket, ticket.tenant, plan, stats, est,
                    _job_cost(est, plan),
                    getattr(query, "context", None), analyze,
                    deadline_s, root)

    def _enqueue(self, job: _Job, sub) -> None:
        """Under the lock: refuse a closed or full service, else append
        the job to its tenant's queue. ``service.submit`` ends and the
        queue wait starts at ONE stamp taken here, where the worker
        first can see the job."""
        tenant, qid = job.tenant, job.ticket.query_id
        with self._cv:
            if self._closed:
                raise CylonPlanError(
                    f"service {self.name!r} is closed",
                    code=Code.Invalid)
            cap = queue_max()
            if self._depth >= cap:
                # typed backpressure BEFORE enqueue — and the same
                # forensic trail as an admission shed, tenant included
                _flight.record_admission({
                    "action": "shed", "tenant": tenant,
                    "query_id": qid, "est_bytes": job.cost,
                    "budget": None,
                    "reason": f"service queue full (depth "
                              f"{self._depth} >= "
                              f"CYLON_SERVICE_QUEUE_MAX {cap})"})
                raise CylonResourceExhausted(
                    f"service {self.name!r} queue full: depth "
                    f"{self._depth} >= CYLON_SERVICE_QUEUE_MAX {cap} "
                    f"(tenant {tenant!r}, query {qid})")
            q = self._queues.get(tenant)
            if q is None:
                q = self._queues[tenant] = deque()
                self._deficit.setdefault(tenant, 0.0)
            job.depth_at_enqueue = self._depth
            q.append(job)
            self._depth += 1
            self._depth_gauge(tenant).set(len(q))
            sub.end_s = job.t_enqueued = time.perf_counter()
            self._cv.notify_all()

    def drain(self, timeout: Optional[float] = None) -> None:
        """Block until every queued query has been dispatched AND is
        complete (its result ready on the device, its ticket set);
        raises :class:`CylonTimeoutError` on timeout. Starts the worker
        if the service was built paused."""
        self.start()
        deadline = None if timeout is None else \
            time.monotonic() + timeout
        with self._cv:
            while self._depth > 0 or self._in_flight_locked():
                rem = None if deadline is None else \
                    deadline - time.monotonic()
                if rem is not None and rem <= 0:
                    raise CylonTimeoutError(
                        f"service drain timed out with {self._depth} "
                        f"queued + {self._in_flight_locked()} in "
                        f"flight")
                self._cv.wait(rem)

    def depth(self, tenant: Optional[str] = None) -> int:
        with self._cv:
            if tenant is None:
                return self._depth
            q = self._queues.get(tenant)
            return len(q) if q is not None else 0

    def _in_flight_locked(self) -> int:
        """Queries dispatched and not yet complete (caller holds the
        lock): the one the worker is dispatching and those whose
        result the completion thread still waits for."""
        return (self._active is not None) + sum(
            c is not None for c in self._completions)

    def health(self) -> dict:
        """One lock-consistent liveness snapshot — the observability
        endpoint's ``/healthz`` payload: worker liveness, total and
        per-tenant queue depths, the query being dispatched
        (``active``), how many are dispatched and not yet complete
        (``in_flight``: ``active`` and those awaiting their result on
        the device), dispatch count."""
        with self._cv:
            worker = self._worker
            active = self._active
            doc = {
                "service": self.name,
                "closed": self._closed,
                "worker_alive": worker is not None and
                worker.is_alive(),
                "queue_depth": self._depth,
                "queue_depth_by_tenant": {
                    t: len(q) for t, q in self._queues.items()},
                "dispatched": self._dispatched,
                "active": None if active is None else {
                    "query_id": active.ticket.query_id,
                    "tenant": active.tenant},
                "in_flight": self._in_flight_locked(),
            }
        return doc

    # -- scheduling (deficit round-robin) -------------------------------

    def _depth_gauge(self, tenant: str):
        return _metrics.REGISTRY.gauge("cylon_service_queue_depth",
                                       {"tenant": tenant})

    def _count_outcome(self, tenant: str, outcome: str) -> None:
        _metrics.REGISTRY.counter(
            "cylon_queries_total",
            {"tenant": tenant, "outcome": outcome}).inc()

    def _pick_locked(self) -> Optional[_Job]:
        """One DRR pick (caller holds the lock): sweep active tenants
        cyclically starting after the last-served one; each visit adds
        a quantum to the tenant's deficit; the first tenant whose
        deficit covers its head query's cost is served. Computed in
        closed form (no per-round loop), so a pathological byte
        estimate cannot spin the scheduler. An emptied queue forfeits
        its residual deficit — the classic DRR anti-hoarding rule."""
        active = [t for t, q in self._queues.items() if q]
        if not active:
            return None
        # rotation: continue AFTER the tenant served last
        if self._last_served in active:
            i = active.index(self._last_served) + 1
            active = active[i:] + active[:i]
        q = float(quantum_bytes())
        best = None  # ((rounds, order_idx), tenant)
        for idx, t in enumerate(active):
            need = self._queues[t][0].cost - self._deficit[t]
            rounds = 1 if need <= q else -int(-need // q)  # ceil, >= 1
            key = (rounds, idx)
            if best is None or key < best[0]:
                best = (key, t)
        (r_serve, i_serve), serve = best
        # fast-forward every tenant's deficit by the visits it received
        # before the serving visit in the cyclic sweep
        for idx, t in enumerate(active):
            visits = r_serve if idx <= i_serve else r_serve - 1
            if visits > 0:
                self._deficit[t] += visits * q
        job = self._queues[serve].popleft()
        self._deficit[serve] = max(
            self._deficit[serve] - job.cost, 0.0)
        if not self._queues[serve]:
            self._deficit[serve] = 0.0
        self._last_served = serve
        self._depth -= 1
        self._depth_gauge(serve).set(len(self._queues[serve]))
        return job

    # -- the executor worker --------------------------------------------

    def _run(self) -> None:
        while True:
            with self._cv:
                job = self._pick_locked()
                while job is None:
                    if self._closed:
                        # behind the last dispatched query: the
                        # completion thread ends when it gets here
                        self._completions.append(None)
                        self._cv.notify_all()
                        return
                    self._cv.wait()
                    job = self._pick_locked()
                job.t_picked = time.perf_counter()
                self._active = job
                self._dispatched += 1
                job.ticket.dispatch_seq = self._dispatched
            pending = None
            try:
                pending = self._dispatch(job)
            finally:
                with self._cv:
                    # one step under the lock: the query is in flight
                    # as ``active`` or as a pending completion, never
                    # as neither
                    self._active = None
                    if pending is not None:
                        self._completions.append(pending)
                    self._cv.notify_all()
                # this thread now sleeps until the next pick: it must
                # not keep the last query's plan (its input tables) and
                # result alive meanwhile
                job = pending = None

    def _dispatch(self, job: _Job) -> Optional[tuple]:
        """Admit, then execute, one query. Returns (job, result,
        report) for the completion thread when the executor returned a
        result (the device may still be running its last programs);
        a query that was shed, timed out or failed has nothing to wait
        for and is completed here. Never raises — the worker must
        survive every query."""
        ticket, root = job.ticket, job.span
        wait_s = job.t_picked - job.t_enqueued
        ticket.wait_s = wait_s
        _metrics.REGISTRY.histogram(
            "cylon_service_wait_seconds",
            buckets=WAIT_BUCKETS_S).observe(wait_s)
        # the wait crossed threads, so it is no ``with`` block: recorded
        # from its two stamps (and is no annotation on a profiler trace)
        waited = _open_span("service.queue_wait", parent=root,
                            depth_at_enqueue=job.depth_at_enqueue)
        waited.start_s, waited.end_s = job.t_enqueued, job.t_picked
        _close_span(waited)
        outcome, result, report, error = "error", None, None, None
        try:
            with _attach(root), _span("service.dispatch") as disp:
                # dispatch-time admission: the budget is live-HBM aware
                # (the pool nets out ledger-tracked bytes), so queries
                # admitted now see the memory the PREVIOUS queries'
                # held results still pin
                pool = getattr(job.ctx, "memory_pool", None) \
                    if job.ctx is not None else None
                budget = _admission.effective_budget(pool)
                world = job.ctx.get_world_size() \
                    if job.ctx is not None and \
                    job.ctx.is_distributed() else 1
                # calibrate at DISPATCH time, not submit time: a queued
                # query admitted now sees the statistics the queries
                # ahead of it just taught the warehouse (idempotent —
                # the executor's _preflight skips nodes already
                # calibrated)
                calibrate_estimates(job.root, job.est, world)
                decision = _admission.decide(
                    list(ir.walk(job.root)), job.est, budget, world)
                disp.set(admission=decision.action)
                root.set(wait_s=round(wait_s, 6),
                         dispatch_seq=ticket.dispatch_seq,
                         admission=decision.action,
                         est_bytes=decision.est_bytes,
                         est_source=decision.est_source)
                # the non-admit plan.admission marker span record()
                # emits nests here, under the tenant's root
                _admission.record(decision, tenant=job.tenant)
                _admission.enforce(decision)
                plan_fp = root.attrs.get("plan_fp")
                with _retry.query_deadline(job.deadline_s):
                    if job.analyze:
                        result, report = _execute_analyzed(
                            job.root, job.ctx, stats=job.stats,
                            decision=decision, est=job.est,
                            plan_fp=plan_fp)
                        # the report leaves with the client, apart
                        # from the root: its own span says whose it is
                        report.span.set(tenant=job.tenant,
                                        query_id=ticket.query_id,
                                        service=self.name)
                    else:
                        result = _execute(job.root, job.ctx,
                                          decision=decision,
                                          est=job.est, plan_fp=plan_fp)
            outcome = "ok"
        except Exception as e:
            error = e
            outcome = "timeout" if isinstance(e, CylonTimeoutError) \
                else "shed" if isinstance(e, CylonResourceExhausted) \
                else "error"
            _logger.warning(
                "service %s: query %d (tenant %s) %s: %s: %s",
                self.name, ticket.query_id, job.tenant, outcome,
                type(e).__name__, e)
        job.t_dispatched = disp.end_s
        if outcome == "ok":
            return job, result, report
        self._complete(job, outcome, error=error, report=report)
        return None

    # -- the completion thread ------------------------------------------

    def _run_completions(self) -> None:
        """Take the dispatched queries in dispatch order; wait for each
        one's result on the device, record it, set its ticket. The head
        stays in the deque until then, so drain() and health() count
        it."""
        while True:
            with self._cv:
                while not self._completions:
                    self._cv.wait()
                pending = self._completions[0]
            done = pending is None
            if not done:
                self._drain_result(*pending)
            # dropped before the next wait: a completion thread that
            # kept the last result alive until the next one arrived
            # read +256 MB of peak HBM in ``join-w1-served`` (PR 51)
            pending = None
            with self._cv:
                self._completions.popleft()
                self._cv.notify_all()
            if done:
                return

    def _drain_result(self, job: _Job, result, report) -> None:
        """Wait until the device has finished every buffer of
        ``result``; the query is complete then, not when its last
        program was queued. A fault that surfaces only at this wait
        (errors on the device do) fails the ticket typed."""
        buffers = result.buffers()
        outcome, error = "ok", None
        try:
            with _attach(job.span), \
                    _span("service.drain", buffers=len(buffers)) as d:
                # from where dispatch returned, on the stamps' clock
                # (the annotation on a trace starts where this thread
                # took the query up)
                d.start_s = job.t_dispatched
                # the wait for a FINISHED result: it decides nothing
                # the host dispatches next, so it is no host_fetch
                jax.block_until_ready(buffers)
        except Exception as e:
            outcome, error, result = "error", e, None
            _logger.warning(
                "service %s: query %d (tenant %s) failed at the "
                "drain: %s: %s", self.name, job.ticket.query_id,
                job.tenant, type(e).__name__, e)
        job.span.end_s = d.end_s
        self._complete(job, outcome, result=result, error=error,
                       report=report)

    # -- completion: the one place a query's fate is recorded -----------

    def _complete(self, job: _Job, outcome: str, result=None,
                  error=None, report=None) -> None:
        """Record one finished query and set its ticket, in that order:
        the stage times on the root span, the counters, the root's
        close (query-log digest, SLO, flight ring), then the ticket, so
        that a client back from ``result()`` finds its own query in
        all of them. A query that did not run to a result ends where
        its dispatch did (``drain_ms`` 0)."""
        root = job.span
        job.submitted.wait()     # ``service.submit`` closed under it
        if root.end_s is None:
            root.end_s = job.t_dispatched
        edges = (root.start_s, job.t_enqueued, job.t_picked,
                 job.t_dispatched, root.end_s)
        self._record(root, job.tenant, outcome,
                     [b - a for a, b in zip(edges, edges[1:])])
        job.ticket._finish(outcome, result=result, error=error,
                           report=report)

    def _reject(self, root, tenant: str, outcome: str) -> None:
        """A submission that raised in ``submit()``: it never became a
        job, and all of its life was the submit stage."""
        root.end_s = time.perf_counter()
        self._record(root, tenant, outcome,
                     [root.end_s - root.start_s, 0.0, 0.0, 0.0])

    def _record(self, root, tenant: str, outcome: str, stages) -> None:
        """The stage times (seconds, in ``STAGES``' order) onto the root
        span and into ``cylon_service_stage_seconds_total{stage=}``,
        the outcome into ``cylon_queries_total``, then the root closes:
        its hooks write the digest and feed the SLO tracker."""
        root.set(outcome=outcome, total_ms=round(sum(stages) * 1e3, 3))
        for name, secs in zip(STAGES, stages):
            root.attrs[f"{name}_ms"] = round(secs * 1e3, 3)
            _metrics.REGISTRY.counter(
                "cylon_service_stage_seconds_total",
                {"stage": name}).inc(secs)
        if outcome != "ok":
            root.error = True
            root.attrs["error"] = True
        self._count_outcome(tenant, outcome)
        _close_span(root)
