#!/usr/bin/env python
"""Chaos drill: seeded fault plans swept through a join→groupby pipeline.

Proves the resilience layer END TO END, deterministically (wired into
scripts/check.sh after the telemetry smoke gate):

* ``compile``    — an injected transient fault at the first kernel-
  factory build is retried to success (lru_cache never caches the
  exception, so the retry rebuilds); result bit-matches the clean run.
* ``transient``  — an injected transient exchange fault (arrival index
  varied by seed) is retried to success: ``cylon_retries_total`` > 0,
  ``[RETRY×n]`` in EXPLAIN ANALYZE, result matches the clean run.
* ``persistent`` — a persistent exchange fault exhausts the retry
  budget and surfaces as a TYPED ``CylonTransientError`` (never a raw
  traceback) plus a parseable crash dump whose ``faults`` section
  names the injected site.
* ``shed``       — a chaos-clamped budget makes the admission
  controller SHED the query with ``CylonResourceExhausted`` before any
  device work; the decision lands in the flight admission ring.
* ``degrade``    — a moderately clamped budget on a single-shard plan
  DEGRADES the join to the blocked/chunked path; the result matches
  the clean run.
* ``deadline``   — a ~zero ``CYLON_QUERY_DEADLINE_S`` surfaces as a
  typed ``CylonTimeoutError`` with a crash dump.
* ``stats``      — the statistics-warehouse drill (PR 12): a CORRUPT
  stats snapshot at service startup is quarantined (renamed aside,
  typed ``CylonDataError`` event in the admission ring) and startup
  proceeds clean; then an injected ~10x-rows drift on a learned
  fingerprint fires ``cylon_stats_drift_total``, records a
  ``stats_drift`` flight-ring event, EVICTS the plan-cache entry
  (next optimize is a miss), and the next admission decision falls
  back to ``est_source=static`` — while the drifted run's results
  stay bit-identical to an uncached baseline.
* ``mislearn``   — the adaptive-join drill (PR 15): the stats store is
  POISONED with a 100x-understated build-side estimate on a learned
  join fingerprint, so the optimizer rewrites the shape to a
  broadcast-hash join it should never have chosen. The broadcast run
  itself measures the TRUE input sizes under the same (algorithm-
  invariant) decision fingerprint, drift fires
  (``cylon_stats_drift_total``), the plan-cache entry evicts, and the
  next optimize REVERTS to the shuffle join — with results
  bit-identical to an uncached baseline at every step (a mis-learned
  rewrite may waste memory for one run; it can never corrupt data).
* ``service``    — the CONCURRENT drill (PR 7): 6 queries across two
  tenants plus one over-budget query submitted through the
  ``QueryService`` while a transient exchange fault is armed and the
  admission budget is chaos-clamped. The faulted query retries to
  success, the over-budget one is SHED typed (admission ring names
  its tenant), every other ticket completes with results equal to
  the sequential baseline, and per-tenant outcome counters balance.

Every scenario asserts ZERO ledger leaks after its results are
dropped — retry, shed and degrade paths must not strand HBM.

Usage::

    python scripts/chaos.py --seeds 3            # the check.sh gate
    python scripts/chaos.py --seed 1             # replay one seed
    python scripts/chaos.py --seed 1 --scenario persistent

Each seed runs in a fresh subprocess (cold kernel-factory caches make
the ``compile`` arrival index deterministic); a failure prints the
fault plan + the one-command replay line.
"""
import argparse
import gc
import json
import os
import subprocess
import sys
import tempfile

os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "--xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + \
        " --xla_force_host_platform_device_count=8"
# fast, deterministic backoff for the drill
os.environ.setdefault("CYLON_RETRY_BACKOFF_S", "0.001")

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)
# the chunk pipeline is reached by no budget at a drill's size: the
# overlap scenario stands in for the function that chooses
# (tests/forced_paths.py)
sys.path.insert(0, os.path.join(_ROOT, "tests"))

SCENARIOS = ("compile", "transient", "overlap", "persistent", "shed",
             "degrade", "deadline", "stats", "mislearn", "service")


class ChaosFailure(AssertionError):
    pass


def _check(ok, msg, scenario, seed, plan):
    if not ok:
        raise ChaosFailure(
            f"[{scenario}] {msg}\n"
            f"  fault plan: {plan!r}\n"
            f"  replay: CYLON_FAULT_PLAN={plan or ''!r} python "
            f"scripts/chaos.py --seed {seed} --scenario {scenario}")


# ---------------------------------------------------------------------------
# child: one seed, fresh process
# ---------------------------------------------------------------------------


def _tables(ct, ctx, n, seed):
    import numpy as np

    rng = np.random.default_rng(seed)
    left = ct.Table.from_pydict(ctx, {
        "k": rng.integers(0, n // 4, n).astype(np.int32),
        "v": rng.normal(size=n).astype(np.float32),
        "z": rng.integers(0, 50, n).astype(np.int32)})
    right = ct.Table.from_pydict(ctx, {
        "k": rng.integers(0, n // 4, n).astype(np.int32),
        "w": rng.normal(size=n).astype(np.float32)})
    return left, right


def _pipe(plan, left, right):
    return plan.scan(left).join(plan.scan(right), on="k") \
        .groupby("lt-2", ["rt-4"], ["sum"])


def _result_rows(table):
    import numpy as np

    d = table.to_pydict()
    ks = sorted(d)
    rows = sorted(zip(*(np.asarray(d[k]).tolist() for k in ks)))
    return ks, rows


def _same_result(a, b) -> bool:
    import numpy as np

    (ka, ra), (kb, rb) = _result_rows(a), _result_rows(b)
    if ka != kb or len(ra) != len(rb):
        return False
    return all(np.allclose(x, y, rtol=1e-5, atol=1e-5)
               for x, y in zip(np.asarray(ra, dtype=np.float64).T,
                               np.asarray(rb, dtype=np.float64).T))


def _retries(telemetry) -> int:
    snap = telemetry.metrics_snapshot()
    return sum(v for k, v in snap.items()
               if k.startswith("cylon_retries_total"))


def _outcomes(telemetry, tenant: str, outcome: str) -> int:
    key = (f'cylon_queries_total{{outcome="{outcome}",'
           f'tenant="{tenant}"}}')
    return telemetry.metrics_snapshot().get(key, 0)


def _leak_check(ledger, held, scenario, seed, plan):
    """Zero NEW leaks: after a scenario drops its results, the live
    non-borrowed entry count must return to ``held`` (the deliberately
    held baseline result)."""
    gc.collect()
    _check(ledger.leak_count() == held,
           f"ledger leaks after scenario (expected {held} held "
           f"entries): {ledger.outstanding()}",
           scenario, seed, plan)


def run_seed(seed: int, only=None) -> dict:
    import cylon_tpu as ct
    from cylon_tpu import plan, telemetry
    from cylon_tpu.resilience import inject
    from cylon_tpu.telemetry import flight, ledger

    n = 2048 + 256 * (seed % 4)
    ctx = ct.CylonContext.InitDistributed(ct.TPUConfig(world_size=4))
    left, right = _tables(ct, ctx, n, seed)
    ran = {}

    def wants(name):
        return only is None or name == only

    # -- compile: first kernel-factory build faults, retried ----------
    # MUST run first: arrival 1 is only the first build while the
    # process's factory caches are cold
    if wants("compile"):
        fp = "compile:1:transient"
        inject.arm(fp)
        r0 = _retries(telemetry)
        try:
            txt = _pipe(plan, left, right).explain(analyze=True)
        finally:
            inject.disarm()
        _check(_retries(telemetry) > r0,
               "no retry recorded for the injected compile fault",
               "compile", seed, fp)
        _check("[RETRY" in txt,
               f"no [RETRY marker in EXPLAIN ANALYZE:\n{txt}",
               "compile", seed, fp)
        ran["compile"] = {"retries": _retries(telemetry) - r0}

    # clean baseline (after `compile` so its arrival index stays cold)
    baseline = _pipe(plan, left, right).execute()

    if wants("compile") and "compile" in ran:
        # the faulted-and-retried run must have produced honest output
        redo = _pipe(plan, left, right).execute()
        _check(_same_result(redo, baseline),
               "post-compile-fault execution diverges from baseline",
               "compile", seed, "compile:1:transient")
        del redo

    # every tracked entry live PAST this point that is not the held
    # baseline result is a leak
    gc.collect()
    held = ledger.leak_count()

    # -- transient: Nth exchange launch faults, retried ---------------
    if wants("transient"):
        nth = 1 + seed % 2
        fp = f"exchange:{nth}:transient"
        inject.arm(fp)
        r0 = _retries(telemetry)
        p = _pipe(plan, left, right)
        try:
            txt = p.explain(analyze=True)
            result = p.execute()
        finally:
            inject.disarm()
        _check(_retries(telemetry) > r0,
               "no retry recorded for the injected exchange fault",
               "transient", seed, fp)
        _check("[RETRY" in txt,
               f"no [RETRY marker in EXPLAIN ANALYZE:\n{txt}",
               "transient", seed, fp)
        _check(_same_result(result, baseline),
               "retried run diverges from clean baseline",
               "transient", seed, fp)
        del result
        _leak_check(ledger, held, "transient", seed, fp)
        ran["transient"] = {"retries": _retries(telemetry) - r0,
                            "nth": nth}

    # -- overlap: transient fault mid-chunk-stream of the chunked
    # (double-buffered) exchange pipeline — the faulted chunk retries
    # idempotently and the result bit-matches the single-shot baseline
    if wants("overlap"):
        nth = 2 + seed % 3
        fp = f"exchange:{nth}:transient"
        import forced_paths
        from _pytest.monkeypatch import MonkeyPatch

        patch = MonkeyPatch()
        forced_paths.chunked(patch, 4096)
        inject.arm(fp)
        r0 = _retries(telemetry)
        c0 = telemetry.metrics_snapshot().get(
            "cylon_exchange_chunks_total", 0)
        p = _pipe(plan, left, right)
        try:
            txt = p.explain(analyze=True)
            result = p.execute()
        finally:
            inject.disarm()
            patch.undo()
        chunks_moved = telemetry.metrics_snapshot().get(
            "cylon_exchange_chunks_total", 0) - c0
        _check(chunks_moved > 0,
               "forced chunk plan did not engage the chunked pipeline",
               "overlap", seed, fp)
        _check(_retries(telemetry) > r0,
               "no retry recorded for the fault mid-chunk-stream",
               "overlap", seed, fp)
        _check("[RETRY" in txt,
               f"no [RETRY marker in EXPLAIN ANALYZE:\n{txt}",
               "overlap", seed, fp)
        _check(_same_result(result, baseline),
               "chunked pipeline result diverges from the single-shot "
               "baseline after mid-stream retry", "overlap", seed, fp)
        del result
        _leak_check(ledger, held, "overlap", seed, fp)
        ran["overlap"] = {"retries": _retries(telemetry) - r0,
                          "nth": nth, "chunks": chunks_moved}

    # -- persistent: every exchange attempt faults -> typed + dump ----
    if wants("persistent"):
        fp = "exchange:1+:transient"
        dump_dir = tempfile.mkdtemp(prefix="cylon-chaos-")
        os.environ["CYLON_FLIGHT_DIR"] = dump_dir
        inject.arm(fp)
        err_text = None
        try:
            # capture TEXT, never the exception object: its traceback
            # would pin the executor frames (and their intermediate
            # tables) past the leak check below
            try:
                _pipe(plan, left, right).explain(analyze=True)
            except ct.CylonTransientError as e:
                err_text = str(e)
            except Exception as e:  # noqa: BLE001 - asserted below
                _check(False, f"expected CylonTransientError, got "
                       f"{type(e).__name__}: {e}", "persistent", seed,
                       fp)
            else:
                _check(False, "persistent fault did not fail the query",
                       "persistent", seed, fp)
        finally:
            fault_state = inject.state()
            inject.disarm()
            os.environ.pop("CYLON_FLIGHT_DIR", None)
        _check("injected transient fault at exchange" in err_text,
               f"error does not name the fault: {err_text}",
               "persistent", seed, fp)
        dumps = [f for f in os.listdir(dump_dir) if f.endswith(".json")]
        _check(len(dumps) == 1, f"expected one crash dump, found "
               f"{dumps}", "persistent", seed, fp)
        doc = json.load(open(os.path.join(dump_dir, dumps[0])))
        faults = doc.get("sections", {}).get("faults", {})
        _check(any(f.get("site") == "exchange"
                   for f in faults.get("fired", [])),
               f"crash dump faults section does not name the exchange "
               f"site: {faults}", "persistent", seed, fp)
        _check(any(s["name"].startswith("plan.")
                   for s in doc.get("error_path", [])),
               f"crash dump error path has no plan span: "
               f"{[s['name'] for s in doc.get('error_path', [])]}",
               "persistent", seed, fp)
        _leak_check(ledger, held, "persistent", seed, fp)
        ran["persistent"] = {"fired": len(fault_state["fired"]),
                             "dump": dumps[0]}

    # -- shed: clamped budget -> admission sheds before device work ---
    if wants("shed"):
        fp = "pool:4096:oom"
        inject.arm(fp)
        err_text = None
        try:
            try:
                _pipe(plan, left, right).execute(analyze=True)
            except ct.CylonResourceExhausted as e:
                err_text = str(e)
            else:
                _check(False, "over-budget query was not shed", "shed",
                       seed, fp)
        finally:
            inject.disarm()
        _check("shed by admission controller" in err_text,
               f"unexpected shed error text: {err_text}", "shed", seed,
               fp)
        last = flight.admissions()[-1] if flight.admissions() else {}
        _check(last.get("action") == "shed",
               f"admission ring does not record the shed: {last}",
               "shed", seed, fp)
        _leak_check(ledger, held, "shed", seed, fp)
        ran["shed"] = {"decision": last}

    # -- degrade: single-shard join over budget -> blocked path -------
    if wants("degrade"):
        fp = "pool:32768:oom"
        lctx = ct.CylonContext.Init()
        l2, r2 = _tables(ct, lctx, n, seed + 100)
        lpipe = plan.scan(l2).join(plan.scan(r2), on="k")
        clean = lpipe.execute()
        inject.arm(fp)
        try:
            p = plan.scan(l2).join(plan.scan(r2), on="k")
            degraded = p.execute(analyze=True)
            rep = p.last_report
        finally:
            inject.disarm()
        _check(rep.admission is not None
               and rep.admission.get("action") == "degrade",
               f"admission did not degrade: {rep.admission}",
               "degrade", seed, fp)
        _check(_same_result(degraded, clean),
               "degraded (blocked) join diverges from clean join",
               "degrade", seed, fp)
        last = flight.admissions()[-1] if flight.admissions() else {}
        _check(last.get("action") == "degrade",
               f"admission ring does not record the degrade: {last}",
               "degrade", seed, fp)
        del degraded, clean
        _leak_check(ledger, held, "degrade", seed, fp)
        ran["degrade"] = {"decision": last}

    # -- deadline: ~zero budget -> typed timeout + dump ---------------
    if wants("deadline"):
        dump_dir = tempfile.mkdtemp(prefix="cylon-chaos-")
        os.environ["CYLON_FLIGHT_DIR"] = dump_dir
        os.environ["CYLON_QUERY_DEADLINE_S"] = "0.000001"
        err_text = None
        try:
            try:
                _pipe(plan, left, right).execute(analyze=True)
            except ct.CylonTimeoutError as e:
                err_text = str(e)
            else:
                _check(False, "zero deadline did not time the query "
                       "out", "deadline", seed, None)
        finally:
            os.environ.pop("CYLON_QUERY_DEADLINE_S", None)
            os.environ.pop("CYLON_FLIGHT_DIR", None)
        _check("deadline exceeded" in err_text,
               f"unexpected timeout text: {err_text}", "deadline",
               seed, None)
        dumps = [f for f in os.listdir(dump_dir) if f.endswith(".json")]
        _check(len(dumps) == 1,
               f"expected one crash dump, found {dumps}", "deadline",
               seed, None)
        _leak_check(ledger, held, "deadline", seed, None)
        ran["deadline"] = {"dump": dumps[0]}

    # -- stats: corrupt snapshot quarantined; drift evicts + reverts --
    if wants("stats"):
        from cylon_tpu.service import QueryService, plancache
        from cylon_tpu.telemetry import querylog

        def snap_counter(name):
            return telemetry.metrics_snapshot().get(name, 0)

        # (a) corrupted stats file at startup -> quarantine + clean
        # start through the REAL startup path (QueryService.start)
        sdir = tempfile.mkdtemp(prefix="cylon-chaos-stats-")
        spath = os.path.join(sdir, "stats.jsonl")
        with open(spath, "w") as f:
            f.write("{corrupt" + "}" * (seed + 1) + "\n")
        os.environ["CYLON_STATS_PATH"] = spath
        q0 = snap_counter("cylon_stats_quarantine_total")
        try:
            svc = QueryService(name=f"chaos-stats-{seed}")
            svc.close()
        finally:
            os.environ.pop("CYLON_STATS_PATH", None)
        _check(snap_counter("cylon_stats_quarantine_total") == q0 + 1,
               "corrupt stats snapshot was not quarantined", "stats",
               seed, None)
        _check(os.path.exists(spath + ".quarantine"),
               "quarantined snapshot not preserved on disk", "stats",
               seed, None)
        quarantines = [d for d in flight.admissions()
                       if d.get("action") == "stats_quarantine"]
        _check(quarantines and
               "CylonDataError" in quarantines[-1].get("error", ""),
               f"no typed quarantine event in the admission ring: "
               f"{quarantines[-1:]}", "stats", seed, None)

        # (b) drift: learn a shape, then hit it with ~10x the rows
        os.environ["CYLON_STATS_MIN_OBS"] = "2"
        try:
            sl, sr = _tables(ct, ctx, n, seed + 200)

            def spipe(l, r):
                return plan.scan(l).join(plan.scan(r), on="k") \
                    .groupby("lt-2", ["rt-4"], ["min"])

            for _ in range(2):
                spipe(sl, sr).execute()
            learned = querylog.recent()[-1]
            _check(learned.get("est_source") == "measured",
                   f"learned shape not measured-calibrated: "
                   f"{learned.get('est_source')}", "stats", seed, None)
            d0 = snap_counter("cylon_stats_drift_total")
            m0 = snap_counter("cylon_plan_cache_misses_total")
            bl, br = _tables(ct, ctx, n * 10, seed + 201)
            drifted = spipe(bl, br).execute()
            _check(snap_counter("cylon_stats_drift_total") > d0,
                   "10x-rows run did not fire drift detection",
                   "stats", seed, None)
            drifts = [d for d in flight.admissions()
                      if d.get("action") == "stats_drift"]
            _check(bool(drifts), "no stats_drift event in the "
                   "admission ring", "stats", seed, None)
            # eviction: the next optimize of the learned shape MISSES
            spipe(sl, sr).optimized()
            _check(snap_counter("cylon_plan_cache_misses_total")
                   == m0 + 1,
                   "drift did not evict the cached plan template",
                   "stats", seed, None)
            # fallback: the next decision runs on static estimates
            after = spipe(bl, br)
            redo = after.execute()
            _check(querylog.recent()[-1].get("est_source") == "static",
                   f"post-drift admission did not fall back to static "
                   f"estimates: {querylog.recent()[-1]}", "stats",
                   seed, None)
            # ...and none of it perturbs data: bit-identical to an
            # uncached fresh execution
            with plancache.disabled():
                clean10 = spipe(bl, br).execute()
            _check(_same_result(drifted, clean10)
                   and _same_result(redo, clean10),
                   "drifted/post-drift results diverge from the "
                   "uncached baseline", "stats", seed, None)
            del drifted, redo, clean10, sl, sr, bl, br
        finally:
            os.environ.pop("CYLON_STATS_MIN_OBS", None)
        _leak_check(ledger, held, "stats", seed, None)
        ran["stats"] = {"quarantine": quarantines[-1]["error"][:60],
                        "drift": drifts[-1]["metric"]}

    # -- mislearn: poisoned stats -> unsound-by-stats broadcast choice
    # self-corrects via drift eviction, zero wrong results throughout
    if wants("mislearn"):
        from cylon_tpu.plan.fingerprint import join_decision_fingerprint
        from cylon_tpu.plan.optimizer import BROADCAST_MIN_RATIO
        from cylon_tpu.service import plancache
        from cylon_tpu.telemetry import stats as stats_mod

        stats_mod.reset()
        ml, mr = _tables(ct, ctx, n, seed + 300)

        def mpipe():
            return plan.scan(ml).join(plan.scan(mr), on="k")

        with plancache.disabled():
            mbase = mpipe().execute()
        world = ctx.get_world_size()
        # poison: REPLACE the learned evidence with a build (right)
        # side measured at ~1/100 of its true size, the probe
        # comfortably past the ratio guard — the mis-learned state a
        # corrupted snapshot or a regime change could leave behind
        # (the baseline's own genuine observation is dropped first:
        # poisoning means the store's memory IS the lie)
        stats_mod.reset()
        real = float(mr.nbytes)
        assert float(ml.nbytes) >= BROADCAST_MIN_RATIO * real / 100.0
        fp = join_decision_fingerprint(mpipe()._node, world)
        for i in range(stats_mod.min_obs()):
            stats_mod.STORE._observe_node(
                "poisoned", fp, "join_input",
                {"left_bytes": float(ml.nbytes),
                 "right_bytes": max(real / 100.0, 1.0)},
                ("left_bytes", "right_bytes"), None, float(i))
        txt = mpipe().explain()
        _check("algo=broadcast" in txt,
               f"poisoned stats did not fire the broadcast rewrite:\n"
               f"{txt}", "mislearn", seed, None)
        d0 = telemetry.metrics_snapshot().get(
            "cylon_stats_drift_total", 0)
        bad_run = mpipe().execute()    # broadcast runs, measures truth
        _check(_same_result(bad_run, mbase),
               "mis-learned broadcast run diverges from the uncached "
               "baseline", "mislearn", seed, None)
        _check(telemetry.metrics_snapshot().get(
            "cylon_stats_drift_total", 0) > d0,
               "true input sizes did not fire drift on the poisoned "
               "fingerprint", "mislearn", seed, None)
        drifts = [d for d in flight.admissions()
                  if d.get("action") == "stats_drift"]
        _check(bool(drifts), "no stats_drift event in the admission "
               "ring", "mislearn", seed, None)
        txt2 = mpipe().explain()
        _check("algo=broadcast" not in txt2,
               f"drift did not revert the shape to shuffle:\n{txt2}",
               "mislearn", seed, None)
        good_run = mpipe().execute()
        _check(_same_result(good_run, mbase),
               "post-revert shuffle run diverges from the uncached "
               "baseline", "mislearn", seed, None)
        del bad_run, good_run, mbase, ml, mr
        stats_mod.reset()
        _leak_check(ledger, held, "mislearn", seed, None)
        ran["mislearn"] = {"drift": drifts[-1]["metric"],
                           "reverted": True}

    # -- service: concurrent submissions, fault + shed among them -----
    if wants("service"):
        from cylon_tpu.service import QueryService

        clamp = 256 * 1024          # normal queries ~0.5x, big ~26x
        nth = 3 + seed % 3          # exchange arrival hit mid-stream
        fp = f"pool:{clamp}:oom,exchange:{nth}:transient"
        tenants = ("tenant-a", "tenant-b")
        tabs = {t: _tables(ct, ctx, n, seed + 10 + i)
                for i, t in enumerate(tenants)}
        big_l, big_r = _tables(ct, ctx, 1 << 16, seed + 50)
        # clean sequential baselines, BEFORE arming (the acceptance
        # bar: concurrent results bit-match sequential execution)
        baselines = {t: _pipe(plan, l, r).execute()
                     for t, (l, r) in tabs.items()}
        svc = QueryService(start=False)   # paused: dispatch order is a
        #                                   pure function of submission
        inject.arm(fp)
        r0 = _retries(telemetry)
        ok0 = {t: _outcomes(telemetry, t, "ok") for t in tenants}
        tickets = []
        try:
            for _ in range(3):
                for t, (l, r) in tabs.items():
                    tickets.append((t, svc.submit(_pipe(plan, l, r),
                                                  tenant=t)))
            big = svc.submit(
                plan.scan(big_l).join(plan.scan(big_r), on="k"),
                tenant="tenant-a")
            svc.drain(timeout=600)
        finally:
            inject.disarm()
            svc.close()
        _check(_retries(telemetry) > r0,
               "no retry recorded for the injected exchange fault "
               "during the service drill", "service", seed, fp)
        for t, tk in tickets:
            res = tk.result(timeout=60)
            _check(tk.outcome == "ok",
                   f"ticket {tk.query_id} ({t}) outcome "
                   f"{tk.outcome!r}, wanted ok", "service", seed, fp)
            _check(_same_result(res, baselines[t]),
                   f"concurrent result for {t} diverges from the "
                   f"sequential baseline", "service", seed, fp)
            del res
        err_text = None
        try:
            big.result(timeout=60)
        except ct.CylonResourceExhausted as e:
            err_text = str(e)
        else:
            _check(False, "over-budget service query was not shed",
                   "service", seed, fp)
        _check("shed by admission controller" in err_text,
               f"unexpected shed error text: {err_text}", "service",
               seed, fp)
        _check(big.outcome == "shed",
               f"shed ticket outcome {big.outcome!r}", "service",
               seed, fp)
        sheds = [d for d in flight.admissions()
                 if d.get("action") == "shed"]
        _check(sheds and sheds[-1].get("tenant") == "tenant-a",
               f"admission ring does not name the shed tenant: "
               f"{sheds[-1:]}", "service", seed, fp)
        for t in tenants:
            got = _outcomes(telemetry, t, "ok") - ok0[t]
            _check(got == 3,
                   f"cylon_queries_total{{tenant={t},outcome=ok}} "
                   f"moved by {got}, wanted 3", "service", seed, fp)
        n_retried = _retries(telemetry) - r0
        # drop every result reference (incl. the comparison loop vars)
        # before the zero-new-leaks assertion
        del big, tickets, baselines, tabs, big_l, big_r, svc, t, tk, l, r
        _leak_check(ledger, held, "service", seed, fp)
        ran["service"] = {"retries": n_retried, "nth": nth,
                          "shed": sheds[-1]}

    del baseline
    gc.collect()
    return ran


# ---------------------------------------------------------------------------
# parent: sweep seeds in fresh subprocesses
# ---------------------------------------------------------------------------


def sweep(seeds: int, scenario=None) -> int:
    for seed in range(seeds):
        cmd = [sys.executable, os.path.abspath(__file__),
               "--seed", str(seed)]
        if scenario:
            cmd += ["--scenario", scenario]
        r = subprocess.run(cmd, capture_output=True, text=True,
                           timeout=900)
        if r.returncode != 0:
            sys.stderr.write(r.stdout)
            sys.stderr.write(r.stderr)
            print(f"chaos: FAIL at seed {seed} — replay with: "
                  f"python scripts/chaos.py --seed {seed}"
                  + (f" --scenario {scenario}" if scenario else ""),
                  file=sys.stderr)
            return 1
        # last stdout line is the child's JSON summary
        tail = [l for l in r.stdout.splitlines() if l.strip()]
        print(f"chaos: seed {seed} OK — "
              f"{tail[-1] if tail else '(no summary)'}")
    print(f"chaos: OK — {seeds} seed(s), all scenarios deterministic")
    return 0


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="python scripts/chaos.py",
        description="seeded chaos drill over the resilience layer "
                    "(docs/resilience.md)")
    p.add_argument("--seeds", type=int,
                   help="sweep seeds 0..N-1, one fresh subprocess each")
    p.add_argument("--seed", type=int,
                   help="run ONE seed in this process (the child/"
                        "replay mode)")
    p.add_argument("--scenario", choices=SCENARIOS,
                   help="restrict to one scenario")
    args = p.parse_args(argv)
    if args.seed is not None:
        ran = run_seed(args.seed, only=args.scenario)
        print(json.dumps({"seed": args.seed, "scenarios": ran},
                         default=str))
        return 0
    return sweep(args.seeds or 3, scenario=args.scenario)


if __name__ == "__main__":
    sys.exit(main())
