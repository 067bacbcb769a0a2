"""cylon_tpu.analysis self-tests: each checker reports EXACTLY the
violations seeded in tests/analysis_fixtures/ (no more, no fewer), the
repo's own tree is clean, suppressions count, and the JSON output
schema is stable."""
import json
import os
import subprocess
import sys

import pytest

import cylon_tpu
from cylon_tpu.analysis import (AnalysisContext, SCHEMA_VERSION,
                                run_checkers, specialization, to_json_text)

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURES = os.path.join(HERE, "analysis_fixtures")
PKG_BAD = os.path.join(FIXTURES, "pkg_bad")
PKG_REAL = os.path.dirname(os.path.abspath(cylon_tpu.__file__))


def findings_of(res, family):
    return [f for f in res.findings if f.family == family]


# ---------------------------------------------------------------------------
# layering
# ---------------------------------------------------------------------------


def test_layering_fixture_reports_exactly_seeded():
    res = run_checkers(AnalysisContext(PKG_BAD), families=["layering"])
    got = {(f.path, f.line, f.rule) for f in res.findings}
    assert got == {
        ("memory.py", 3, "layering/base-leaf"),
        # the telemetry module→package split: the leaf contract still
        # fires on a back-import, while intra-telemetry imports pass
        ("telemetry/__init__.py", 4, "layering/telemetry-leaf"),
        # private-internals across the split: module form, submodule
        # import form, and both attribute-access forms
        ("sneaky.py", 4, "layering/private-internals"),
        ("sneaky.py", 6, "layering/private-internals"),
        ("sneaky.py", 11, "layering/private-internals"),
        ("sneaky.py", 16, "layering/private-internals"),
        ("ops/bad_kernel.py", 7, "layering/ops-leaf"),
        ("plan/bad_lowering.py", 3, "layering/plan-no-ops"),
        ("plan/bad_lowering.py", 4, "layering/plan-no-ops"),
        ("data/column.py", 3, "layering/data-below-ops"),
        # the service tier (PR 7): reaching past the plan seam into
        # device machinery, and a lower layer importing service back
        ("service/__init__.py", 4, "layering/service-top"),
        ("plan/uses_service.py", 4, "layering/below-service"),
    }, res.format_text()
    # the seeded suppression on data/column.py:7 counted as suppressed
    assert res.suppressed == 1


def test_layering_real_tree_clean():
    res = run_checkers(AnalysisContext(PKG_REAL), families=["layering"])
    assert res.findings == [], res.format_text()


def test_parallel_no_plan_has_no_exemption():
    """The `parallel/task_plan.py` shim went (PR 45): nothing under
    `parallel/` imports `plan/`, and the contract exempts no file."""
    from cylon_tpu.analysis import layering

    (c,) = [c for c in layering.DEFAULT_CONTRACTS
            if c.name == "parallel-no-plan"]
    assert not c.exempt
    assert not os.path.exists(os.path.join(PKG_REAL, "parallel",
                                           "task_plan.py"))


# ---------------------------------------------------------------------------
# span-coverage
# ---------------------------------------------------------------------------


def test_spancov_fixture_reports_exactly_seeded():
    res = run_checkers(AnalysisContext(PKG_BAD),
                       families=["span-coverage"])
    got = {(f.path, f.line, f.rule) for f in res.findings}
    assert got == {
        ("parallel/dist_ops.py", 14, "span-coverage/missing-span"),
        ("plan/executor.py", 12, "span-coverage/missing-span"),
        # host_fetch(site, x) with a computed site; the literal site
        # one line above it stays clean
        ("parallel/dist_ops.py", 70, "span-coverage/dynamic-sync-site"),
    }, res.format_text()
    # private helpers / non-distributed_* / non-_do_* stay out of scope
    msgs = " ".join(f.message for f in res.findings)
    assert "_helper" not in msgs and "repartition_like" not in msgs


def test_spancov_real_tree_clean():
    """Every public distributed_* op and every executor lowering in the
    real package runs under a span — the observability coverage
    contract the EXPLAIN ANALYZE acceptance rests on."""
    res = run_checkers(AnalysisContext(PKG_REAL),
                       families=["span-coverage"])
    assert res.findings == [], res.format_text()


# ---------------------------------------------------------------------------
# ledger-coverage
# ---------------------------------------------------------------------------


def test_ledgercov_fixture_reports_exactly_seeded():
    """The memory analog of span-coverage: the bare op fails BOTH
    families, the spanned-but-untracked ones fail only the ledger."""
    res = run_checkers(AnalysisContext(PKG_BAD),
                       families=["ledger-coverage"])
    got = {(f.path, f.line, f.rule) for f in res.findings}
    assert got == {
        ("parallel/dist_ops.py", 14, "ledger-coverage/missing-ledger"),
        ("parallel/dist_ops.py", 18, "ledger-coverage/missing-ledger"),
        ("plan/executor.py", 12, "ledger-coverage/missing-ledger"),
        ("plan/executor.py", 15, "ledger-coverage/missing-ledger"),
    }, res.format_text()
    msgs = " ".join(f.message for f in res.findings)
    assert "_helper" not in msgs and "repartition_like" not in msgs


def test_ledgercov_real_tree_clean():
    """Every materializing distributed_* op and every executor lowering
    registers its output with the telemetry ledger — the attribution
    contract the leak report and crash-dump forensics rest on."""
    res = run_checkers(AnalysisContext(PKG_REAL),
                       families=["ledger-coverage"])
    assert res.findings == [], res.format_text()


# ---------------------------------------------------------------------------
# errors (no silent swallowing)
# ---------------------------------------------------------------------------


def test_errors_fixture_reports_exactly_seeded():
    """Bare excepts and broad swallows are findings; re-raising,
    logging, error=True span marking and narrow handlers are not; the
    deliberate fallback's per-line opt-out counts as suppressed."""
    res = run_checkers(AnalysisContext(PKG_BAD), families=["errors"])
    got = {(f.path, f.line, f.rule) for f in res.findings}
    assert got == {
        ("errors_bad.py", 11, "errors/bare-except"),
        ("errors_bad.py", 18, "errors/broad-swallow"),
        ("errors_bad.py", 25, "errors/broad-swallow"),
        ("errors_bad.py", 32, "errors/broad-swallow"),
    }, res.format_text()
    assert res.suppressed == 1


def test_errors_real_tree_clean():
    """Every broad handler in the real package either reports through
    the telemetry error channel or carries an explicit per-line
    opt-out documenting the deliberate fallback — silent swallowing
    is never the default."""
    res = run_checkers(AnalysisContext(PKG_REAL), families=["errors"])
    assert res.findings == [], res.format_text()
    # the deliberate defensive fallbacks are visible as suppressions,
    # not invisible as accepted defaults
    assert res.suppressed >= 10


def test_errors_family_in_fixture_cli_default():
    """`python -m cylon_tpu.analysis --package-root <fixture>` runs the
    errors family by default and fails on the seeded swallows."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, "-m", "cylon_tpu.analysis", "--package-root",
         PKG_BAD],
        capture_output=True, text=True, cwd=os.path.dirname(PKG_REAL),
        env=env, timeout=300)
    assert r.returncode == 1
    assert "[errors/bare-except]" in r.stdout
    assert "[errors/broad-swallow]" in r.stdout


# ---------------------------------------------------------------------------
# hostsync
# ---------------------------------------------------------------------------


def test_hostsync_fixture_reports_exactly_seeded():
    res = run_checkers(AnalysisContext(PKG_BAD), families=["hostsync"])
    got = {(f.path, f.line, f.rule) for f in res.findings}
    assert got == {
        ("ops/bad_kernel.py", 11, "hostsync/concretize"),
        ("ops/bad_kernel.py", 12, "hostsync/transfer"),
        ("ops/bad_kernel.py", 20, "hostsync/transfer"),
        ("ops/bad_kernel.py", 25, "hostsync/transfer"),
        # host-side device_get outside the choke point, in a file of
        # the fetch scope (the nested def reports once)
        ("parallel/dist_ops.py", 56, "hostsync/bare-fetch"),
        ("parallel/dist_ops.py", 63, "hostsync/bare-fetch"),
    }, res.format_text()
    # host_side_ok's transfers are OUTSIDE any traced closure: none of
    # its lines (29+) may appear
    assert not any(f.path == "ops/bad_kernel.py" and f.line >= 28
                   for f in res.findings)


def test_hostsync_bare_fetch_honours_declared_bulk_exports():
    """A function declared as a bulk mover of whole columns keeps its
    plain device_get; the undeclared one beside it stays flagged."""
    ctx = AnalysisContext(PKG_BAD, options={"bulk_exports": {
        ("parallel/dist_ops.py", "_gather_for_output")}})
    res = run_checkers(ctx, families=["hostsync"])
    got = {f.line for f in res.findings if f.rule == "hostsync/bare-fetch"}
    assert got == {56}, res.format_text()


def test_hostsync_real_tree_clean():
    res = run_checkers(AnalysisContext(PKG_REAL), families=["hostsync"])
    assert res.findings == [], res.format_text()


def test_hostsync_closure_reports_trace_chain():
    res = run_checkers(AnalysisContext(PKG_BAD), families=["hostsync"])
    via = [f.message for f in res.findings if f.line == 20]
    assert via and "decorated_kernel" in via[0] and "_helper" in via[0]


# ---------------------------------------------------------------------------
# collectives
# ---------------------------------------------------------------------------


def test_collectives_fixture_reports_exactly_seeded():
    ctx = AnalysisContext(PKG_REAL, options={
        "collectives_entry_module":
            os.path.join(FIXTURES, "collectives_bad.py")})
    res = run_checkers(ctx, families=["collectives"])
    rules = sorted(f.rule for f in res.findings)
    assert rules == ["collectives/all-to-all-axes",
                     "collectives/f64-promotion",
                     "collectives/trace-error"], res.format_text()
    by_rule = {f.rule: f.message for f in res.findings}
    assert "bad_axis" in by_rule["collectives/trace-error"]
    assert "bad_all_to_all" in by_rule["collectives/all-to-all-axes"]
    assert "f64_promotion" in by_rule["collectives/f64-promotion"]
    # the clean control kernel contributed nothing
    assert not any("clean" in f.message for f in res.findings)


def test_collectives_real_catalog_clean():
    res = run_checkers(AnalysisContext(PKG_REAL),
                       families=["collectives"])
    assert res.findings == [], res.format_text()
    # Pallas stream factories are skipped off-TPU, with a note
    assert any("TPU-only" in n for n in res.notes)


def test_collectives_uncataloged_factory_fixture():
    """The old coverage NOTE is now a real finding: a `_*_fn` in
    parallel/ outside the entry-point catalog fails the gate, and an
    intentional exclusion is a per-line suppression (counted), never a
    hidden set."""
    res = run_checkers(
        AnalysisContext(PKG_BAD,
                        options={"collectives_coverage_only": True}),
        families=["collectives"])
    got = {(f.path, f.rule) for f in res.findings}
    assert got == {("parallel/dist_ops.py",
                    "collectives/uncataloged-factory")}, res.format_text()
    assert len(res.findings) == 4
    names = " ".join(f.message for f in res.findings)
    assert "_rogue_kernel_fn" in names
    # the chunked-exchange-shaped factory is swept the same way: a new
    # chunk program outside the catalog is a finding, not a note
    assert "_chunk_rogue_fn" in names
    # …as is a partition-path-shaped factory (the Pallas-kernel route)
    assert "_partition_rogue_fn" in names
    # …and a broadcast-join-shaped factory (the adaptive-join route)
    assert "_bcast_rogue_fn" in names
    # _host_helper_fn opted out on its def line — suppressed, visible
    assert res.suppressed == 1


def test_collectives_coverage_sweep_real_tree_pinned():
    """Every `_*_fn` factory in the real parallel/ tree is either in
    the catalog or carries an explicit disable (currently exactly one:
    shuffle._to_varying_fn, which returns a host callable)."""
    res = run_checkers(
        AnalysisContext(PKG_REAL,
                        options={"collectives_coverage_only": True}),
        families=["collectives"])
    assert res.findings == [], res.format_text()
    assert res.suppressed == 1


# ---------------------------------------------------------------------------
# witness (checker level; verifier semantics in test_plan_verify.py)
# ---------------------------------------------------------------------------


def test_witness_fixture_rejects_mutated_accepts_intact():
    ctx = AnalysisContext(PKG_REAL, options={
        "witness_plan_module": os.path.join(FIXTURES, "witness_bad.py")})
    res = run_checkers(ctx, families=["witness"])
    assert len(res.findings) == 1, res.format_text()
    f = res.findings[0]
    assert f.rule == "witness/unjustified-elision"
    assert "hand-deleted-shuffle" in f.message
    assert "intact" not in f.message


def test_witness_default_corpus_clean():
    res = run_checkers(
        AnalysisContext(PKG_REAL, options={"random_plans": 32}),
        families=["witness"])
    assert res.findings == [], res.format_text()
    assert any("mutations correctly rejected" in n for n in res.notes)


# ---------------------------------------------------------------------------
# concurrency (thread-domain race detector)
# ---------------------------------------------------------------------------


def test_concurrency_fixture_reports_exactly_seeded():
    """The seeded race classes all fire — two-domain unlocked counter
    (both write sites), lock-discipline break, direct + transitive
    blocking-under-lock (the transitive case flags the locked call
    site AND the inherited-lock primitive site), unstamped worker
    contextvar read, and both finalizer hazards — and the suppressed
    control counts as suppressed, never as accepted."""
    res = run_checkers(AnalysisContext(PKG_BAD),
                       families=["concurrency"])
    got = {(f.path, f.line, f.rule) for f in res.findings}
    assert got == {
        ("service/racy.py", 24, "concurrency/unlocked-shared-write"),
        ("service/racy.py", 25, "concurrency/unstamped-contextvar"),
        ("service/racy.py", 32, "concurrency/unlocked-shared-write"),
        ("service/racy.py", 35, "concurrency/blocking-under-lock"),
        ("service/racy.py", 38, "concurrency/lock-discipline"),
        ("service/racy.py", 42, "concurrency/blocking-under-lock"),
        ("service/racy.py", 45, "concurrency/blocking-under-lock"),
        # review-fix pins: the nested _helper's local _registry must
        # not hide the outer _poll's global write, and a bare
        # queue-shaped .get() under a lock blocks indefinitely — while
        # the explicit non-blocking spellings (acquire(blocking=False),
        # get(block=False) at lines 79/81) stay legal
        ("service/racy.py", 63, "concurrency/unlocked-shared-write"),
        ("service/racy.py", 75, "concurrency/blocking-under-lock"),
        # two writers under two DIFFERENT locks do not exclude each
        # other: the guard is the intersection of locks held at every
        # locked write, and an empty intersection flags each write
        ("service/racy.py", 91, "concurrency/lock-discipline"),
        ("service/racy.py", 95, "concurrency/lock-discipline"),
        # contextvar matching is name-level, so a var imported from its
        # declaring module (telemetry.gc_bad) is still seen in the
        # importing module's worker code
        ("service/racy.py", 110, "concurrency/unstamped-contextvar"),
        # a multi-item with: the 2nd item's expression evaluates with
        # the 1st item's lock already held (CvWaiter's clean cv.wait
        # helper idiom is pinned by ABSENCE — no findings on
        # _loop/_wait_ready, the caller-inherited cv keeps wait legal)
        ("service/racy.py", 132, "concurrency/blocking-under-lock"),
        ("telemetry/gc_bad.py", 20, "concurrency/finalizer-hazard"),
        ("telemetry/gc_bad.py", 22, "concurrency/finalizer-hazard"),
    }, res.format_text()
    # the suppressed _fut write (explicit per-line opt-out)
    assert res.suppressed == 1


def test_concurrency_reports_domain_and_chain():
    """Findings carry the thread-domain reachability chain so a false
    positive is cheap to triage: the transitive sleep names the
    locked caller, the counter names both domains."""
    res = run_checkers(AnalysisContext(PKG_BAD),
                       families=["concurrency"])
    by_line = {f.line: f.message for f in res.findings
               if f.path == "service/racy.py"}
    assert "drain" in by_line[45] and "_flush" in by_line[45]
    assert "api" in by_line[24] and "worker:" in by_line[24]
    # the finalizer hazard names the fix
    gc_msgs = [f.message for f in res.findings
               if f.path == "telemetry/gc_bad.py"]
    assert any("RLock" in m for m in gc_msgs)
    assert any("jax" in m for m in gc_msgs)
    # the domain census rides the notes
    assert any(n.startswith("concurrency: domains") for n in res.notes)


def test_concurrency_real_tree_clean():
    """The real service/telemetry/resilience tree passes the race
    detector — every deliberate lock-free fast path (GIL-atomic
    reference/int reads) carries a reasoned per-line opt-out, visible
    as suppressions rather than silently accepted."""
    res = run_checkers(AnalysisContext(PKG_REAL),
                       families=["concurrency"])
    assert res.findings == [], res.format_text()
    assert res.suppressed >= 5
    # the worker/api/finalizer/hook domains were actually discovered
    note = next(n for n in res.notes
                if n.startswith("concurrency: domains"))
    for d in ("api", "finalizer", "hook", "worker:"):
        assert d in note, note


# ---------------------------------------------------------------------------
# envknobs (declared CYLON_* knob registry)
# ---------------------------------------------------------------------------


def test_envknobs_fixture_reports_exactly_seeded():
    res = run_checkers(AnalysisContext(PKG_BAD), families=["envknobs"])
    got = {(f.path, f.line, f.rule) for f in res.findings}
    assert got == {
        ("envknobs_bad.py", 10, "envknobs/unregistered-read"),
        ("envknobs_bad.py", 11, "envknobs/unregistered-read"),
        ("envknobs_bad.py", 12, "envknobs/unregistered-read"),
        ("envknobs_bad.py", 18, "envknobs/unregistered-read"),
        ("envknobs_bad.py", 27, "envknobs/undeclared-knob"),
    }, res.format_text()
    # the suppressed CYLON_QUIET read
    assert res.suppressed == 1
    # fixture trees have no sibling docs/ — skipped with a note
    assert any("documentation check skipped" in n for n in res.notes)


def test_envknobs_real_tree_clean_zero_suppressions():
    """Every CYLON_* read in the real package routes through
    telemetry/knobs.py and every declared knob is documented — with
    ZERO suppressions (the migration left no sanctioned ad-hoc
    reads)."""
    res = run_checkers(AnalysisContext(PKG_REAL), families=["envknobs"])
    assert res.findings == [], res.format_text()
    assert res.suppressed == 0
    note = next(n for n in res.notes if "declared knobs" in n)
    assert "0 unregistered read site(s)" in note


def test_envknobs_real_registry_matches_docs_table():
    """The generated table (knobs.render_table) is embedded verbatim in
    docs/telemetry.md, so the docs can never drift from the code."""
    from cylon_tpu.telemetry import knobs

    docs = open(os.path.join(os.path.dirname(PKG_REAL), "docs",
                             "telemetry.md"), encoding="utf-8").read()
    assert knobs.render_table() in docs
    # and the registry itself parses + floors like env_number did
    assert knobs.get("CYLON_RETRY_MAX") == 3
    assert knobs.default("CYLON_SERVICE_QUEUE_MAX") == 256


@pytest.mark.parametrize("name,value", [
    ("CYLON_PARTITION_KERNEL", "sort"), ("CYLON_EXCHANGE_OVERLAP", "0"),
    ("CYLON_JOIN_ALGORITHM", "sort"),
    ("CYLON_EXCHANGE_CHUNK_BYTES", "4096")])
def test_no_knob_chooses_a_distributed_path(name, value, monkeypatch):
    """The switches by which a user chose a code path under `parallel/`
    and `plan/` went (three in PR 45, the chunk's byte target in PR 48):
    none is declared, reading one is the registry's KeyError, and
    setting it in the environment changes nothing the registry can see
    and no exchange's plan."""
    from cylon_tpu.parallel import shuffle
    from cylon_tpu.telemetry import knobs

    plans = [shuffle._chunk_plan(1 << 22, 4, 8, budget)
             for budget in (None, 1 << 28)]
    monkeypatch.setenv(name, value)
    assert name not in knobs.KNOBS
    with pytest.raises(KeyError, match="not a declared knob"):
        knobs.get(name)
    assert len(knobs.KNOBS) == 26
    assert [shuffle._chunk_plan(1 << 22, 4, 8, budget)
            for budget in (None, 1 << 28)] == plans \
        == [(1 << 22, 1), (1 << 21, 2)]


def test_envknobs_undocumented_knob(tmp_path):
    """A declared-but-undocumented knob is a finding anchored at its
    declare() line when the tree has a sibling docs/telemetry.md."""
    pkg = tmp_path / "pkg_knobs" / "telemetry"
    pkg.mkdir(parents=True)
    (tmp_path / "pkg_knobs" / "__init__.py").write_text("")
    (pkg / "__init__.py").write_text("")
    (pkg / "knobs.py").write_text(
        "def declare(name, default, kind, doc):\n"
        "    return name\n"
        "declare('CYLON_DOCUMENTED', 1, 'int', 'yes')\n"
        "declare('CYLON_GHOST', 1, 'int', 'no')\n")
    docs = tmp_path / "docs"
    docs.mkdir()
    (docs / "telemetry.md").write_text("only CYLON_DOCUMENTED here\n")
    res = run_checkers(AnalysisContext(str(tmp_path / "pkg_knobs")),
                       families=["envknobs"])
    assert [(f.path, f.line, f.rule) for f in res.findings] == \
        [("telemetry/knobs.py", 4, "envknobs/undocumented-knob")]
    assert "CYLON_GHOST" in res.findings[0].message


def test_new_families_in_fixture_cli_default():
    """`python -m cylon_tpu.analysis --package-root <fixture>` runs
    concurrency + envknobs by default and fails on the seeded races
    and rogue env reads."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, "-m", "cylon_tpu.analysis", "--package-root",
         PKG_BAD],
        capture_output=True, text=True, cwd=os.path.dirname(PKG_REAL),
        env=env, timeout=300)
    assert r.returncode == 1
    assert "[concurrency/unlocked-shared-write]" in r.stdout
    assert "[concurrency/blocking-under-lock]" in r.stdout
    assert "[concurrency/finalizer-hazard]" in r.stdout
    assert "[envknobs/unregistered-read]" in r.stdout
    assert "[envknobs/undeclared-knob]" in r.stdout


# ---------------------------------------------------------------------------
# specialization
# ---------------------------------------------------------------------------


def test_specialization_fixture_reports_exactly_seeded():
    """All three rules fire on the seeded factories: the raw runtime
    count AND the mantissa-rounded one are unbucketed-capacity, the
    opaque callee is unbounded-key, the non-factory closure is
    closure-capture — while the bucketed call site and the
    counted_cache factory's own key-derived closure stay clean."""
    res = run_checkers(AnalysisContext(PKG_BAD),
                       families=["specialization"])
    got = {(f.path, f.line, f.rule) for f in res.findings}
    assert got == {
        ("spec_bad.py", 59, "specialization/closure-capture"),
        ("spec_bad.py", 67, "specialization/unbucketed-capacity"),
        ("spec_bad.py", 68, "specialization/unbucketed-capacity"),
        ("spec_bad.py", 69, "specialization/unbounded-key"),
        # the chunked-exchange-shaped factory: the bucketed block +
        # pow2_floor chunk-block call stays clean, the raw runtime
        # chunk block is a finding
        ("spec_bad.py", 94, "specialization/unbucketed-capacity"),
        # the partition-path-shaped factory: bucketed block + literal
        # path string clean, the raw capacity key a finding
        ("spec_bad.py", 111, "specialization/unbucketed-capacity"),
        # the salted-exchange-shaped factory: the structural salt
        # literal stays clean, a raw runtime count as the salt key is
        # a finding
        ("spec_bad.py", 128, "specialization/unbucketed-capacity"),
        # the compaction-shaped factory (PR 50): its capacity may come
        # through the 16-an-octave mantissa rounding (lines 154-155 stay
        # clean), a raw count may not, and the same rounding into a
        # factory of another name is the finding it is at line 68
        ("spec_bad.py", 156, "specialization/unbucketed-capacity"),
        ("spec_bad.py", 157, "specialization/unbucketed-capacity"),
    }, res.format_text()
    # the reasoned per-line disable on the env-sourced cap counted
    assert res.suppressed == 1
    msgs = {f.line: f.message for f in res.findings}
    # findings carry the derivation chain / classification rationale
    assert "bucket_cap" in msgs[67]
    assert "mantissa" in msgs[68] and "mantissa" in msgs[157]
    assert "runtime" in msgs[156] and "mantissa" not in msgs[156]
    assert "derivation:" in msgs[69]
    assert "make_scaled" in msgs[59] and "'scale'" in msgs[59]


def test_specialization_real_tree_clean_zero_suppressions():
    """The real tree passes with ZERO suppressions: every capacity-
    keyed factory call site routes through a recognized bucketing
    helper, and no traced body closes over un-keyed state. The census
    note proves the audit actually covered the factory surface."""
    res = run_checkers(AnalysisContext(PKG_REAL),
                       families=["specialization"])
    assert res.findings == [], res.format_text()
    assert res.suppressed == 0
    census = [n for n in res.notes if "counted_cache factories" in n]
    assert census, res.notes
    # the factory surface is ~25 strong and every data-dependent key
    # is bucketed; a new unbucketed one becomes a finding, a shrinking
    # census means the auditor lost sight of factories
    assert "0 data-dependent" in census[0], census[0]
    assert "0 unbounded" in census[0], census[0]
    # the keys on util.capacity's grid: the compaction's (PR 50) and the
    # padded exchange's block (PR 52), 11 call-site arguments in all; the
    # policy's recorded exceptions, not suppressions
    assert "11 fine-bucketed-capacity" in census[0], census[0]
    assert specialization.FINE_KEYED_FACTORY_PARAMS == {
        ("_compact_program_fn", "cap"),
        ("_exchange_padded_fn", "block"),
        ("_exchange_padded_pair_fn", "block1"),
        ("_exchange_padded_pair_fn", "block2"),
        ("_exchange_chunk_first_fn", "block"),
        ("_exchange_chunk_first_fn", "chunk_block"),
        ("_exchange_chunk_fn", "block"),
        ("_exchange_chunk_fn", "chunk_block"),
        ("_exchange_partition_fn", "block"),
        ("_exchange_partition_fn", "chunk_block"),
        ("_starts_reconcile_fn", "row_block"),
        ("_starts_reconcile_fn", "word_block")}
    # ... and the compact rounds' block is not among them: it keeps its
    # power of two
    assert ("_exchange_fn", "block") \
        not in specialization.FINE_KEYED_FACTORY_PARAMS


def test_specialization_in_fixture_cli_default():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, "-m", "cylon_tpu.analysis", "--package-root",
         PKG_BAD],
        capture_output=True, text=True, cwd=os.path.dirname(PKG_REAL),
        env=env, timeout=300)
    assert r.returncode == 1
    assert "[specialization/unbucketed-capacity]" in r.stdout
    assert "[specialization/unbounded-key]" in r.stdout
    assert "[specialization/closure-capture]" in r.stdout


# ---------------------------------------------------------------------------
# shared ModuleIndex
# ---------------------------------------------------------------------------


def test_module_index_built_once_across_families():
    """One CLI invocation = one ModuleIndex build: hostsync,
    concurrency, envknobs and specialization all close over the same
    shared index (the walk+index is the dominant cost the check.sh
    wall-clock budget guards)."""
    ctx = AnalysisContext(PKG_BAD)
    run_checkers(ctx, families=["hostsync", "concurrency", "envknobs",
                                "specialization"])
    assert ctx.index_builds == 1
    # and a fresh context builds its own (no cross-run leakage)
    ctx2 = AnalysisContext(PKG_BAD)
    run_checkers(ctx2, families=["hostsync"])
    assert ctx2.index_builds == 1


# ---------------------------------------------------------------------------
# output schema + CLI
# ---------------------------------------------------------------------------


def test_json_schema_stable():
    res = run_checkers(AnalysisContext(PKG_BAD), families=["layering"])
    doc = json.loads(to_json_text(res))
    assert set(doc) == {"version", "ok", "checkers", "counts",
                        "suppressed", "notes", "findings"}
    assert doc["version"] == SCHEMA_VERSION == 1
    assert doc["ok"] is False
    assert doc["checkers"] == ["layering"]
    assert doc["counts"] == {"layering": 12}
    assert doc["suppressed"] == 1
    for f in doc["findings"]:
        assert set(f) == {"rule", "path", "line", "col", "message"}
        assert isinstance(f["line"], int)
    # deterministic ordering: sorted by (path, line, rule)
    keys = [(f["path"], f["line"], f["rule"]) for f in doc["findings"]]
    assert keys == sorted(keys)


def test_sarif_envelope_stable():
    """SARIF v2.1.0 envelope pin: one run, driver "cylint", one rule
    entry per distinct rule id, one result per finding with a physical
    location CI annotators can anchor inline comments to."""
    from cylon_tpu.analysis import to_sarif

    res = run_checkers(AnalysisContext(PKG_BAD), families=["layering"])
    doc = to_sarif(res)
    assert set(doc) == {"$schema", "version", "runs"}
    assert doc["version"] == "2.1.0"
    assert "sarif-schema-2.1.0" in doc["$schema"]
    assert len(doc["runs"]) == 1
    run = doc["runs"][0]
    assert set(run) == {"tool", "invocations", "properties", "results"}
    drv = run["tool"]["driver"]
    assert drv["name"] == "cylint"
    rule_ids = [r["id"] for r in drv["rules"]]
    assert rule_ids == sorted(set(rule_ids))  # one entry per rule, sorted
    assert set(rule_ids) == {f.rule for f in res.findings}
    assert run["invocations"] == [{"executionSuccessful": False}]
    assert run["properties"]["suppressed"] == res.suppressed
    assert len(run["results"]) == len(res.findings)
    for r, f in zip(run["results"], res.findings):
        assert r["ruleId"] == f.rule
        assert rule_ids[r["ruleIndex"]] == f.rule
        assert r["level"] == "error"
        assert r["message"]["text"] == f.message
        loc = r["locations"][0]["physicalLocation"]
        assert loc["artifactLocation"]["uri"] == f.path
        assert loc["region"]["startLine"] == f.line
        assert loc["region"]["startColumn"] >= 1  # SARIF is 1-based


def test_cli_format_sarif():
    """--format sarif parses, carries the findings, and keeps the
    exit-code contract; a clean family run is executionSuccessful."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    repo = os.path.dirname(PKG_REAL)
    bad = subprocess.run(
        [sys.executable, "-m", "cylon_tpu.analysis", "--package-root",
         PKG_BAD, "--families", "layering", "--format", "sarif"],
        capture_output=True, text=True, cwd=repo, env=env, timeout=300)
    assert bad.returncode == 1
    doc = json.loads(bad.stdout)
    assert doc["version"] == "2.1.0"
    assert doc["runs"][0]["results"], "findings must surface in SARIF"
    assert doc["runs"][0]["invocations"][0]["executionSuccessful"] is False
    ok = subprocess.run(
        [sys.executable, "-m", "cylon_tpu.analysis", "--families",
         "layering", "--format", "sarif"],
        capture_output=True, text=True, cwd=repo, env=env, timeout=300)
    assert ok.returncode == 0, ok.stdout + ok.stderr
    doc = json.loads(ok.stdout)
    assert doc["runs"][0]["results"] == []
    assert doc["runs"][0]["invocations"][0]["executionSuccessful"] is True


def test_cli_exit_codes():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    repo = os.path.dirname(PKG_REAL)
    ok = subprocess.run(
        [sys.executable, "-m", "cylon_tpu.analysis", "--families",
         "layering,hostsync"],
        capture_output=True, text=True, cwd=repo, env=env, timeout=300)
    assert ok.returncode == 0, ok.stdout + ok.stderr
    bad = subprocess.run(
        [sys.executable, "-m", "cylon_tpu.analysis", "--package-root",
         PKG_BAD],
        capture_output=True, text=True, cwd=repo, env=env, timeout=300)
    assert bad.returncode == 1, bad.stdout + bad.stderr
    assert "[layering/plan-no-ops]" in bad.stdout


def test_unknown_family_is_an_error():
    """A typo in --families must not become an exit-0 gate that ran
    nothing."""
    with pytest.raises(ValueError, match="layring"):
        run_checkers(AnalysisContext(PKG_BAD), families=["layring"])
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, "-m", "cylon_tpu.analysis", "--families",
         "layring"],
        capture_output=True, text=True, cwd=os.path.dirname(PKG_REAL),
        env=env, timeout=300)
    assert r.returncode == 2
    assert "unknown checker families" in r.stderr


def test_suppression_file_level(tmp_path):
    pkg = tmp_path / "pkg_sup"
    (pkg / "plan").mkdir(parents=True)
    (pkg / "__init__.py").write_text("")
    (pkg / "plan" / "__init__.py").write_text("")
    (pkg / "plan" / "x.py").write_text(
        "# cylint: disable-file=layering/plan-no-ops\n"
        "from ..ops import join\n")
    res = run_checkers(AnalysisContext(str(pkg)), families=["layering"])
    assert res.findings == []
    assert res.suppressed == 1
