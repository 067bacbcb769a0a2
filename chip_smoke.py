#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the main path still runs on the
chip.

The main path is a query handed to ``LazyTable.execute()`` or to
``QueryService.submit()`` until its result is materialised:

    plan.scan(left).join(plan.scan(right), on="k")
        .groupby("lt-0", ["rt-4"], ["sum"])

with ``left`` = (k int32 in [0, n/4), v f32, z int32), ``right`` =
(k int32, w f32), n = 16M rows a side per chip, data from ``--seed``.

    python chip_smoke.py              one chip: library phase + served phase
    python chip_smoke.py --chips 4    four chips: the planned pipeline over
                                      the real mesh, and nothing else
    python chip_smoke.py --rows 65536 rehearsal size; off a TPU every
                                      phase runs, then the run fails

ONE process, no child, no JAX_PLATFORMS forced. Every phase compares with
a numpy reference written here, independent of cylon_tpu. Anything that
raises, mismatches or finds a platform other than ``tpu`` ends the run
non-zero: at once without ``--rows``, and with it after every phase has run
(a rehearsal of control flow, only the device verdict fails). The last line
of a passing run is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
"""
import argparse
import gc
import importlib.metadata
import json
import threading
import time

import numpy as np

ROWS_PER_CHIP = 1 << 24
TENANTS = ("tenant-a", "tenant-b")
SERVED_QUERIES = 4

# jax.monitoring event names (jax/_src/dispatch.py, compiler.py)
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class Compiles:
    """Every backend compile of this process, from jax.monitoring:
    how many, their seconds (a persistent-cache hit counts, at the
    seconds the load took) and how many were such hits."""

    def __init__(self):
        import jax.monitoring as mon

        self.n, self.secs, self.hits = 0, 0.0, 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **_kw):
        if event == _COMPILE_EVENT:
            self.n += 1
            self.secs += secs

    def _on_event(self, event, **_kw):
        if event == _CACHE_HIT_EVENT:
            self.hits += 1

    def mark(self):
        return (self.n, self.secs, self.hits)

    def since(self, mark):
        return (self.n - mark[0], self.secs - mark[1], self.hits - mark[2])


def check(ok, what):
    """A failed check ends the run: no error is recorded and carried on."""
    if not ok:
        raise SystemExit(f"chip_smoke: FAIL — {what}")
    print(f"  ok: {what}", flush=True)


def make_data(n, seed):
    r = np.random.default_rng(seed)
    left = {"k": r.integers(0, n // 4, n).astype(np.int32),
            "v": r.normal(size=n).astype(np.float32),
            "z": r.integers(0, 50, n).astype(np.int32)}
    right = {"k": r.integers(0, n // 4, n).astype(np.int32),
             "w": r.normal(size=n).astype(np.float32)}
    return left, right


def reference(left, right):
    """join(on k) -> groupby(k).sum(w) without cylon_tpu: per key,
    count_left(k) * sum of right.w(k), in float64. Returns (keys, sums,
    scale, join_rows); scale = count_left * sum|w| bounds the float32
    summation error of a sum that cancels."""
    nkeys = int(max(left["k"].max(), right["k"].max())) + 1
    cl = np.bincount(left["k"], minlength=nkeys)
    cr = np.bincount(right["k"], minlength=nkeys)
    w = right["w"].astype(np.float64)
    sw = np.bincount(right["k"], weights=w, minlength=nkeys)
    saw = np.bincount(right["k"], weights=np.abs(w), minlength=nkeys)
    both = (cl > 0) & (cr > 0)
    return (np.flatnonzero(both), (cl * sw)[both], (cl * saw)[both],
            int((cl.astype(np.int64) * cr).sum()))


def result_arrays(table):
    """(keys, sums) of a result table, ordered by key."""
    d = table.to_pydict()
    keys, sums = np.asarray(d["lt-0"]), np.asarray(d["rt-2"])
    order = np.argsort(keys, kind="stable")
    return keys[order], sums[order]


def check_against_reference(got, ref, what):
    keys, sums = got
    rkeys, rsums, rscale, _ = ref
    check(keys.shape == rkeys.shape and np.array_equal(keys, rkeys),
          f"{what}: {keys.shape[0]} groups, keys equal the reference's "
          f"{rkeys.shape[0]} exactly")
    check(bool(np.isfinite(sums).all()), f"{what}: sums finite")
    err = np.abs(sums.astype(np.float64) - rsums)
    tol = 1e-3 * np.abs(rsums) + 1e-5 * rscale
    worst = float((err / np.maximum(tol, 1e-300)).max())
    check(worst <= 1.0, f"{what}: sums within rtol 1e-3 of the float64 "
          f"reference (worst error/tolerance {worst:.3g})")


def join_rows_of(report):
    """The Join node's measured output rows in a PlanReport."""
    def walk(node):
        if node["kind"] == "join":
            return node
        for c in node["children"]:
            found = walk(c)
            if found is not None:
                return found
        return None

    return walk(report.to_dict()["plan"])


def timed_execute(pipe, compiles, label):
    import jax

    m = compiles.mark()
    t0 = time.perf_counter()
    out = pipe.execute()
    jax.block_until_ready([c.data for c in out.columns()])
    secs = time.perf_counter() - t0
    n, csecs, hits = compiles.since(m)
    print(f"  {label}: {secs:.3f} s wall, of it {csecs:.3f} s in {n} "
          f"compile(s) ({hits} from the persistent cache)", flush=True)
    return out


def factory_builds(name=None):
    from cylon_tpu import telemetry

    prefix = "cylon_kernel_factory_builds_total"
    if name is not None:
        prefix += f'{{factory="{name}"}}'
    return sum(v for k, v in telemetry.metrics_snapshot().items()
               if k.startswith(prefix) and isinstance(v, int))


def counter(name):
    from cylon_tpu import telemetry

    return telemetry.metrics_snapshot().get(name, 0)


def build_pipe(left, right):
    from cylon_tpu import plan

    return plan.scan(left).join(plan.scan(right), on="k") \
        .groupby("lt-0", ["rt-4"], ["sum"])


# ---------------------------------------------------------------------------
# one chip: library phase + served phase
# ---------------------------------------------------------------------------

def one_chip(ctx, ct, n, seed, on_tpu, compiles):
    from cylon_tpu.ops import join as _join
    from cylon_tpu.service import QueryService
    from cylon_tpu.telemetry import ledger

    ldata, rdata = make_data(n, seed)
    t0 = time.perf_counter()
    ref = reference(ldata, rdata)
    print(f"reference: {ref[0].shape[0]} groups, {ref[3]} joined rows "
          f"({time.perf_counter() - t0:.1f} s numpy)", flush=True)
    left = ct.Table.from_pydict(ctx, ldata)
    right = ct.Table.from_pydict(ctx, rdata)

    print(f"== library phase: LazyTable.execute(), {n} rows a side",
          flush=True)
    pipe = build_pipe(left, right)
    out = timed_execute(pipe, compiles, "cold")
    lib = result_arrays(out)
    check_against_reference(lib, ref, "library cold")
    for i in (1, 2):
        m = compiles.mark()
        out = timed_execute(pipe, compiles, f"warm {i}")
    check(compiles.since(m)[0] == 0, "warm 2 compiled nothing")
    warm = result_arrays(out)
    check(np.array_equal(warm[0], lib[0]) and np.array_equal(warm[1], lib[1]),
          "warm result bit-equal to the cold one")

    # the kernels really ran compiled. On one chip the planner lowers the
    # join to the LOCAL twin (data/table._join_once), which has no counted
    # factory: its programs are module-level jits, and jit's own cache says
    # which of them were built. plan_program_stream only calls its jit when
    # interpret=False, so an entry there IS the compiled Pallas route.
    stream = (_join._plan_program_stream_jit._cache_size(),
              _join._materialize_program_stream_jit._cache_size())
    xla = (_join.plan_program._cache_size(),
           _join.materialize_program._cache_size())
    print(f"  join route: stream (Pallas, compiled) plan/materialize "
          f"programs built {stream}, XLA twin {xla}; dist factories "
          f"_join_plan_stream_fn={factory_builds('_join_plan_stream_fn')} "
          f"_join_mat_fn={factory_builds('_join_mat_fn')}", flush=True)
    if on_tpu:
        check(min(stream) >= 1 and xla == (0, 0)
              and factory_builds("_join_mat_fn") == 0
              and factory_builds("_join_plan_fn") == 0,
              "the join took the compiled Pallas stream route, the XLA "
              "plan was never built")
    else:
        print("  (not a TPU: the join takes the XLA plan here — part of "
              "the device verdict)", flush=True)

    out = pipe.execute(analyze=True)
    rep = pipe.last_report
    print(rep.render(), flush=True)
    jn = join_rows_of(rep)
    check(jn is not None and jn["rows"] == ref[3],
          f"EXPLAIN ANALYZE join rows {jn and jn['rows']} == reference "
          f"sum(count_left*count_right) {ref[3]}")
    check(rep.leaks == [], "analyzed run reports no leaked intermediate")
    del out, rep

    print(f"== served phase: QueryService.submit() x{SERVED_QUERIES}, "
          f"{len(TENANTS)} tenants", flush=True)
    threads_before = set(threading.enumerate())
    hits0 = counter("cylon_plan_cache_hits_total")
    svc = QueryService()
    m = compiles.mark()
    t0 = time.perf_counter()
    first = svc.submit(build_pipe(left, right), tenant=TENANTS[0])
    first.result(timeout=900)
    first_s = time.perf_counter() - t0
    builds_after_first = factory_builds()
    compiles_after_first = compiles.mark()
    tickets = [first]
    t0 = time.perf_counter()
    for i in range(1, SERVED_QUERIES):
        tickets.append(svc.submit(build_pipe(left, right),
                                  tenant=TENANTS[i % len(TENANTS)]))
    svc.drain(timeout=900)
    rest_s = time.perf_counter() - t0
    print(f"  first served query {first_s:.3f} s ({compiles.since(m)[0]} "
          f"compile(s) so far), the other {SERVED_QUERIES - 1} together "
          f"{rest_s:.3f} s", flush=True)
    for tk in tickets:
        check(tk.outcome == "ok", f"ticket {tk.query_id} ({tk.tenant}) "
              f"outcome {tk.outcome!r}")
        got = result_arrays(tk.result(timeout=60))
        check(np.array_equal(got[0], lib[0])
              and np.array_equal(got[1], lib[1]),
              f"ticket {tk.query_id} result bit-equal to the library "
              f"phase's")
    svc.close()
    hits = counter("cylon_plan_cache_hits_total") - hits0
    check(hits >= SERVED_QUERIES - 1, f"plan cache hits {hits} >= "
          f"{SERVED_QUERIES - 1}")
    check(factory_builds() == builds_after_first
          and compiles.since(compiles_after_first)[0] == 0,
          "no kernel factory build and no compile after the first served "
          "query")
    stray = [t.name for t in set(threading.enumerate()) - threads_before
             if t.is_alive()]
    check(not stray, f"svc.close() left no thread ({stray})")

    del tickets, first, tk, got, pipe, svc
    gc.collect()
    check(ledger.leak_count() == 0, "ledger.leak_count() == 0")


# ---------------------------------------------------------------------------
# four chips: the planned pipeline over the real mesh
# ---------------------------------------------------------------------------

def shard_report(table, what, world):
    """Every column: `world` addressable shards on `world` distinct
    devices, live rows on each."""
    mask = table.row_mask
    for c in table.columns():
        shards = c.data.addressable_shards
        devs = {s.device for s in shards}
        check(len(shards) == world and len(devs) == world,
              f"{what}.{c.name}: {len(shards)} shards on {len(devs)} "
              f"distinct devices")
    if mask is None:
        live = [int(s.data.shape[0])
                for s in table.columns()[0].data.addressable_shards]
    else:
        live = [int(np.asarray(s.data).sum())
                for s in mask.addressable_shards]
    check(len(live) == world and min(live) > 0,
          f"{what}: live rows a shard {live}")


def four_chips(ctx, ct, n, seed, on_tpu, compiles, world):
    from cylon_tpu import telemetry
    from cylon_tpu.parallel import shard
    from cylon_tpu.telemetry import ledger

    ldata, rdata = make_data(n, seed)
    ref = reference(ldata, rdata)
    print(f"reference: {ref[0].shape[0]} groups, {ref[3]} joined rows",
          flush=True)
    staged_l = ct.Table.from_pydict(ctx, ldata)
    staged_r = ct.Table.from_pydict(ctx, rdata)
    staged = staged_l.nbytes + staged_r.nbytes
    print(f"staged on {staged_l.columns()[0].data.sharding}: "
          f"{staged / 2**30:.3f} GiB before shard.distribute", flush=True)
    left = shard.distribute(staged_l, ctx)
    right = shard.distribute(staged_r, ctx)
    del staged_l, staged_r
    shard_report(left, "left", world)
    shard_report(right, "right", world)

    print(f"== planned pipeline on {world} chips, {n} rows a side "
          f"({n // world} a chip)", flush=True)
    pipe = build_pipe(left, right)
    bytes0 = counter("cylon_shuffle_bytes_total")
    with telemetry.collect_phases() as cp:
        out = timed_execute(pipe, compiles, "cold")
    moved = counter("cylon_shuffle_bytes_total") - bytes0
    check_against_reference(result_arrays(out), ref, "planned cold")
    shard_report(out, "result", world)
    stages = cp.count("plan.shuffle")
    spans = [lab for lab in cp.labels if lab.startswith("shuffle.exchange")]
    # one STAGE moves both join sides: as one pair program, or — where
    # the comm budget refuses a side's stacks — as one exchange a side
    check(stages == 1 and 1 <= len(spans) <= 2,
          f"ONE exchange stage in the planned run (plan.shuffle x{stages}, "
          f"spans {spans})")
    # the optimizer prunes left.v and left.z before the exchange:
    # what moves is left.k + right.k + right.w
    pruned = n * 4 + n * 8
    check(0.9 * pruned <= moved <= 1.1 * pruned,
          f"cylon_shuffle_bytes_total moved {moved} B, the pruned columns "
          f"are {pruned} B")
    for i in (1, 2):
        m = compiles.mark()
        out = timed_execute(pipe, compiles, f"warm {i}")
    check(compiles.since(m)[0] == 0, "warm 2 compiled nothing")

    out = pipe.execute(analyze=True)
    rep = pipe.last_report
    print(rep.render(), flush=True)
    jn = join_rows_of(rep)
    check(jn["rows"] == ref[3], f"EXPLAIN ANALYZE join rows {jn['rows']} "
          f"== reference {ref[3]}")
    check(rep.shuffle_count == 1, "PlanReport.shuffle_count == 1")
    print(f"  exchange: part={jn['partition_path']}, chunk programs "
          f"_exchange_chunk_first_fn="
          f"{factory_builds('_exchange_chunk_first_fn')} "
          f"_exchange_chunk_fn={factory_builds('_exchange_chunk_fn')} "
          f"pair program={factory_builds('_exchange_padded_pair_fn')}; "
          f"join factories _join_plan_stream_fn="
          f"{factory_builds('_join_plan_stream_fn')} _join_mat_stream_fn="
          f"{factory_builds('_join_mat_stream_fn')} _join_mat_fn="
          f"{factory_builds('_join_mat_fn')}", flush=True)
    if on_tpu:
        check(jn["partition_path"] == "pallas",
              "the exchange reports part=pallas")
        check(factory_builds("_join_plan_stream_fn") >= 1
              and factory_builds("_join_mat_stream_fn") >= 1
              and factory_builds("_join_mat_fn") == 0,
              "the per-shard join took the compiled Pallas stream route")
    check(rep.leaks == [], "analyzed run reports no leaked intermediate")
    del out, rep, pipe
    gc.collect()
    check(ledger.leak_count() == 0, "ledger.leak_count() == 0")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--rows", type=int, default=None,
                    help="rows a side PER CHIP (default 16M; off a TPU "
                         "there is no default: name a rehearsal size)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    t_start = time.perf_counter()

    import jax

    devs = jax.devices()
    platform, kind = devs[0].platform, devs[0].device_kind
    on_tpu = platform == "tpu"
    compiles = Compiles()

    import cylon_tpu as ct
    from cylon_tpu import context as _context

    check(len(devs) >= args.chips,
          f"{len(devs)} device(s) found, {args.chips} needed")
    ctx = ct.CylonContext.InitDistributed(
        ct.TPUConfig(world_size=args.chips))
    stats = devs[0].memory_stats()
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "not installed"
    print(f"device: platform={platform} kind={kind!r} count={len(devs)} "
          f"jax={jax.__version__} libtpu={libtpu} "
          f"x64={jax.config.jax_enable_x64} "
          f"compile_cache={jax.config.jax_compilation_cache_dir} "
          f"(fixed default {_context.COMPILE_CACHE_DIR}) "
          f"memory_stats.bytes_limit="
          f"{stats.get('bytes_limit') if stats else stats} "
          f"pool.bytes_limit={ctx.memory_pool.bytes_limit()} "
          f"comm_budget_bytes={ctx.memory_pool.comm_budget_bytes()}",
          flush=True)
    if not on_tpu:
        if args.rows is None:
            raise SystemExit(
                f"chip_smoke: FAIL — platform is {platform!r}, not 'tpu' "
                f"(to rehearse the control flow here, pass --rows 65536)")
        print(f"NOT A TPU ({platform}): rehearsal only, this run will "
              f"exit non-zero", flush=True)

    n = (args.rows or ROWS_PER_CHIP) * args.chips
    if args.chips == 1:
        one_chip(ctx, ct, n, args.seed, on_tpu, compiles)
    else:
        four_chips(ctx, ct, n, args.seed, on_tpu, compiles, args.chips)

    tot_n, tot_s, tot_hits = compiles.mark()
    print(f"total: {time.perf_counter() - t_start:.1f} s wall, "
          f"{tot_s:.1f} s in {tot_n} compiles ({tot_hits} from the "
          f"persistent cache)", flush=True)
    if not on_tpu:
        raise SystemExit(f"chip_smoke: FAIL — platform is {platform!r}, "
                         f"not 'tpu': every phase ran, no device result")
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": len(devs)}}))


if __name__ == "__main__":
    main()
