#!/usr/bin/env python3
"""benchmarks/run.py — one run of one cell of BENCHMARK.json.

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

ONE process, no child, no JAX_PLATFORMS forced. A platform other than
``tpu``, fewer devices than the cell's ``chips`` or a ``device_kind`` that
``peaks.json`` does not hold ends the run non-zero with no result line.

Set-up (``setup_s``): imports, the context, data from ``--seed``, tables on
the device, the first query (``first_query_s``) and one more untimed one.
The plain reference and every comparison with it run on the host and are
timed apart (``check_s``), not as set-up.

Window: a closed loop of one client through ``LazyTable.execute()``; each
query is timed from the call to ``block_until_ready`` on every buffer of
its result; the window closes at the first completion at or after
``--seconds``. EVERY query gets inputs of its own: between two queries,
untimed, the placed tables are copied on the device into new buffers and
the ``LazyTable`` is built anew over the copies, as for a user whose tables
are new each time, so that nothing the program remembers by the identity
of a buffer (its count memo, ``shuffle._count_cached``) can answer for it. ``--trace 1`` wraps ``jax.profiler`` around a few queries in
the middle of the same loop and prints the per-layer metrics instead of the
end-to-end ones.

Nothing here names a cell, a configuration or a metric: each is found by
the name that BENCHMARK.json (or the file that asks for it) gives —
``configs/<config>.json``, ``traffic/<traffic>.json``,
``metrics/<metric>.json``, and the code those files name under
``generators/``, ``references/``, ``queries/`` and ``reducers/``.

Two arguments the driver never passes: ``--scale <f>`` shrinks the rows (a
rehearsal: off a TPU every phase runs, then the run ends non-zero), and
``--control 1`` puts the reference computed in the next lower precision in
the program's place, which has to come out ``correct: false``.
"""
import argparse
import importlib.util
import json
import os
import re
import sys
import time

T_START = time.perf_counter()

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)   # loop, xplane
sys.path.insert(1, ROOT)   # the system under test, cylon_tpu

_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")

# jax.monitoring event names (jax/_src/dispatch.py, compiler.py)
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class NotMeasurable(SystemExit):
    """The run cannot give a result: printed to stderr, exit code 4."""

    def __init__(self, why):
        print(f"benchmarks/run.py: {why}", file=sys.stderr, flush=True)
        super().__init__(4)


def say(msg):
    """Every line but the last carries the seconds since process start."""
    print(f"[{time.perf_counter() - T_START:8.3f}] {msg}", flush=True)


def named_file(kind, name, ext):
    """benchmarks/<kind>/<name><ext>, for a name that is only a name."""
    if not _NAME.match(name):
        raise NotMeasurable(f"bad {kind} name {name!r}")
    path = os.path.join(HERE, kind, name + ext)
    if not os.path.isfile(path):
        raise NotMeasurable(f"no file {os.path.relpath(path, ROOT)}")
    return path


def load_json(kind, name):
    with open(named_file(kind, name, ".json")) as f:
        return json.load(f)


def load_code(kind, name):
    path = named_file(kind, name, ".py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name}".replace("-", "_").replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find_cell(bench, name):
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise NotMeasurable(f"BENCHMARK.json has no workload {name!r}")


def metrics_of(bench, group, cell):
    """The metrics of ``group`` that this cell reports."""
    return [m for m in bench[group]
            if "workloads" not in m or cell in m["workloads"]]


class Compiles:
    """Every backend compile of this process, from jax.monitoring: how
    many, their seconds (a persistent-cache hit counts, at the seconds the
    load took) and how many were such hits. Copied from chip_smoke.py."""

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


def use_compile_cache(jax):
    """Every program of the cell, however small, comes from the cache on
    the second run. The directory is fixed (it is part of the key) and
    inside the checkout, or where JAX_COMPILATION_CACHE_DIR says; the
    program's own helper (context._use_compile_cache) finds it set and
    sets nothing."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def host_result(table):
    """The live rows of a result table as host numpy, one array a column
    in the table's column order, plus how many live cells were null. The
    mask is applied here, on the host: no program of the benchmark runs
    on the device."""
    import numpy as np

    mask = None if table.row_mask is None else np.asarray(table.row_mask)
    cols, nulls = [], 0
    for c in table.columns():
        data = np.asarray(c.data)
        if mask is not None:
            data = data[mask]
        if c.validity is not None:
            valid = np.asarray(c.validity)
            if mask is not None:
                valid = valid[mask]
            nulls += int((~valid).sum())
        cols.append(data)
    return {"names": list(table.column_names), "columns": cols,
            "nulls": nulls}


def fresh_copy(ct, jnp, table):
    """``table`` in device buffers of its own: the same rows, sharding and
    schema, and no buffer that any earlier query has seen."""
    def copy(a):
        return None if a is None else jnp.copy(a)

    if any(c.is_varbytes for c in table.columns()):
        raise NotMeasurable("fresh_copy: no string columns yet")
    cols = [ct.Column(copy(c.data), c.dtype, copy(c.validity), c.dictionary,
                      c.name) for c in table.columns()]
    return ct.Table(cols, table.context, copy(table.row_mask))


def judge(what, numbers):
    """Print each number compared beside its limit; True if all hold."""
    ok = True
    for n in numbers:
        good = n["value"] <= n["limit"]
        ok = ok and good
        say(f"  compare {what}: {n['name']} = {n['value']!r} "
            f"(limit {n['limit']!r}) {'ok' if good else 'MISMATCH'}")
    return ok


def profiler_options(jax):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    opts.enable_hlo_proto = False
    return opts


def settle_host_wakeups(jax, scratch_dir):
    """Start and stop the profiler once, in set-up, in EVERY run. A process
    on the chip's machine wakes from a wait for the device in one of two
    modes, some 4 ms a wait apart, and keeps its mode; the first start of
    the profiler puts it in the fast one for good (PERF.md section 2). Runs
    are comparable only within one mode, so every run is put there."""
    import shutil

    jax.profiler.start_trace(scratch_dir,
                             profiler_options=profiler_options(jax))
    jax.profiler.stop_trace()
    shutil.rmtree(scratch_dir, ignore_errors=True)


class Tracer:
    """jax.profiler around ``n`` queries, started by the loop's between-
    queries hook once ``after_s`` of the window have passed."""

    def __init__(self, jax, out_dir, n, after_s):
        self.jax, self.dir, self.n, self.after_s = jax, out_dir, n, after_s
        self.state = "waiting"   # -> tracing -> done
        self.first = None        # index of the first traced query
        self.marks = {}
        self.seconds = 0.0       # spent starting and stopping the profiler

    def between(self, i, elapsed, snapshot):
        if self.state == "waiting" and elapsed >= self.after_s:
            self.marks["counters0"] = snapshot()
            t0 = time.perf_counter()
            self.jax.profiler.start_trace(
                self.dir, profiler_options=profiler_options(self.jax))
            self.seconds += time.perf_counter() - t0
            self.state, self.first = "tracing", i
        elif self.state == "tracing" and i - self.first >= self.n:
            self._stop(snapshot)

    def _stop(self, snapshot):
        t0 = time.perf_counter()
        self.jax.profiler.stop_trace()
        self.seconds += time.perf_counter() - t0
        self.marks["counters1"] = snapshot()
        self.state = "done"

    def finish(self, snapshot, n_queries):
        if self.state == "tracing":
            self._stop(snapshot)
        if self.state != "done":
            return 0
        return min(self.n, n_queries - self.first)

    def file(self):
        for base, _dirs, files in os.walk(self.dir):
            for f in files:
                if f.endswith(".xplane.pb"):
                    return os.path.join(base, f)
        return None


def run(args):
    """One run. Returns (result dict, exit code)."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = find_cell(bench, args.workload)
    chips = int(cell["chips"])
    config = load_json("configs", cell["config"])
    traffic = load_json("traffic", cell["traffic"])
    with open(os.path.join(HERE, "peaks.json")) as f:
        peaks_table = json.load(f)
    rehearsal = args.scale != 1.0

    import numpy as np
    import jax
    import jax.numpy as jnp

    compiles = Compiles()
    devs = jax.devices()
    platform, kind = devs[0].platform, devs[0].device_kind
    on_tpu = platform == "tpu"
    if on_tpu:   # a rehearsal on the CPU writes no cache into the tree
        use_compile_cache(jax)
    say(f"device: platform={platform} kind={kind!r} count={len(devs)} "
        f"jax={jax.__version__} x64={jax.config.jax_enable_x64} "
        f"compile_cache={jax.config.jax_compilation_cache_dir}")
    if len(devs) < chips:
        raise NotMeasurable(f"{len(devs)} device(s) found, the cell "
                            f"{cell['name']!r} needs {chips}")
    if not on_tpu and not rehearsal:
        raise NotMeasurable(f"platform is {platform!r}, not 'tpu' (to "
                            f"rehearse the control flow pass --scale)")
    peaks = peaks_table.get(kind)
    if peaks is None and on_tpu:
        raise NotMeasurable(f"peaks.json has no device kind {kind!r}")

    import cylon_tpu as ct
    from cylon_tpu import plan, telemetry
    from cylon_tpu.parallel import shard

    generator = load_code("generators", config["generator"])
    reference = load_code("references", config["reference"])
    query = load_code("queries", traffic["query"])

    ctx = ct.CylonContext.InitDistributed(ct.TPUConfig(world_size=chips))
    check_s = 0.0

    # -- data from the seed, the reference's answer, tables on the device
    t0 = time.perf_counter()
    data = generator.generate(config, traffic, chips, args.scale, args.seed)
    gen_s = time.perf_counter() - t0
    input_rows = sum(len(next(iter(t.values())))
                     for t in data["tables"].values())
    input_bytes = sum(a.nbytes for t in data["tables"].values()
                      for a in t.values())
    say(f"data: {input_rows} input rows, {input_bytes} bytes in "
        f"{gen_s:.3f} s ({config['generator']}, seed {args.seed})")

    t0 = time.perf_counter()
    ref = reference.reference(data["tables"], config, traffic)
    control = reference.control(data["tables"], config, traffic) \
        if args.control else None
    check_s += time.perf_counter() - t0
    say(f"reference: {reference.describe(ref)} ({check_s:.3f} s on the "
        f"host, not set-up)")

    t0 = time.perf_counter()
    tables = {}
    for name, cols in data["tables"].items():
        t = ct.Table.from_pydict(ctx, cols)
        tables[name] = shard.distribute(t, ctx) if chips > 1 else t
    jax.block_until_ready([b for t in tables.values()
                           for b in t.buffers()])
    del data
    say(f"placed: {len(tables)} table(s) on {chips} chip(s) in "
        f"{time.perf_counter() - t0:.3f} s")
    live = {}   # the query about to run, over input buffers of its own

    def fresh_inputs():
        """Untimed: drop the last query's inputs, copy the placed tables
        into new buffers, wait for the copies, build the query on them."""
        live.clear()
        copies = {name: fresh_copy(ct, jnp, t) for name, t in tables.items()}
        jax.block_until_ready([b for t in copies.values()
                               for b in t.buffers()])
        live["pipe"] = query.build(plan, copies, traffic)

    def one_query():
        out = live["pipe"].execute()
        jax.block_until_ready(out.buffers())   # every buffer of the result
        return out

    fresh_inputs()
    say("plan:\n" + live["pipe"].explain())

    def compared(out, what):
        """Full comparison of one result with the reference (check_s)."""
        nonlocal check_s
        t0 = time.perf_counter()
        got = control if control is not None else host_result(out)
        ok = judge(what, reference.compare(got, ref))
        check_s += time.perf_counter() - t0
        return ok, got

    # -- the first query of the process, then one more, untimed
    m = compiles.mark()
    t0 = time.perf_counter()
    out = one_query()
    first_query_s = time.perf_counter() - t0
    n, secs, hits = compiles.since(m)
    say(f"first query: {first_query_s:.3f} s, of it {secs:.3f} s in {n} "
        f"compile(s) ({hits} from the persistent cache)")
    t0 = time.perf_counter()
    rows_first = int(out.row_count)
    count_s = time.perf_counter() - t0
    first_ok, got = compared(out, "first query")
    result_bytes = sum(a.nbytes for a in got["columns"])
    del got
    expected_rows = reference.rows_out(ref)
    t0 = time.perf_counter()
    settle_host_wakeups(jax, os.path.join(ROOT, ".bench_trace",
                                          cell["name"] + ".settle"))
    say(f"profiler started and stopped once in "
        f"{time.perf_counter() - t0:.3f} s")
    fresh_inputs()
    m = compiles.mark()
    t0 = time.perf_counter()
    out = one_query()
    second_s = time.perf_counter() - t0
    del out
    say(f"second query: {second_s:.3f} s, {compiles.since(m)[0]} "
        f"compile(s); row count of the first {rows_first} "
        f"({count_s:.3f} s)")

    def snapshot():
        return {k: v for k, v in telemetry.metrics_snapshot().items()
                if isinstance(v, (int, float))}

    trace_dir = os.path.join(ROOT, ".bench_trace", cell["name"])
    tracer = None
    if args.trace:
        import shutil

        shutil.rmtree(trace_dir, ignore_errors=True)
        os.makedirs(trace_dir, exist_ok=True)
        tracer = Tracer(jax, trace_dir, int(traffic.get("traced_queries", 3)),
                        0.4 * args.seconds)

    # the query whose whole result is kept for the comparison after the
    # window, besides the last: the first to start at or after a point of
    # the window drawn from the seed
    sample_at = float(np.random.default_rng(args.seed).random()) \
        * args.seconds * 0.8
    kept = {}

    from loop import closed_loop

    def timed(i):
        with jax.profiler.TraceAnnotation("bench:query"):
            return one_query()

    def between(i, elapsed):
        fresh_inputs()
        if tracer is not None:
            tracer.between(i, elapsed, snapshot)

    def accept(i, started, out):
        rows = int(out.row_count)
        if "sample" not in kept and started >= sample_at:
            kept["sample"] = (i, out)
        kept["last"] = (i, out)
        return rows == expected_rows

    setup_s = time.perf_counter() - T_START - check_s
    m = compiles.mark()
    records, window_s = closed_loop(timed, args.seconds, accept=accept,
                                    between=between)
    compiles_in_window = compiles.since(m)[0]
    traced_queries = tracer.finish(snapshot, len(records)) if tracer else 0
    for r in records:
        if r.error:
            say(f"query {r.index} raised:\n{r.error}")
    failed = sum(not r.ok for r in records)
    done = sum(r.ok for r in records)
    say(f"window: {len(records)} queries in {window_s:.3f} s, "
        f"{failed} failed, {compiles_in_window} compile(s); "
        f"{input_rows * done / window_s:.1f} input rows/s over the whole "
        f"window; query seconds "
        + " ".join(f"{r.seconds:.4f}" for r in records))

    stats = [d.memory_stats() or {} for d in ctx.devices]
    memory_peak = max((s.get("peak_bytes_in_use", 0) for s in stats),
                      default=0)

    # -- after the window: the kept results against the reference
    correct = first_ok and compiles_in_window == 0 and failed == 0
    if compiles_in_window:
        say(f"  MISMATCH: {compiles_in_window} compile(s) inside the window")
    seen = set()
    for what in ("sample", "last"):
        if what in kept and kept[what][0] not in seen:
            i, out = kept[what]
            seen.add(i)
            ok, _got = compared(out, f"query {i} ({what})")
            correct = correct and ok
    kept.clear()
    say(f"check: {check_s:.3f} s for the reference and the comparisons")

    run_ctx = {
        "records": records, "window_s": window_s, "input_rows": input_rows,
        "input_bytes": input_bytes, "result_bytes": result_bytes,
        "phases": {"setup_s": setup_s, "first_query_s": first_query_s},
        "compiles_in_window": compiles_in_window, "chips": chips,
        "peaks": peaks, "memory_peak_bytes": memory_peak,
        "traced_queries": traced_queries, "counters": None, "trace": None,
    }
    device = {"platform": platform, "kind": kind, "count": len(devs),
              "memory_peak_bytes": memory_peak}
    result = {"correct": bool(correct), "attempted": len(records),
              "failed": failed}

    if tracer is not None and traced_queries:
        import xplane

        c0, c1 = tracer.marks["counters0"], tracer.marks["counters1"]
        run_ctx["counters"] = {k: c1[k] - c0.get(k, 0) for k in c1}
        path = tracer.file()
        if path is None:
            raise NotMeasurable(f"no .xplane.pb under {trace_dir}")
        trace = xplane.Trace(xplane.load(path))
        run_ctx["trace"] = trace
        say(f"trace: {os.path.getsize(path)} bytes, {traced_queries} "
            f"traced queries, {tracer.seconds:.3f} s of the window spent "
            f"starting and stopping the profiler; planes: "
            + "; ".join(trace.describe()))
        busy = trace.busy_s()
        if busy is not None:
            device["busy_s"], device["window_s"] = busy, trace.window_s()
        result["breakdown"] = trace.breakdown()

    group = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for m in metrics_of(bench, group, cell["name"]):
        spec = load_json("metrics", m["name"])
        value = load_code("reducers", spec["reducer"]).reduce(run_ctx, spec)
        if value is None:
            say(f"  metric {m['name']}: nothing to read, left out")
            continue
        metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        say(f"  metric {m['name']} = {value!r} {m['unit']}")
    result["metrics"] = metrics
    result["device"] = device

    if not on_tpu:
        say(f"NOT A TPU ({platform}): every phase ran, no result")
        return result, 1
    if args.trace and "busy_s" not in device:
        raise NotMeasurable("the trace shows no operation on a device")
    return result, 0


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--scale", type=float, default=1.0,
                    help="rehearsal: shrink the rows; never a result")
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="compare the lower-precision reference instead "
                         "of the program's results; must be incorrect")
    result, code = run(ap.parse_args(argv))
    if code == 0:
        print(json.dumps(result), flush=True)
    return code


if __name__ == "__main__":
    sys.exit(main())
