"""The chips' idle time laid over the program's host spans BY OVERLAP, and
the device programs a query launches. The spans are ``TraceAnnotation``s
on the clock of the device planes, so every idle interval of the chips
can be cut at the edges of the spans that were open while it lasted.

All readings are per traced query and inside ``bench:query``. A chip is
idle while no event of its line ``XLA Ops`` runs. ``chips`` picks whose
idle time is read:

- ``all`` (the default): the time in which NO chip runs an operation (the
  intersection of the chips' gaps): the host's doing;
- ``worst``: the reading on each chip alone, and the chip on which it is
  longest: what ``device_idle_share`` and ``query_spans``' ``idle_ms``
  read. It holds, besides, the time in which this chip is done and another
  still runs. On one chip the two agree.

``read`` picks the reading:

- ``idle_ms``: all of the idle time;
- ``imbalance_ms``: ``idle_ms`` of ``worst`` less ``idle_ms`` of ``all``;
- ``leaf_idle_ms`` with ``span`` (one exact name or a list of them, a
  ``#<seq>`` suffix apart): the idle time during which such a span is the
  INNERMOST open ``cylon:`` span. A gap that lasts across three spans is
  split among them at their edges (``xplane.Trace._label_at`` books the
  whole gap to the span at its midpoint);
- ``unspanned_idle_ms``: the idle time under no ``cylon:`` span at all.
  Over every span name of a trace, ``leaf_idle_ms`` and this add up to
  ``idle_ms`` (``leaves`` gives the whole list);
- ``programs``: the events of the line ``XLA Modules`` (one a run of a
  jitted program) that START inside ``bench:query``, on the chip that has
  most; with ``not_matching`` (regular expressions), only those whose
  name matches none of them.

None when there is no trace or no traced query, when no device plane has
the line, and for ``leaf_idle_ms`` when the trace holds no span of that
name anywhere (the program opens none: nothing to read).

By hand, the whole list of a traced run (``benchmarks/run.py --trace 1``
leaves its trace under ``.bench_trace/<cell>/``):

    PYTHONPATH=benchmarks python3 benchmarks/reducers/span_idle.py <dir>
"""
import re

from xplane import OPS_LINE, QUERY_SPAN, base_name, clip, gaps, total, union

MODULES_LINE = "XLA Modules"
_SEQ = re.compile(r"#\d+$")   # the op sequence some span labels carry
_SPAN = "cylon:"


def _queries(trace):
    return union((s, s + d) for n, s, d in trace.host if n == QUERY_SPAN)


def _idle(trace, queries, chips):
    """[idle intervals inside the queries], one list a reading: ONE for
    ``all`` (no chip busy), one a chip for ``worst``. None if no device
    plane has the line."""
    busy = [trace._busy(c) for c, lines in trace.devices.items()
            if OPS_LINE in lines]   # merged once a chip, kept by the trace
    if not busy:
        return None
    if chips == "all":
        busy = [union(iv for chip in busy for iv in chip)]
    elif chips != "worst":
        raise ValueError(f"span_idle: unknown chips {chips!r}")
    return [[g for lo, hi in queries for g in gaps(clip(chip, lo, hi), lo, hi)]
            for chip in busy]


def _leaf_segments(trace):
    """[(name, start, end)]: the intervals in which each ``cylon:`` span
    is the innermost open one (the last opened that has not closed), its
    ``#<seq>`` dropped."""
    spans = sorted(((s, -(s + d), _SEQ.sub("", n))
                    for n, s, d in trace.host if n.startswith(_SPAN)))
    out, stack, at = [], [], 0

    def advance(to):
        nonlocal at
        while stack and stack[-1][1] <= to:
            name, end = stack.pop()
            if end > at:
                out.append((name, at, end))
                at = end
        if stack and to > at:
            out.append((stack[-1][0], at, to))
        at = max(at, to)

    for start, neg_end, name in spans:
        advance(start)
        stack.append((name, -neg_end))
    advance(float("inf"))
    return out


def _under(idle, segments):
    """ns of the idle intervals that lie inside the segments."""
    return sum(total(clip(idle, s, e)) for s, e in segments)


def leaves(trace, chips="all"):
    """{span name: idle ms a query under it as the innermost span}, with
    ``(unspanned)`` and ``(idle)`` (the whole, read on its own: the others
    have to add up to it) beside them; for ``worst`` the chip whose whole
    idle time is longest. None if nothing to read."""
    if trace is None or not trace.n_queries:
        return None
    idle = _idle(trace, _queries(trace), chips)
    if not idle:
        return None
    idle = max(idle, key=total)
    segments = _leaf_segments(trace)
    by_name = {}
    for name, s, e in segments:
        ns = total(clip(idle, s, e))
        if ns:
            by_name[name] = by_name.get(name, 0) + ns
    by_name["(unspanned)"] = total(idle) - _under(
        idle, union((s, e) for _n, s, e in segments))
    by_name["(idle)"] = total(idle)
    return {k: v / 1e6 / trace.n_queries for k, v in by_name.items()}


def _program_runs(trace, not_matching=()):
    """{program name, its hash dropped: [runs, device ns]} over the traced
    queries, on the chip that runs most programs inside them; None if no
    device plane has the line."""
    queries = _queries(trace)
    rx = [re.compile(p) for p in not_matching]
    best = None
    for lines in trace.devices.values():
        if MODULES_LINE not in lines:
            continue
        by_name = {}
        for name, start, dur in lines[MODULES_LINE]:
            if any(s <= start < e for s, e in queries) \
                    and not any(r.search(name) for r in rx):
                runs_ns = by_name.setdefault(base_name(name), [0, 0])
                runs_ns[0] += 1
                runs_ns[1] += dur
        if best is None or _runs(by_name) > _runs(best):
            best = by_name
    return best


def _runs(found):
    return sum(runs for runs, _ns in found.values())


def programs(trace, not_matching=()):
    """{program name: [runs, device ms] a query} of ``_program_runs``;
    None if nothing to read."""
    if trace is None or not trace.n_queries:
        return None
    found = _program_runs(trace, not_matching)
    return found and {name: [runs / trace.n_queries,
                             ns / 1e6 / trace.n_queries]
                      for name, (runs, ns) in found.items()}


def reduce(run, spec):
    trace = run["trace"]
    if trace is None or not trace.n_queries:
        return None
    read = spec["read"]
    if read == "programs":
        found = _program_runs(trace, spec.get("not_matching", ()))
        # whole runs, divided once: a count reads 95.0, not 94.99999999999997
        return None if found is None else _runs(found) / trace.n_queries
    queries = _queries(trace)
    per_query = 1e6 * trace.n_queries
    if read == "imbalance_ms":
        worst, every = (_idle(trace, queries, c) for c in ("worst", "all"))
        if not worst:
            return None
        return (max(map(total, worst)) - total(every[0])) / per_query
    idle = _idle(trace, queries, spec.get("chips", "all"))
    if not idle:
        return None
    if read == "idle_ms":
        return max(map(total, idle)) / per_query
    segments = _leaf_segments(trace)
    if read == "unspanned_idle_ms":
        spanned = union((s, e) for _n, s, e in segments)
        return max(total(i) - _under(i, spanned)
                   for i in idle) / per_query
    if read == "leaf_idle_ms":
        names = spec["span"]
        names = {names} if isinstance(names, str) else set(names)
        mine = [(s, e) for n, s, e in segments if n in names]
        if not mine:
            return None
        return max(_under(i, mine) for i in idle) / per_query
    raise ValueError(f"span_idle: unknown read {read!r}")


def main(argv):
    """Print the whole list of a traced run: every leaf span's idle time
    beside the sum, for ``all`` and for ``worst``, and the programs by
    name."""
    import os

    import xplane

    where = argv[1]
    paths = [os.path.join(base, f) for base, _d, files in os.walk(where)
             for f in files if f.endswith(".xplane.pb")] \
        if os.path.isdir(where) else [where]
    if not paths:
        print(f"span_idle: no .xplane.pb under {where}")
        return 1
    trace = xplane.Trace(xplane.load(paths[0]))
    print(f"{paths[0]}: {trace.n_queries} traced queries, "
          f"{len(trace.devices)} chip(s)")
    for chips in ("all", "worst"):
        table = leaves(trace, chips)
        if table is None:
            print(f"chips={chips}: nothing to read")
            continue
        whole = table.pop("(idle)")
        print(f"chips={chips}: idle {whole:.3f} ms a query; by innermost "
              f"span (sum {sum(table.values()):.3f}):")
        for name, ms in sorted(table.items(), key=lambda kv: -kv[1]):
            print(f"  {ms:9.3f}  {name}")
    found = programs(trace) or {}
    print(f"programs a query on the chip that runs most: "
          f"{sum(runs for runs, _ms in found.values()):.2f}; "
          f"runs and device ms a query by name:")
    for name, (runs, ms) in sorted(found.items(), key=lambda kv: -kv[1][0]):
        print(f"  {runs:9.2f} {ms:10.3f}  {name}")
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main(sys.argv))
