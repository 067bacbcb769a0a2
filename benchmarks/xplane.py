"""From a profiler trace (``.xplane.pb``) to intervals the reducers read.

``load`` reads the file with nothing but JAX (``jax.profiler.ProfileData``)
into plain data: planes -> lines -> events ``[name, start_ns, dur_ns]``.
Of the host planes only the annotations the reduction needs are kept
(``bench:*`` from this benchmark, ``cylon:*`` from the program's spans).
``Trace`` is the reduction on that plain data, so that the tests can run it
on a small recorded trace kept as JSON (``tests/trace_small.json``).

What a v5e trace holds (looked at by hand, PERF.md section 3): one plane a
chip named ``/device:TPU:<n>``; its line ``XLA Ops`` has one event for
every HLO operation that ran, named by the instruction's whole text
(fusions, sorts, copies, collectives; a Pallas call is a ``custom-call``
named after the jitted function around it), its line ``XLA Modules`` one
event for every run of a jitted program, named ``jit_<function>(<hash>)``;
``Async XLA Ops`` holds copy-start/slice-start spans that overlap the
others and is not counted as busy. Host threads are lines of the plane
``/host:CPU``; ``TraceAnnotation`` spans are on its line ``python`` under
their own names. All planes share one clock (nanoseconds).
"""
import gzip
import json
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
QUERY_SPAN = "bench:query"
_KEEP_HOST = ("bench:", "cylon:")


_OPCODE = re.compile(r" ([a-z][a-z\-]*)\(")


def short_name(name):
    """An ``XLA Ops`` event is named by the whole text of its HLO
    instruction, ``%sort.16 = (u32[32000000]{...}, ...) sort(...)``: keep
    the instruction's name and its opcode, ``sort.16 sort``. A Pallas call
    reads ``_plan_program_stream_impl.1 custom-call`` (it takes the name of
    the jitted function around it). Other names stay as they are."""
    if not name.startswith("%") or " = " not in name:
        return name
    head, rest = name[1:].split(" = ", 1)
    m = _OPCODE.search(" " + rest)
    return f"{head} {m.group(1)}" if m else head


def load(path):
    from jax.profiler import ProfileData

    planes = []
    for p in ProfileData.from_file(path).planes:
        device = bool(DEVICE_PLANE.match(p.name))
        lines = []
        for ln in p.lines:
            events = [[short_name(e.name), int(e.start_ns),
                       int(e.duration_ns)]
                      for e in ln.events
                      if device or e.name.startswith(_KEEP_HOST)]
            if events:
                lines.append({"name": ln.name, "events": events})
        if lines:
            planes.append({"name": p.name, "lines": lines})
    return {"planes": planes}


def union(intervals):
    """Sorted, merged copy of ``[(start, end), ...]``."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def total(intervals):
    return sum(e - s for s, e in intervals)


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def gaps(merged, lo, hi):
    """The parts of [lo, hi] that the merged intervals leave uncovered."""
    out, at = [], lo
    for s, e in merged:
        if s > at:
            out.append((at, min(s, hi)))
        at = max(at, e)
        if at >= hi:
            break
    if at < hi:
        out.append((at, hi))
    return [(s, e) for s, e in out if e > s]


def base_name(name):
    """``fusion.123 fusion`` -> ``fusion``, ``x.1 custom-call`` ->
    ``x (custom-call)``, ``jit_f(4711)`` -> ``jit_f``: one name for every
    instance, so that times add up by kind."""
    head, _, opcode = name.partition(" ")
    head = re.sub(r"(\.\d+|\(\d+\))+$", "", head)
    return f"{head} ({opcode})" if opcode == "custom-call" else head


class Trace:
    def __init__(self, data):
        self.data = data
        self._busy_of = {}     # chip number -> merged busy intervals
        self.devices = {}      # chip number -> {line name: events}
        self.host = []         # [name, start, dur] of kept host spans
        for p in data["planes"]:
            m = DEVICE_PLANE.match(p["name"])
            if m:
                self.devices[int(m.group(1))] = {
                    ln["name"]: ln["events"] for ln in p["lines"]}
            else:
                for ln in p["lines"]:
                    self.host.extend(ln["events"])
        q = [(s, s + d) for n, s, d in self.host if n == QUERY_SPAN]
        self.window = (min(s for s, _ in q), max(e for _, e in q)) \
            if q else None
        self.n_queries = len(q)

    def describe(self):
        return [f"{p['name']} [" + ", ".join(
            f"{ln['name']}: {len(ln['events'])}" for ln in p["lines"]) + "]"
            for p in self.data["planes"]]

    def dump(self, path):
        with gzip.open(path, "wt") as f:
            json.dump(self.data, f)

    # -- intervals ---------------------------------------------------------

    def _busy(self, chip):
        if chip not in self._busy_of:
            lo, hi = self.window
            ops = self.devices[chip].get(OPS_LINE, [])
            self._busy_of[chip] = union(
                clip([(s, s + d) for _, s, d in ops], lo, hi))
        return self._busy_of[chip]

    def measurable(self):
        return bool(self.window and self.devices and any(
            self._busy(c) for c in self.devices))

    def window_s(self):
        return (self.window[1] - self.window[0]) / 1e9

    def busy_s(self):
        """Seconds in which an operation ran on the device, within the
        traced queries, averaged over the chips."""
        if not self.measurable():
            return None
        return sum(total(self._busy(c)) for c in self.devices) / 1e9 \
            / len(self.devices)

    def idle_share(self):
        """1 - busy/window on the WORST chip, in percent."""
        if not self.measurable():
            return None
        lo, hi = self.window
        return 100.0 * max(1.0 - total(self._busy(c)) / (hi - lo)
                           for c in self.devices)

    def seconds_matching(self, line, patterns):
        """Device seconds of the events of ``line`` whose name matches one
        of the regular expressions, within the traced queries, averaged
        over the chips; None if no chip has the line."""
        if not self.window or not any(line in d
                                      for d in self.devices.values()):
            return None
        lo, hi = self.window
        rx = [re.compile(p) for p in patterns]
        ns = 0
        for d in self.devices.values():
            hit = [(s, s + dur) for n, s, dur in d.get(line, [])
                   if any(r.search(n) for r in rx)]
            ns += total(union(clip(hit, lo, hi)))
        return ns / 1e9 / len(self.devices)

    # -- the breakdown the ledger keeps --------------------------------------

    def _label_at(self, t):
        """The innermost ``cylon:`` span of the host that encloses time t,
        else the benchmark's own span, else 'between queries'."""
        best = None
        for n, s, d in self.host:
            if s <= t < s + d and (best is None or d < best[1]) \
                    and n.startswith("cylon:"):
                best = (n, d)
        if best:
            return re.sub(r"#\d+$", "", best[0])   # drop the op sequence
        inside = any(n == QUERY_SPAN and s <= t < s + d
                     for n, s, d in self.host)
        return "bench:query (no cylon span)" if inside else "between queries"

    def breakdown(self, top=10):
        if not self.measurable():
            return {"device_ops": [], "idle_gaps": []}
        lo, hi = self.window
        by_op = {}
        for d in self.devices.values():
            for n, s, dur in d.get(OPS_LINE, []):
                if s + dur > lo and s < hi:
                    k = base_name(n)
                    by_op[k] = by_op.get(k, 0) + dur
        ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
        worst = min(self.devices, key=lambda c: total(self._busy(c)))
        by_gap = {}
        for s, e in gaps(self._busy(worst), lo, hi):
            k = self._label_at((s + e) // 2)
            by_gap[k] = by_gap.get(k, 0) + (e - s)
        idle = sorted(by_gap.items(), key=lambda kv: -kv[1])[:top]
        n = len(self.devices)
        return {"device_ops": [[k, v / 1e9 / n] for k, v in ops],
                "idle_gaps": [[k, v / 1e9] for k, v in idle]}
