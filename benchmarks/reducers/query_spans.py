"""What the program's host spans say about the traced queries. The spans
are ``TraceAnnotation``s on the profiler's clock, the same clock as the
device planes, so a host span can be laid over the chip's busy time.

``read`` in the metric's file picks the reading, each per traced query:

- ``count``: how many spans whose name starts with ``spans`` START inside
  a ``bench:query`` span;
- ``idle_ms``: the time in which such a span is open, inside
  ``bench:query``, and no event of the device line ``line`` runs, on the
  chip where that time is longest (one chip that waits is what the query
  waits for);
- ``self_ms``: the time covered by the spans named ``spans`` less the
  time covered, inside them, by the spans whose name starts with ``less``:
  a span's own time, without what it spent in those children.

None when there is no trace, when the trace holds no span named by
``spans`` (``count``, ``idle_ms``) or by ``less`` (``self_ms``) anywhere
(the program does not open such spans, so there is nothing to read), or,
for ``idle_ms``, when no device plane has the line.
"""
import re

from xplane import QUERY_SPAN, clip, gaps, total, union

_SEQ = re.compile(r"#\d+$")   # the op sequence some span labels carry


def _spans(trace, prefix, exact=False):
    """[(start, end)] of the host spans named ``prefix`` (exactly, but
    for a ``#<seq>`` suffix) or whose name starts with it."""
    out = []
    for name, start, dur in trace.host:
        name = _SEQ.sub("", name)
        if name == prefix if exact else name.startswith(prefix):
            out.append((start, start + dur))
    return out


def _within(intervals, merged):
    """The parts of ``intervals`` that lie inside the merged ones."""
    return [c for s, e in intervals for c in clip(merged, s, e)]


def reduce(run, spec):
    trace = run["trace"]
    if trace is None or not trace.n_queries:
        return None
    queries = union(_spans(trace, QUERY_SPAN, exact=True))
    read = spec["read"]
    if read == "self_ms":
        own = union(_within(_spans(trace, spec["spans"], exact=True),
                            queries))
        less = _spans(trace, spec["less"])
        if not own or not less:
            return None
        ns = total(own) - total(union(_within(less, own)))
        return ns / 1e6 / trace.n_queries
    spans = _spans(trace, spec["spans"])
    if not spans:
        return None
    if read == "count":
        inside = sum(any(s <= start < e for s, e in queries)
                     for start, _end in spans)
        return inside / trace.n_queries
    if read == "idle_ms":
        chips = [lines[spec["line"]] for lines in trace.devices.values()
                 if spec["line"] in lines]
        if not chips:
            return None   # no device plane: nothing ran on a chip
        open_ = union(_within(spans, queries))
        worst = 0
        for events in chips:
            busy = union((s, s + d) for _n, s, d in events)
            idle = sum(total(gaps(clip(busy, s, e), s, e))
                       for s, e in open_)
            worst = max(worst, idle)
        return worst / 1e6 / trace.n_queries
    raise ValueError(f"query_spans: unknown read {read!r}")
