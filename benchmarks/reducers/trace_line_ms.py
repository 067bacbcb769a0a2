"""Device milliseconds per traced query (mean over the chips) of the
events of one line of the device planes (``line``) whose names match one
of ``patterns``. None when the trace has no such line."""


def reduce(run, spec):
    trace = run["trace"]
    if trace is None or not trace.n_queries:
        return None
    s = trace.seconds_matching(spec["line"], spec["patterns"])
    if s is None:
        return None
    return 1e3 * s / trace.n_queries
