"""The measured window: a closed loop of one client.

The client sends its next query only when the previous one has completed,
so a slower system receives less load. Each query is timed from the call
to its return (the caller's ``query`` blocks until the device has finished
the result). The window opens at the first call and closes at the first
completion at or after ``seconds``; rates divide by the time that really
passed, which includes what ``accept`` and ``between`` take between two
queries.
"""
import time
import traceback
from dataclasses import dataclass
from typing import Optional


@dataclass
class QueryRecord:
    index: int
    started: float        # seconds after the window opened
    seconds: float        # call to completion
    ok: bool              # did not raise, and ``accept`` took the result
    error: Optional[str] = None


def closed_loop(query, seconds, accept=None, between=None,
                clock=time.perf_counter):
    """Run ``query(i)`` back to back until one completes at or after
    ``seconds``. ``between(i, elapsed)`` runs before each query and
    ``accept(i, started, result) -> bool`` after it, both untimed but
    inside the window. A query that raises is a failed query, not the end
    of the run. Returns (records, window_seconds)."""
    records = []
    t_open = clock()
    i = 0
    while True:
        if between is not None:
            between(i, clock() - t_open)
        t0 = clock()
        result, error = None, None
        try:
            result = query(i)
        except Exception:  # a failed query is counted, the loop goes on
            error = traceback.format_exc()
        t1 = clock()
        ok = error is None
        if ok and accept is not None:
            ok = bool(accept(i, t0 - t_open, result))
        del result
        records.append(QueryRecord(i, t0 - t_open, t1 - t0, ok, error))
        i += 1
        if t1 - t_open >= seconds:
            return records, t1 - t_open
