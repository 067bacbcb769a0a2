"""Seconds of a phase of set-up that the harness timed on the host clock
(``phase`` in the metric's file)."""


def reduce(run, spec):
    return run["phases"].get(spec["phase"])
