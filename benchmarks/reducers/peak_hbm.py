"""memory_stats()['peak_bytes_in_use'], max over the chips, after the
window."""


def reduce(run, spec):
    return run["memory_peak_bytes"] or None
