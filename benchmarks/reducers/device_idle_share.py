"""1 - (union of device-op intervals) / (traced span), worst chip, %."""


def reduce(run, spec):
    return None if run["trace"] is None else run["trace"].idle_share()
