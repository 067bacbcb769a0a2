"""A quantile (``q`` in the metric's file) of the seconds, call to
block_until_ready, of ALL the queries completed in the window: linear
interpolation between the two nearest of the sorted times, so ``q`` 0.5 is
the median."""
import math


def reduce(run, spec):
    times = sorted(r.seconds for r in run["records"] if r.ok)
    if not times:
        return None
    pos = spec["q"] * (len(times) - 1)
    lo = math.floor(pos)
    hi = min(lo + 1, len(times) - 1)
    return times[lo] + (pos - lo) * (times[hi] - times[lo])
