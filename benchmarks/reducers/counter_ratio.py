"""A ratio of the growth of two of the program's counters over the traced
queries: the counters whose name starts with ``numerator`` over those
whose name starts with ``denominator`` (every label summed). With
``"complement": true`` the reading is 1 - ratio; ``scale`` (default 1)
multiplies it (100 for a share in %). None when either counter is absent
in this cell (a program without them, as the parent of the PR that brought
them) or the denominator did not move: nothing to read."""


def _delta(run, prefix):
    hit = [v for k, v in run["counters"].items() if k.startswith(prefix)]
    return sum(hit) if hit else None


def reduce(run, spec):
    if run["counters"] is None or not run["traced_queries"]:
        return None
    num = _delta(run, spec["numerator"])
    den = _delta(run, spec["denominator"])
    if num is None or not den:
        return None
    ratio = num / den
    if spec.get("complement"):
        ratio = 1.0 - ratio
    return spec.get("scale", 1) * ratio
