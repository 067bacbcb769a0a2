"""The process's total, at the end of the run, of the program's numeric
series whose name starts with ``prefix`` and, where the metric's file
gives ``any_of``, holds one of those label texts (``stage="trace"``):
what the program counted over set-up and window together. None when the
program has no such series."""


def reduce(run, spec):
    from cylon_tpu import telemetry

    any_of = spec.get("any_of")
    hit = [v for k, v in telemetry.metrics_snapshot().items()
           if k.startswith(spec["prefix"]) and isinstance(v, (int, float))
           and (any_of is None or any(a in k for a in any_of))]
    return sum(hit) if hit else None
