#!/usr/bin/env python
"""What one ``telemetry.span`` costs the host, with the profiler off and
under an active trace.

    python scripts/span_cost.py [--chips N] [--spans 100000]

The spans are opened as the benchmark's queries open theirs: a context
on the machine's devices (so the memory pool's snapshots are the real
``memory_stats`` calls, one a local device), under a ``plan.query`` root,
empty bodies. 100 roots of ``spans / 100`` children each; a batch's cost
is its wall time over its children, and the numbers printed are the
median and the 90th percentile of the batches, in microseconds a span
(open + close). The traced round runs under ``jax.profiler`` with the
options ``benchmarks/run.py`` traces with; ``off_no_hbm_attrs`` is the
untraced round again with ``CYLON_HBM_SPAN_ATTRS=0``, to say what of the
cost the snapshots are. ``crossed`` is a span that is no ``with`` block
(PR 51: the service's ``service.query`` and ``service.queue_wait``):
``open_span`` on this thread, ``close_span`` on another one under
``attach``, profiler off; its two halves are timed apart and add up to
one span. The last line is JSON.
"""
import argparse
import json
import os
import shutil
import statistics
import sys
import tempfile
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def batches_us(telemetry, roots, children):
    out = []
    for _ in range(roots):
        with telemetry.span("plan.query"):
            t0 = time.perf_counter()
            for _ in range(children):
                with telemetry.span("cost.empty"):
                    pass
            out.append((time.perf_counter() - t0) * 1e6 / children)
    return out


def crossed_us(telemetry, roots, children):
    """(open, close) microseconds a span: the children of a root opened
    here with ``open_span`` and closed by another thread that attached
    the root."""
    opens, closes = [], []
    for _ in range(roots):
        root = telemetry.open_span("plan.query")
        t0 = time.perf_counter()
        kids = [telemetry.open_span("cost.crossed", parent=root)
                for _ in range(children)]
        opens.append((time.perf_counter() - t0) * 1e6 / children)

        def close_all():
            with telemetry.attach(root):
                t0 = time.perf_counter()
                for kid in kids:
                    telemetry.close_span(kid)
                closes.append((time.perf_counter() - t0) * 1e6 / children)

        closer = threading.Thread(target=close_all)
        closer.start()
        closer.join()
        telemetry.close_span(root)
    return opens, closes


def summary(us):
    return {"median_us": statistics.median(us),
            "p90_us": statistics.quantiles(us, n=10)[-1]}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1)
    ap.add_argument("--spans", type=int, default=100_000)
    args = ap.parse_args(argv)

    import jax

    import cylon_tpu as ct
    from cylon_tpu import telemetry

    ctx = ct.CylonContext.InitDistributed(ct.TPUConfig(world_size=args.chips))
    roots, children = 100, max(args.spans // 100, 1)
    batches_us(telemetry, 2, children)   # warm the histogram's series
    result = {"platform": jax.devices()[0].platform,
              "kind": jax.devices()[0].device_kind,
              "devices": len(ctx.devices), "spans": roots * children,
              "off": summary(batches_us(telemetry, roots, children))}
    opens, closes = crossed_us(telemetry, roots, children)
    result["crossed"] = {"open": summary(opens), "close": summary(closes)}
    # where the cost is: the same round without the two pool snapshots a
    # span takes (the knob is read live)
    os.environ["CYLON_HBM_SPAN_ATTRS"] = "0"
    result["off_no_hbm_attrs"] = summary(
        batches_us(telemetry, roots, children))
    del os.environ["CYLON_HBM_SPAN_ATTRS"]

    trace_dir = tempfile.mkdtemp(prefix="span_cost_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        result["traced"] = summary(batches_us(telemetry, roots, children))
    finally:
        jax.profiler.stop_trace()
        shutil.rmtree(trace_dir, ignore_errors=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
