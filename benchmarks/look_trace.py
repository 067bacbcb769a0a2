#!/usr/bin/env python3
"""Look at a profiler trace by hand before trusting a reduction of it:

    python benchmarks/look_trace.py <file.xplane.pb> [<events.json.gz>]

prints every plane and line, the statistics an event carries, and for the
device planes the names on ``XLA Modules`` and the operations that took
most time on ``XLA Ops``. With a second argument it also writes the events
as ``xplane.py`` reduces them (what ``tests/trace_small.json`` was cut
from)."""
import collections
import os
import sys

from jax.profiler import ProfileData


def main(path):
    for p in ProfileData.from_file(path).planes:
        lines = list(p.lines)
        print(f"PLANE {p.name!r}: {len(lines)} line(s)")
        for ln in lines:
            events = list(ln.events)
            by = collections.defaultdict(lambda: [0, 0.0])
            for e in events:
                b = by[e.name]
                b[0] += 1
                b[1] += e.duration_ns
            span = (min(e.start_ns for e in events),
                    max(e.start_ns + e.duration_ns for e in events)) \
                if events else (0, 0)
            print(f"  LINE {ln.name!r}: {len(events)} events, "
                  f"{len(by)} names, from {span[0]} to {span[1]} ns")
            if events:
                stats = [(k, str(v)[:80]) for k, v in events[0].stats]
                print(f"    first event {events[0].name!r} stats {stats}")
            top = sorted(by.items(), key=lambda kv: -kv[1][1])[:30]
            for name, (n, ns) in top:
                print(f"    {ns / 1e6:12.3f} ms {n:7d} x {name[:120]}")


if __name__ == "__main__":
    main(sys.argv[1])
    if len(sys.argv) > 2:
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        import xplane

        xplane.Trace(xplane.load(sys.argv[1])).dump(sys.argv[2])
