"""Look at one trace by hand: ``python3 benchmark/trace_summary.py
<file.xplane.pb | trace dir>`` prints each plane, its lines, and the
names that took most time on each line, with one event's stats."""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv) -> int:
    from jax.profiler import ProfileData

    from benchmark import trace_reduce

    path = argv[0]
    if os.path.isdir(path):
        path = trace_reduce.find_xplane(path)
    top = int(argv[1]) if len(argv) > 1 else 12
    for plane in ProfileData.from_file(path).planes:
        print(f"PLANE {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            if not events:
                continue
            total: dict = {}
            for e in events:
                entry = total.setdefault(e.name, [0.0, 0, e])
                entry[0] += e.duration_ns
                entry[1] += 1
            print(f"  LINE {line.name!r}: {len(events)} events, "
                  f"{len(total)} names")
            for name, (ns, n, sample) in sorted(
                    total.items(), key=lambda kv: -kv[1][0])[:top]:
                stats = {k: (v if not isinstance(v, str) else v[:80])
                         for k, v in dict(sample.stats).items()}
                print(f"    {ns / 1e6:12.3f} ms {n:7d} x {name[:90]!r} "
                      f"{stats}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
