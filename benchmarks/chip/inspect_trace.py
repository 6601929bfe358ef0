"""Print what a profiler trace holds: planes, lines and their most frequent
and longest events.  For looking at a trace by hand before a reader keys on
a name.

    python3 benchmarks/chip/inspect_trace.py <dir or .xplane.pb>
"""
import os
import sys
from collections import Counter

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from benchmarks.chip import trace_reduce  # noqa: E402


def main(path: str) -> None:
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = trace_reduce.find_xplane(path)
    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r}: {len(lines)} lines")
        for line in lines:
            count, total = Counter(), Counter()
            for e in line.events:
                count[e.name] += 1
                total[e.name] += e.duration_ns
            print(f"  LINE {line.name!r}: {sum(count.values())} events")
            for name, t in total.most_common(12):
                print(f"    {t / 1e6:12.3f} ms  x{count[name]:<6d} {name[:110]}")


if __name__ == "__main__":
    main(sys.argv[1])
