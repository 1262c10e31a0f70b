#!/usr/bin/env python3
"""Look at a profiler trace by hand: planes, lines, how many events, the
names that took most time. `python3 benchmarks/tools/trace_look.py <dir|file>`"""

import collections
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(path: str) -> int:
    from jax.profiler import ProfileData
    from benchmarks.lib import trace
    if os.path.isdir(path):
        path = trace.find_xplane(path)
    print(f"{path}: {os.path.getsize(path)} bytes")
    for plane in ProfileData.from_file(path).planes:
        print(f"PLANE {plane.name!r}")
        for line in plane.lines:
            total = collections.Counter()
            count = collections.Counter()
            n = 0
            for e in line.events:
                n += 1
                total[e.name] += e.duration_ns
                count[e.name] += 1
            print(f"  LINE {line.name!r}: {n} events, {len(total)} names")
            for name, ns in total.most_common(8):
                print(f"      {ns / 1e6:12.3f} ms  x{count[name]:<6} {name[:90]}")
    red = trace.reduce(trace.load(path))
    print("REDUCED", {k: v for k, v in vars(red).items()})
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
