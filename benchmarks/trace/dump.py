"""Look at a profiler capture by hand: planes, lines, the first events of
each line with their stats, and the names that take most time.

    python3 -m benchmarks.trace.dump <file.xplane.pb | capture dir> [--events N]

Read one before changing ``reduce.py``: which planes are devices, which lines
hold operations, and how the kernels and scopes are named.
"""

import argparse
import collections
import glob
import os
import sys


def newest_xplane(path: str) -> str:
    if os.path.isfile(path):
        return path
    found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no *.xplane.pb under {path!r}")
    return found[-1]


def main(argv=None) -> int:
    from jax.profiler import ProfileData
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("path")
    p.add_argument("--events", type=int, default=12)
    p.add_argument("--top", type=int, default=25)
    args = p.parse_args(argv)
    path = newest_xplane(args.path)
    print(f"{path}  {os.path.getsize(path)} bytes")
    for plane in ProfileData.from_file(path).planes:
        print(f"PLANE {plane.name!r} stats={dict(plane.stats)}")
        for line in plane.lines:
            events = list(line.events)
            total = collections.Counter()
            for e in events:
                total[e.name] += e.duration_ns
            span = ((min(e.start_ns for e in events),
                     max(e.start_ns + e.duration_ns for e in events))
                    if events else (0, 0))
            print(f"  LINE {line.name!r} events={len(events)} "
                  f"span_ns=({span[0]:.0f}, {span[1]:.0f})")
            for e in events[:args.events]:
                print(f"    {e.name!r} start={e.start_ns:.0f} "
                      f"dur={e.duration_ns:.0f} stats={dict(e.stats)}")
            for name, ns in total.most_common(args.top):
                print(f"    TOP {ns / 1e6:10.3f} ms  {name!r}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
