"""Look at one trace by hand before writing a reader against it: every
plane and line of an .xplane.pb, and on each line the names that took
most time, with one event's stats.

    python3 -m perfbench.tools.trace_dump <file.xplane.pb | trace dir> [N]
"""
from __future__ import annotations

import os
import sys


def main(argv=None):
    argv = sys.argv[1:] if argv is None else argv
    path = argv[0]
    top = int(argv[1]) if len(argv) > 1 else 15
    if os.path.isdir(path):
        from ..trace_reduce import find_xplane
        path = find_xplane(path)
    from jax.profiler import ProfileData
    print(f"{path}: {os.path.getsize(path)} bytes")
    for plane in ProfileData.from_file(path).planes:
        print(f"PLANE {plane.name!r}")
        for line in plane.lines:
            by_name, sample, n, first, last = {}, {}, 0, None, 0
            for e in line.events:
                n += 1
                by_name[e.name] = by_name.get(e.name, 0) + e.duration_ns
                sample.setdefault(e.name, e)
                first = e.start_ns if first is None else min(first, e.start_ns)
                last = max(last, e.start_ns + e.duration_ns)
            span = (last - (first or 0)) * 1e-9
            print(f"  LINE {line.name!r}: {n} events, {len(by_name)} names, "
                  f"spanning {span:.4f} s")
            for name, ns in sorted(by_name.items(),
                                   key=lambda kv: -kv[1])[:top]:
                e = sample[name]
                stats = {k: (v if len(str(v)) < 80 else str(v)[:77] + "...")
                         for k, v in e.stats}
                print(f"    {ns * 1e-9:10.6f} s  {name[:100]!r}  {stats}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
