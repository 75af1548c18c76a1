#!/usr/bin/env python3
"""Print what a profiler trace holds: planes, lines, the most frequent
event names and the stats of a few events per line.

    python3 bench/trace_dump.py <trace dir or .xplane.pb> [events per line]

For reading a new kind of trace by hand before writing a reduction
against it.
"""

import collections
import sys
from pathlib import Path


def main() -> int:
    target = Path(sys.argv[1])
    n_show = int(sys.argv[2]) if len(sys.argv) > 2 else 3
    path = (target if target.is_file()
            else sorted(target.rglob("*.xplane.pb"))[-1])
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    print(f"trace {path} ({path.stat().st_size} bytes)")
    for plane in pd.planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r}: {len(lines)} lines; stats "
              f"{[(k, str(v)[:80]) for k, v in plane.stats][:8]}")
        for line in lines:
            evs = list(line.events)
            names = collections.Counter(e.name for e in evs)
            dur = sum(e.duration_ns for e in evs)
            span = ((max(e.end_ns for e in evs) - min(e.start_ns for e in evs))
                    if evs else 0)
            print(f"  LINE {line.name!r}: {len(evs)} events, sum of "
                  f"durations {dur / 1e9:.6f} s over {span / 1e9:.6f} s; "
                  f"top names {names.most_common(8)}")
            seen = 0
            for e in evs:
                if seen >= n_show:
                    break
                if e.duration_ns <= 0:
                    continue
                seen += 1
                print(f"    {e.name!r} start {e.start_ns} dur "
                      f"{e.duration_ns} stats "
                      f"{[(k, str(v)[:300]) for k, v in e.stats]}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
