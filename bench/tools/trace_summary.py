#!/usr/bin/env python3
"""Print the structure of a profiler trace: planes, lines, event counts,
the most frequent event names and each line's time span, to check by
hand which planes are devices, how ops are named, and whether host and
device events share a clock.

    python3 bench/tools/trace_summary.py <dir holding an .xplane.pb>
"""

import collections
import sys

from jax.profiler import ProfileData

sys.path.insert(0, __file__.rsplit("/bench/", 1)[0])
from bench import trace_reduce  # noqa: E402


def main(path: str) -> None:
    xp = trace_reduce.find_xplane(path) or path
    pd = ProfileData.from_file(xp)
    for plane in pd.planes:
        print(f"PLANE {plane.name}")
        for ln in plane.lines:
            evs = list(ln.events)
            if not evs:
                continue
            names = collections.Counter(e.name for e in evs)
            lo = min(e.start_ns for e in evs)
            hi = max(e.start_ns + e.duration_ns for e in evs)
            print(f"  LINE {ln.name!r}: {len(evs)} events over "
                  f"[{lo:.0f}, {hi:.0f}] ns; top "
                  f"{names.most_common(8)}")


if __name__ == "__main__":
    main(sys.argv[1])
