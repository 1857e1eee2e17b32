#!/usr/bin/env python3
"""Print how a profile is laid out: its planes and lines, and the first
event of each distinct name on each device line, with its stats. Read one
trace by hand with it before changing the reduction in
``bench/harness/trace.py``.

    python bench/inspect_trace.py <profile.xplane.pb> [--names 60]
"""
from __future__ import annotations

import argparse
import sys
from collections import Counter


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("path")
    ap.add_argument("--names", type=int, default=60)
    args = ap.parse_args()
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(args.path)
    for plane in pd.planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r}: {len(lines)} lines")
        for line in lines:
            evs = list(line.events)
            names = Counter(e.name for e in evs)
            print(f"  LINE {line.name!r}: {len(evs)} events, "
                  f"{len(names)} names")
            if not evs:
                continue
            print(f"    first start {evs[0].start_ns}, last end "
                  f"{evs[-1].start_ns + evs[-1].duration_ns}")
            if plane.name.startswith("/host:") and not line.name.startswith(
                    "python"):
                shown = [n for n in names if n.startswith("bench.")]
            else:
                shown = [n for n, _ in names.most_common(args.names)]
            seen = set()
            for e in evs:
                if e.name in shown and e.name not in seen:
                    seen.add(e.name)
                    try:
                        stats = [(k, v if not isinstance(v, str) else v[:160])
                                 for k, v in e.stats]
                    except Exception as err:
                        stats = [("unreadable", repr(err))]
                    print(f"    {names[e.name]:7d}x {e.name[:100]!r} "
                          f"dur {e.duration_ns} {stats}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
