#!/usr/bin/env python3
"""Print the structure of a profiler trace: its planes, their lines with
event counts, and each line's most frequent and longest events.

    python3 bench/tools/xplane_dump.py <file.xplane.pb> [--top 15]
"""
from __future__ import annotations

import argparse
from collections import Counter


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("path")
    ap.add_argument("--top", type=int, default=15)
    args = ap.parse_args()
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(args.path)
    for plane in pd.planes:
        print(f"PLANE {plane.name!r}")
        for line in plane.lines:
            evs = list(line.events)
            if not evs:
                continue
            lo = min(e.start_ns for e in evs)
            hi = max(e.start_ns + e.duration_ns for e in evs)
            print(f"  LINE {line.name!r}: {len(evs)} events, "
                  f"{lo:.0f}..{hi:.0f} ns")
            count = Counter(e.name for e in evs)
            dur = Counter()
            for e in evs:
                dur[e.name] += e.duration_ns
            for name, n in count.most_common(args.top):
                print(f"    {n:7d} x {dur[name] * 1e-6:10.3f} ms  {name[:100]}")
            shown = [e for e in evs if "custom" in e.name.lower()
                     or "pallas" in e.name.lower() or "kernel" in e.name.lower()]
            for e in (shown[:3] or evs[:1]):
                print(f"    stats of {e.name[:60]!r}: {list(e.stats)[:12]}")


if __name__ == "__main__":
    main()
