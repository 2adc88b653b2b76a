"""Print the planes, lines and heaviest events of the last traced run's
profile (``bench/.traces``): what to look at before writing a per-layer
reader against a trace.

  python3 bench/tools/dump_trace.py [--top 25]
"""
from __future__ import annotations

import argparse
import collections
import glob
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--top", type=int, default=25)
    args = ap.parse_args()
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(ROOT, "bench", ".traces", "**",
                                          "*.xplane.pb"), recursive=True))
    for f in files:
        print("file", os.path.relpath(f, ROOT), os.path.getsize(f), "bytes")
        for plane in ProfileData.from_file(f).planes:
            lines = list(plane.lines)
            print("PLANE", repr(plane.name), "lines", len(lines))
            for line in lines:
                events = list(line.events)
                print("   LINE", repr(line.name), len(events))
                count, total = collections.Counter(), collections.Counter()
                for e in events:
                    count[e.name] += 1
                    total[e.name] += e.duration_ns
                for name, ns in total.most_common(args.top):
                    print("      %-90s n=%6d  ms=%.3f"
                          % (name[:90], count[name], ns / 1e6))


if __name__ == "__main__":
    main()
