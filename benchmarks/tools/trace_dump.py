#!/usr/bin/env python3
"""Print what a profiler trace holds, to be read by hand: planes, lines,
event counts, the first events of each line with their statistics, and the
names that took most device time.

    python3 benchmarks/tools/trace_dump.py <trace dir or .xplane.pb> [events per line]
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks import trace_reduce  # noqa: E402


def main():
    path = sys.argv[1]
    show = int(sys.argv[2]) if len(sys.argv) > 2 else 3
    if os.path.isdir(path):
        path = trace_reduce.find_xplane(path)
    profile = trace_reduce.load(path)
    for plane in profile.planes:
        lines = list(plane.lines)
        print("plane %r: %d lines" % (plane.name, len(lines)))
        for line in lines:
            events = list(line.events)
            print("  line %r: %d events" % (line.name, len(events)))
            for ev in events[:show]:
                stats = {k: (str(v)[:160]) for k, v in ev.stats}
                print("    %r start %d dur %d %r"
                      % (ev.name[:120], ev.start_ns, ev.duration_ns, stats))
    reduced = trace_reduce.reduce(profile)
    if reduced:
        for key in ("devices", "busy_s", "window_s", "steps", "top_ops",
                    "idle_gaps"):
            print(key, reduced[key])


if __name__ == "__main__":
    main()
