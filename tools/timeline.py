#!/usr/bin/env python
"""Chrome-trace timeline CLI — thin shim over the package converter.

The xplane→chrome-trace conversion now lives at
``paddle_tpu.observability.tracing.xplane_to_chrome_trace`` so the
package owns ONE trace-export entry point
(``observability.dump_chrome_trace(path, xplane_dir=...)`` merges host
spans + device planes into a single perfetto view). This CLI is kept
for the reference workflow (reference repo's tools/timeline.py:36 —
convert a profiler dump, open in chrome://tracing):

Usage: python tools/timeline.py <trace_dir> <out.json> [line_filter]
"""
import json
import os
import sys

sys.path.insert(0, os.path.abspath(
    os.path.join(os.path.dirname(__file__), os.pardir)))

from paddle_tpu.observability.tracing import (  # noqa: E402,F401
    xplane_to_chrome_trace,
)


def main():
    trace_dir, out = sys.argv[1], sys.argv[2]
    line_filter = sys.argv[3] if len(sys.argv) > 3 else None
    trace = xplane_to_chrome_trace(trace_dir, line_filter)
    with open(out, "w") as f:
        json.dump(trace, f)
    print("wrote %d events to %s" % (len(trace["traceEvents"]), out))


if __name__ == "__main__":
    main()
