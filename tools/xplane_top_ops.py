#!/usr/bin/env python
"""Aggregate device-time by op from a jax.profiler xplane trace — the
trace-reading half of the profiler story (SURVEY §5), used in round 4 to
find where the BERT engine step spends its time vs the probe.

Thin CLI shim: the plane iterator and aggregation live in
``paddle_tpu.observability.opprof`` (the package must never import from
tools/); ``iter_planes``/``top_ops`` are re-exported here for
back-compat with older scripts.

Usage: python tools/xplane_top_ops.py <trace_dir> [top_n] [group]
``group``: 'op' (default, per fused-computation name) or 'kind'
(collapse to the HLO opcode-ish prefix, e.g. fusion/copy/convolution).
"""
import os
import sys

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from paddle_tpu.observability.opprof import (  # noqa: E402,F401
    iter_planes,
    top_ops,
)

if __name__ == "__main__":
    d = sys.argv[1]
    n = int(sys.argv[2]) if len(sys.argv) > 2 else 25
    g = sys.argv[3] if len(sys.argv) > 3 else "op"
    rows, total = top_ops(d, n, g)
    print("total XLA-op device ms: %.2f" % total)
    for name, ms in rows:
        print("%8.2f ms  %5.1f%%  %s" % (ms, ms / total * 100, name[:90]))
