"""Liveness analysis over the def-use graph.

The desc-level mirror of the reference's ``memory_optimize`` pass
(reference: paddle/fluid/framework/details/memory_optimize_pass.cc and
transpiler/memory_optimization_transpiler.py, which reuse buffers by
lifetime analysis). Under XLA the buffer reuse itself is automatic;
``analyze_liveness`` gives per-var live intervals over the graph's global
program order, a peak-bytes estimate from an event sweep, and the top
contributors live at the peak. ``tools/lint_program.py --memory`` prints
it, the ``memory-liveness`` pass reports it, and ``analysis/spmd.py``
builds its per-device peak on it.
"""

import numpy as np

from paddle_tpu.analysis.graph import build_graph
from paddle_tpu.core.types import VarType, convert_dtype_to_np

__all__ = ["LiveInterval", "LivenessReport", "analyze_liveness"]

# Var kinds that never hold a dense tensor at run time (passes.py keeps
# the authoritative set; this is the subset relevant to byte accounting).
_NON_TENSOR_TYPES = frozenset({
    VarType.READER, VarType.RAW, VarType.STEP_SCOPES,
    VarType.LOD_RANK_TABLE, VarType.PLACE_LIST, VarType.FEED_MINIBATCH,
    VarType.FETCH_LIST, VarType.TUPLE,
})


def _var_nbytes(var_node, dim_hints, default_dim=1):
    """Static byte size of a var from its VarDesc, or 0 when unknowable
    (undeclared, non-tensor, shapeless). Dynamic ``-1`` dims resolve from
    ``dim_hints`` (name -> concrete shape, usually the feed shapes) or
    fall back to ``default_dim`` — the estimate stays a lower bound
    rather than guessing a batch."""
    vd = var_node.desc
    if vd is None or vd.type in _NON_TENSOR_TYPES:
        return 0
    shape = vd.shape
    if shape is None:
        return 0
    hint = dim_hints.get(var_node.name)
    n = 1
    for i, d in enumerate(shape):
        d = int(d) if d is not None else -1
        if d < 0:
            if hint is not None and i < len(hint):
                d = int(hint[i])
            else:
                d = default_dim
        n *= max(d, 0)
    try:
        itemsize = np.dtype(convert_dtype_to_np(vd.dtype)).itemsize
    except Exception:
        itemsize = 4
    return n * itemsize


class LiveInterval:
    """One var's lifetime in global program order: ``[start, end]``
    inclusive, both op orders; persistable state is pinned for the whole
    program (the scope holds it across steps)."""

    __slots__ = ("name", "start", "end", "nbytes", "persistable")

    def __init__(self, name, start, end, nbytes, persistable):
        self.name = name
        self.start = start
        self.end = end
        self.nbytes = nbytes
        self.persistable = persistable

    def __repr__(self):
        return "LiveInterval(%s [%d,%d] %dB%s)" % (
            self.name, self.start, self.end, self.nbytes,
            " persistable" if self.persistable else "")


class LivenessReport:
    def __init__(self, intervals, peak_bytes, peak_order, n_orders):
        self.intervals = intervals  # name -> LiveInterval
        self.peak_bytes = peak_bytes
        self.peak_order = peak_order
        self.n_orders = n_orders

    def live_at(self, order):
        return [iv for iv in self.intervals.values()
                if iv.start <= order <= iv.end and iv.nbytes > 0]

    def top_contributors(self, n=10):
        """The vars live at the peak, largest first — the report line
        that tells you WHAT to remat/donate/shrink."""
        at_peak = self.live_at(self.peak_order)
        at_peak.sort(key=lambda iv: (-iv.nbytes, iv.name))
        return at_peak[:n]

    def render(self, top=10):
        lines = ["liveness: %d vars tracked over %d ops, peak %s at op "
                 "order %d" % (len(self.intervals), self.n_orders,
                               _fmt_bytes(self.peak_bytes),
                               self.peak_order)]
        for iv in self.top_contributors(top):
            lines.append("  %-12s %-40s live [%d, %d]%s" % (
                _fmt_bytes(iv.nbytes), iv.name, iv.start, iv.end,
                "  (persistable)" if iv.persistable else ""))
        return "\n".join(lines)


def analyze_liveness(graph_or_program, feed_shapes=None, default_dim=None):
    """Liveness over the def-use graph: each tracked var is born at its
    first writer (order 0 for feeds/persistables/scope state — they
    arrive materialized) and dies after its last reader/writer
    (program end for persistables and grads of persistables' updates
    written back to the scope). Peak bytes come from an event sweep over
    the interval set."""
    graph = (graph_or_program if hasattr(graph_or_program, "op_nodes")
             else build_graph(graph_or_program))
    feed_shapes = feed_shapes or {}
    if default_dim is None:
        # dynamic -1 dims on activations are the batch the data layers
        # declare; the largest leading feed dim is that batch
        default_dim = max((int(s[0]) for s in feed_shapes.values()
                           if len(s) and int(s[0]) > 0), default=1)
    max_order = max((op.order for op in graph.op_nodes), default=0)

    intervals = {}
    for v in graph.all_vars():
        if not v.writers and not v.readers:
            continue  # declared but unreferenced — never materialized
        nbytes = _var_nbytes(v, feed_shapes, default_dim=default_dim)
        persistable = v.persistable
        if persistable or not v.writers or v.name in feed_shapes:
            start = 0
        else:
            start = min(w.order for w in v.writers)
        accesses = [o.order for o in v.writers] + [o.order for o in v.readers]
        end = max_order if persistable else max(accesses)
        # last-writer-only vars (unfetched state_out) still occupy their
        # buffer until the write happens; interval is [start, end] as-is
        if v.name in intervals:
            # same name in two blocks: merge conservatively
            old = intervals[v.name]
            intervals[v.name] = LiveInterval(
                v.name, min(old.start, start), max(old.end, end),
                max(old.nbytes, nbytes), old.persistable or persistable)
        else:
            intervals[v.name] = LiveInterval(v.name, start, end, nbytes,
                                             persistable)

    # event sweep: +bytes at start, -bytes after end
    births, deaths = {}, {}
    for iv in intervals.values():
        if iv.nbytes <= 0:
            continue
        births[iv.start] = births.get(iv.start, 0) + iv.nbytes
        deaths[iv.end + 1] = deaths.get(iv.end + 1, 0) + iv.nbytes
    peak, peak_order, running = 0, 0, 0
    for order in range(0, max_order + 2):
        running += births.get(order, 0) - deaths.get(order, 0)
        if running > peak:
            peak, peak_order = running, order
    return LivenessReport(intervals, peak, peak_order, max_order + 1)


def _fmt_bytes(n):
    n = float(n)
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if abs(n) < 1024 or unit == "TiB":
            return "%dB" % int(n) if unit == "B" else "%.2f%s" % (n, unit)
        n /= 1024.0


# -- registry checker -------------------------------------------------------
# Registered (so lint/verify tooling can opt in) but NOT in
# DEFAULT_PASSES: it reports facts, not defects.
from paddle_tpu.analysis.passes import Pass, register_pass
from paddle_tpu.analysis.diagnostics import Severity


@register_pass("memory-liveness")
class MemoryLivenessPass(Pass):
    """INFO-only reporter: peak-bytes estimate + the top contributor, so
    a ``--verify`` or lint run surfaces the memory profile next to the
    correctness findings."""

    def check(self, graph, ctx):
        feed_shapes = {}
        rep = analyze_liveness(graph, feed_shapes=feed_shapes)
        findings = [self.finding(
            Severity.INFO,
            "estimated peak %s at op order %d (%d tracked vars)"
            % (_fmt_bytes(rep.peak_bytes), rep.peak_order,
               len(rep.intervals)),
            hint="tools/lint_program.py --memory prints the full report")]
        top = rep.top_contributors(1)
        if top:
            findings.append(self.finding(
                Severity.INFO,
                "largest live buffer at peak: %s (%s)"
                % (top[0].name, _fmt_bytes(top[0].nbytes)),
                var_names=[top[0].name]))
        return findings
