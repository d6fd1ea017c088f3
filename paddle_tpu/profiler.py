"""Profiler façade (reference: python/paddle/fluid/profiler.py — profiler
ctx mgr:221, start/stop_profiler:125,165, cuda_profiler:39).

Drives BOTH halves of the telemetry stack together:

* the **device** half: the JAX profiler, whose xplane traces load in
  TensorBoard/XProf and convert to chrome-trace JSON via
  ``observability.tracing.xplane_to_chrome_trace`` (the CUPTI +
  chrome-trace pipeline of the reference, SURVEY.md §5);
* the **host** half: paddle_tpu.observability spans (step → trace →
  transform/lower → compile/run) and the metrics registry. The session
  itself switches the spans on, and they land in the device trace too
  (``pt.<name>`` on ``/host:CPU``); ``start_profiler`` also forces the
  metric collectors on for the session even when ``PADDLE_TPU_METRICS``
  is down, and ``stop_profiler`` restores the flag-controlled gate.

``stop_profiler(sorted_key, profile_path)`` writes the host-span summary
table to ``profile_path`` sorted by ``sorted_key`` (calls / total / max /
min / ave — the reference's EventSortingKey set) and also dumps the host
spans as chrome-trace JSON next to it (``<profile_path>.trace.json``),
ready to merge with the device timeline.
"""

import contextlib
import os

import jax

from paddle_tpu import flags, observability

_trace_dir = None
_device_trace_on = False

_SORT_KEYS = {
    None: None,
    "default": None,
    "calls": "calls",
    "total": "total_ms",
    "max": "max_ms",
    "min": "min_ms",
    "ave": "ave_ms",
}


def start_profiler(state="All", tracer_option=None):
    """Start the device trace AND the host span/metric collectors
    (``state``/``tracer_option`` kept for reference API parity)."""
    global _trace_dir, _device_trace_on
    observability.set_enabled(True)
    _trace_dir = (flags.get_flag("trace_dir")
                  or os.environ.get("PADDLE_TPU_TRACE_DIR")
                  or "/tmp/paddle_tpu_trace")
    jax.profiler.start_trace(_trace_dir)
    _device_trace_on = True


def summary_table(sorted_key=None):
    """The host-span summary as text (reference:
    platform/profiler.cc PrintProfiler's table): one row per span name
    with calls / total / self (``tracing.self_time``) / min / max / ave
    milliseconds."""
    if sorted_key not in _SORT_KEYS:
        raise ValueError(
            "sorted_key must be one of %s, got %r"
            % (sorted(k for k in _SORT_KEYS if k), sorted_key))
    agg = observability.tracer.summary()
    rows = list(agg.items())
    field = _SORT_KEYS[sorted_key]
    if field is not None:
        rows.sort(key=lambda kv: kv[1][field], reverse=field != "min_ms")
    lines = ["%-32s %8s %12s %12s %12s %12s %12s"
             % ("Event", "Calls", "Total(ms)", "Self(ms)", "Min(ms)",
                "Max(ms)", "Ave(ms)")]
    for name, r in rows:
        lines.append("%-32s %8d %12.3f %12.3f %12.3f %12.3f %12.3f"
                     % (name[:32], r["calls"], r["total_ms"], r["self_ms"],
                        r["min_ms"], r["max_ms"], r["ave_ms"]))
    if not rows:
        lines.append("(no host spans recorded)")
    return "\n".join(lines)


def op_summary_text(table, top_k=15):
    """The op-attributed device-time table as text: one row per
    provenance tag (framework op), hottest first, with the roofline
    verdict and the source-op list fused ops expand back to — the
    replacement for staring at raw HLO fusion names."""
    from paddle_tpu.observability import opprof

    lines = [
        "Device time by framework op (source: %s, fusion policy: %s)"
        % (table["source"], table["fusion_policy"]),
        "%-36s %10s %6s %10s %-13s %s"
        % ("op", "ms", "%", "FLOP/B", "verdict", "src_ops")]
    for tag, row in opprof.top_rows(table, top_k):
        if row["ms"] <= 0:
            continue
        lines.append(
            "%-36s %10.3f %5.1f%% %10.2f %-13s %s"
            % (tag[:36], row["ms"], 100.0 * row["frac"],
               row["intensity"], row["verdict"],
               ",".join(row["src_ops"])[:40]))
    lines.append(
        "attributed %.1f%% of %.3f ms device time "
        "(unattributed %.3f ms, comm lane %.3f ms)"
        % (100.0 * table["attributed_frac"], table["total_ms"],
           table["unattributed_ms"], table["comm_ms"]))
    total = table["total_ms"] or 1.0
    lines += ["", "Device time by op type",
              "%-36s %10s %6s" % ("op type", "ms", "%")]
    by_type = sorted(table["by_type"].items(), key=lambda kv: -kv[1])
    for op_type, ms in by_type[:top_k]:
        lines.append("%-36s %10.3f %5.1f%%"
                     % (str(op_type)[:36], ms, 100.0 * ms / total))
    lines += ["", "Device time by phase (a fusion is booked whole to the "
              "op XLA names it after)"]
    phases = dict(table["by_phase"], unattributed=table["unattributed_ms"])
    for phase in opprof.PHASES + ("unattributed",):
        ms = phases.get(phase, 0.0)
        lines.append("%-36s %10.3f %5.1f%%"
                     % (phase, ms, 100.0 * ms / total))
    for pair, ms in sorted(table["mixed_phase_ms"].items()):
        lines.append("%-36s %10.3f %5.1f%%"
                     % ("of it in %s fusions" % pair, ms,
                        100.0 * ms / total))
    return "\n".join(lines)


def stop_profiler(sorted_key=None, profile_path="/tmp/profile"):
    """Stop both halves; write the host summary table PLUS the
    op-attributed device-time table (xplane time joined back to
    ProgramDesc ops via the opprof provenance tags, with roofline
    verdicts — no more raw HLO fusion names) to ``profile_path``
    honoring ``sorted_key`` (reference profiler.py:165 contract — the
    arguments are no longer ignored), the host spans as chrome-trace
    JSON to ``<profile_path>.trace.json``, and the metrics registry as
    Prometheus text exposition to ``<profile_path>.metrics.prom`` (the
    ``snapshot_text`` dump a scrape-less run still wants on disk). The
    provenance sidecar (``opprof_provenance.json``) lands next to the
    xplane dumps so perf_report --roofline attributes offline. An
    attached streaming sink is flushed so its JSONL tail is complete at
    the moment the session ends."""
    global _device_trace_on
    if _device_trace_on:
        jax.profiler.stop_trace()
        _device_trace_on = False
    op_table = None
    if _trace_dir and flags.get_flag("opprof"):
        from paddle_tpu.observability import opprof

        try:
            opprof.save_sidecar(_trace_dir)
            op_table = opprof.attribute(_trace_dir)
        except Exception:
            op_table = None
        if op_table is not None:
            observability.set_gauge("opprof.attributed_frac",
                                    op_table["attributed_frac"])
            observability.set_gauge("opprof.unattributed_ms",
                                    op_table["unattributed_ms"])
            observability.set_gauge("opprof.comm_ms",
                                    op_table["comm_ms"])
            for tag, row in opprof.top_rows(op_table, top_k=20):
                if row["ms"] > 0:
                    observability.set_gauge("opprof.%s_ms" % tag,
                                            row["ms"])
    table = summary_table(sorted_key)
    if op_table is not None:
        table += "\n\n" + op_summary_text(op_table)
    if profile_path:
        with open(profile_path, "w") as f:
            f.write(table + "\n")
        observability.dump_chrome_trace(profile_path + ".trace.json")
        # refresh the goodput.*/mfu.* gauges first so the exposition
        # dump carries the ledger, then append the human-readable
        # summary block (comment lines — any Prometheus parser skips
        # them) answering "where did the wall clock go" inline
        observability.goodput.publish()
        with open(profile_path + ".metrics.prom", "w") as f:
            f.write(observability.registry.snapshot_text())
            if observability.goodput.enabled():
                snap = observability.goodput.snapshot()
                f.write("# goodput ledger: %.2f%% of %.1f ms wall "
                        "(attempt %d)\n"
                        % (100.0 * snap["goodput_frac"], snap["wall_ms"],
                           snap["attempt"]))
                for cat, ms in sorted(snap["categories"].items(),
                                      key=lambda cm: -cm[1]):
                    if ms > 0:
                        f.write("#   %-16s %12.3f ms\n" % (cat, ms))
    # snap=True: the opprof.* gauges just set (and the final goodput
    # ledger) land in the sink's last snapshot for perf_report --merge
    observability.flush_sink(snap=True)
    observability.set_enabled(None)  # back to the PADDLE_TPU_METRICS gate
    if _trace_dir:
        print("profiler: device trace in %s (TensorBoard/XProf; "
              "observability.dump_chrome_trace converts), host summary "
              "in %s" % (_trace_dir, profile_path))


def reset_profiler():
    """Drop all recorded host spans and metrics (reference
    profiler.py:148 reset_profiler)."""
    observability.reset()


@contextlib.contextmanager
def profiler(state="All", sorted_key=None, profile_path="/tmp/profile",
             tracer_option=None):
    start_profiler(state, tracer_option)
    try:
        yield
    finally:
        stop_profiler(sorted_key, profile_path)


@contextlib.contextmanager
def cuda_profiler(output_file, output_mode=None, config=None):
    """Accelerator profiler passthrough (name kept for API compat)."""
    with profiler():
        yield


@contextlib.contextmanager
def record_event(name):
    """RAII span (reference: platform/profiler.h:82 RecordEvent) — lands
    in BOTH timelines: a host observability span, which a profiler
    session also writes into the device trace as ``pt.<name>``."""
    with observability.span(name):
        yield
