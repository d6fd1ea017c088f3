"""paddle_tpu.observability — runtime telemetry across the engine seams.

Two pieces (SURVEY §5 — the host half the device-side jax profiler does
not cover):

* a **metrics registry** (metrics.py): thread-safe counters / gauges /
  timing histograms. The engine records cache hit/miss/eviction, compile
  and per-step run wall time, feed/fetch byte counts and nan/inf-guard
  trips; the transform pipeline records per-pass wall time and
  rewrite-fire counts; the lowering records op counts.
* a **span tracer** (tracing.py): RAII host spans (step → trace →
  transform/verify/lower → compile/run), exportable as chrome-trace
  JSON that merges with the xplane device traces
  ``tracing.xplane_to_chrome_trace`` converts.

Everything is gated by ``PADDLE_TPU_METRICS`` (flags.py): with the flag
down every helper here is one module-bool check — no locks, no
allocation — so the instrumented seams stay at PR-2 latency
(tools/marginal_timing.py verifies the off path). The gate is cached in
``_ENABLED`` and kept fresh by a flags change-hook, so
``flags.set_flags({"metrics": True})`` takes effect immediately;
``PADDLE_TPU_METRICS=1`` in the environment is read once at import.

Spans alone have a second switch: a JAX profiler session
(``jax.profiler.start_trace`` .. ``stop_trace``, whoever started it).
While one is on, ``span`` is live with the flag down, and every live span
is also written into the profiler's trace as ``pt.<name>``, on the clock
of the device planes. Counters, histograms, the memory census and the
rest stay on the flag, so a traced slice carries spans and nothing
heavier. The seams (``seam_span``: once an executable, ``trace`` and
its first call ``compile``; once a Program, ``minimize`` and
``append_backward``) are recorded in memory whatever is switched on, and
so is every span opened while one is open: a first call holds one span
``op:<type>`` for every Fluid op lowered under JAX's trace.

Entry points: ``snapshot()``, ``dump_chrome_trace(path)``,
``inc/observe/set_gauge/time_block``, ``span/event``, ``reset()``.
``paddle_tpu.profiler`` is the user-facing façade that starts/stops
these host spans together with the jax device trace.
"""

from paddle_tpu import flags
from paddle_tpu.observability import (  # noqa: F401
    export,
    goodput,
    health,
    memory,
    opprof,
    reqtrace,
)
from paddle_tpu.observability.export import (  # noqa: F401
    FlightRecorder,
    JsonlSink,
)
from paddle_tpu.observability.metrics import (  # noqa: F401
    NULL_BLOCK,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    _TimeBlock,
    snapshot_text,
)
from paddle_tpu.observability.tracing import (  # noqa: F401
    SpanRecord,
    SpanTracer,
    self_time,
    session_on,
)

__all__ = [
    "FlightRecorder", "JsonlSink", "MetricsRegistry", "SpanTracer",
    "attach_sink", "counter_value", "detach_sink", "dump_chrome_trace",
    "enabled", "event", "flush_sink", "goodput", "inc", "observe",
    "opprof", "registry", "reqtrace",
    "health", "reset", "seam_span", "self_time", "set_enabled",
    "set_gauge", "sink", "snapshot", "snapshot_text", "span", "spans",
    "spans_live", "step_span", "time_block", "tracer",
]

registry = MetricsRegistry()
tracer = SpanTracer(flight_depth=int(flags.get_flag("flight_recorder_depth")))

_ENABLED = bool(flags.get_flag("metrics"))


def set_enabled(value=None):
    """Override the gate (``True``/``False``) or re-read the flag
    (``None``). The profiler façade forces the gate up for the duration
    of an explicit profiling session regardless of the flag."""
    global _ENABLED
    _ENABLED = (bool(flags.get_flag("metrics")) if value is None
                else bool(value))


flags.on_change("metrics", lambda _v: set_enabled(None))


def enabled():
    return _ENABLED


# -- streaming sink --------------------------------------------------------
def sink():
    """The active streaming sink, or None."""
    return tracer.sink


def attach_sink(path=None, host=None, **kwargs):
    """Attach a rotating JSONL sink (export.JsonlSink) to the tracer:
    finished spans/events stream to disk, tracer memory stays bounded at
    the flight-recorder depth, ``dropped()`` stays 0 on unbounded loops.

    ``path`` defaults to the ``PADDLE_TPU_METRICS_SINK`` flag; returns
    None (and detaches nothing) when neither is set. Multi-process runs
    (``host`` passed, or a launcher rank in the environment) write to
    the host-tagged ``<base>.h<rank><ext>`` so per-worker dumps merge
    cleanly (tools/perf_report.py --merge). Any previous sink is closed.
    """
    import os

    path = path or flags.get_flag("metrics_sink")
    if not path:
        return None
    explicit = host is not None
    host = export.host_tag() if host is None else int(host)
    try:
        world = int(os.environ.get(
            "PADDLE_TRAINERS_NUM", os.environ.get("WORLD_SIZE") or 1))
    except ValueError:
        world = 1
    if explicit or host or world > 1:
        path = export.host_tagged_path(path, host)
    kwargs.setdefault(
        "rotate_bytes",
        int(float(flags.get_flag("metrics_sink_rotate_mb")) * 2 ** 20))
    kwargs.setdefault("keep", int(flags.get_flag("metrics_sink_keep")))
    kwargs.setdefault("snapshot_fn", registry.snapshot)
    new = JsonlSink(path, host=host, **kwargs)
    prev = tracer.attach_sink(new)
    if prev is not None:
        try:
            prev.close()
        except Exception:
            pass
    return new


def detach_sink():
    """Detach and close the active sink (final metric snapshot + flush
    included). Returns the closed sink, or None."""
    prev = tracer.detach_sink()
    if prev is not None:
        try:
            prev.close()
        except Exception:
            pass
    return prev


def flush_sink(snap=False):
    """Flush the active sink; ``snap=True`` also forces a metrics
    snapshot first — a run's exit seams use it so the FINAL gauge
    values (goodput ledger, watermarks) land on disk even when the
    process never detaches the sink."""
    s = tracer.sink
    if s is not None:
        if snap:
            try:
                s.emit_snapshot(force=True)
            except Exception:
                pass
        s.flush()


def _sink_flag_changed(value):
    if value:
        attach_sink(value)
    else:
        detach_sink()


flags.on_change("metrics_sink", _sink_flag_changed)
flags.on_change("flight_recorder_depth",
                lambda v: tracer.set_flight_depth(int(v)))

if flags.get_flag("metrics_sink"):
    # PADDLE_TPU_METRICS_SINK in the environment: stream from import on.
    attach_sink()

flags.on_change("heartbeat_ms", lambda _v: health.ensure_heartbeat())

if float(flags.get_flag("heartbeat_ms") or 0) > 0:
    # PADDLE_TPU_HEARTBEAT_MS in the environment (the supervised
    # launcher sets it per worker): liveness beats from import on.
    health.ensure_heartbeat()


# -- metrics ---------------------------------------------------------------
def inc(name, n=1):
    if _ENABLED:
        registry.inc(name, n)


def set_gauge(name, value, exemplar=None):
    if _ENABLED:
        registry.set_gauge(name, value, exemplar)


def observe(name, value, exemplar=None):
    if _ENABLED:
        registry.observe(name, value, exemplar)


def time_block(name):
    """Ctx mgr recording the block's wall time (ms) into histogram
    ``name`` — a metric only, no span."""
    if not _ENABLED:
        return NULL_BLOCK
    return _TimeBlock(registry, name)


def counter_value(name, default=0):
    return registry.counter_value(name, default)


# -- spans -----------------------------------------------------------------
def spans_live():
    """Whether ``span`` records: the flag is up, a JAX profiler session
    is on, or a cache-miss seam span is open."""
    return _ENABLED or tracer.seam_open > 0 or session_on()


def span(name, **args):
    """RAII host span: wall start + duration, nests per thread."""
    if not spans_live():
        return NULL_BLOCK
    return tracer.span(name, **args)


def step_span(name, step):
    """``span`` for one step of the program: a ``StepTraceAnnotation``
    (``step_num`` = ``step``) in the profiler's trace."""
    if not spans_live():
        return NULL_BLOCK
    return tracer.step_span(name, step)


def seam_span(name, fun_name=None, **args):
    """A span of the cache-miss seam, always recorded
    (``SpanTracer.seam_span``)."""
    return tracer.seam_span(name, fun_name=fun_name, **args)


def event(name, **args):
    """Zero-duration instant marker in the trace."""
    if _ENABLED:
        tracer.event(name, **args)


def spans():
    return tracer.spans()


# -- export ----------------------------------------------------------------
def snapshot():
    """One plain dict of everything recorded: counters, gauges,
    histogram summaries, and the per-span-name aggregate."""
    out = registry.snapshot()
    out["spans"] = tracer.summary()
    dropped = tracer.dropped()
    if dropped:
        out["dropped_spans"] = dropped
    return out


def dump_chrome_trace(path, xplane_dir=None):
    """Write the host spans as chrome-trace JSON (load in
    chrome://tracing or perfetto). With ``xplane_dir`` the device planes
    are merged into the same file as additional processes."""
    return tracer.dump_chrome_trace(path, xplane_dir=xplane_dir)


def reset():
    """Drop all recorded metrics AND spans (test isolation; the
    conftest fixture calls this around every test). Memory watermarks
    reset too; an attached sink stays attached (stream files are
    append-only history, not registry state)."""
    registry.reset()
    tracer.reset()
    memory.reset_peaks()
    goodput.reset()
    reqtrace.reset()
