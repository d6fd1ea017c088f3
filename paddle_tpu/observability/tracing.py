"""Host-side span tracer: RAII wall-clock spans, nestable, exported as
chrome-trace JSON.

The host half of the reference's RecordEvent timeline (reference:
platform/profiler.h:82 RecordEvent + its timeline tool's chrome-trace
export): ``span("compile")`` records start + duration on exit, spans
nest per thread, and ``chrome_trace()`` emits the same event schema
``xplane_to_chrome_trace`` produces from the jax xplane dump — complete
("ph": "X") slices with microsecond timestamps — so a host dump and a
device trace load side by side in chrome://tracing / perfetto and line
up on the wall clock (both timebases are ns-since-epoch).

Span timestamps come from ``perf_counter_ns`` re-anchored to the epoch
once at import: monotonic durations, epoch-aligned starts.

While a JAX profiler session is on, a span is also written into the
profiler's own trace as ``pt.<name>`` (``jax.profiler.TraceAnnotation``;
a step span as a ``StepTraceAnnotation``), so it lands on ``/host:CPU``
on the clock of the device planes.
"""

import json
import threading
import time

from jax.profiler import StepTraceAnnotation, TraceAnnotation

from paddle_tpu.observability.export import (DEFAULT_FLIGHT_DEPTH,
                                             FlightRecorder)

# True while a JAX profiler session is recording host annotations
# (``jax.profiler.start_trace`` .. ``stop_trace``): a static method of the
# public class, about 80 ns a call.
session_on = TraceAnnotation.is_enabled

# JAX's own durations of a jitted function's first call, charged to the
# engine's first-call span as arguments (``SpanTracer.seam_span``).
JAX_DURATIONS = {
    "/jax/core/compile/jaxpr_trace_duration": "jax_trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jax_lower_s",
    "/jax/core/compile/backend_compile_duration": "backend_compile_s",
}
# What the persistent compilation cache did inside that first call's
# backend seconds: JAX names no function on these, so they are charged to
# the first-call span open on the reporting thread. A hit is counted and
# reports the two durations; a miss is counted where its entry is written.
CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": "cache_hits",
    "/jax/compilation_cache/cache_misses": "cache_misses",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_retrieval_s",
    "/jax/compilation_cache/compile_time_saved_sec": "compile_saved_s",
}

# perf_counter is monotonic but has an arbitrary zero; anchor it to the
# epoch once so span starts align with device-trace timestamps.
_EPOCH_ANCHOR_NS = time.time_ns() - time.perf_counter_ns()

# Finished spans are capped so a long serving loop with tracing left on
# degrades to "recent window + dropped count", never unbounded RAM.
# With a streaming sink attached (observability/export.py) the cap never
# bites: spans stream to disk and only the flight recorder stays in RAM.
MAX_SPANS = 100000


class SpanRecord:
    __slots__ = ("name", "ts_us", "dur_us", "tid", "depth", "args")

    def __init__(self, name, ts_us, dur_us, tid, depth, args):
        self.name = name
        self.ts_us = ts_us
        self.dur_us = dur_us
        self.tid = tid
        self.depth = depth
        self.args = args

    def __repr__(self):
        return "SpanRecord(%r, ts=%.1fus, dur=%.1fus, depth=%d)" % (
            self.name, self.ts_us, self.dur_us, self.depth)


class SpanTracer:
    def __init__(self, max_spans=MAX_SPANS, flight_depth=None):
        self._lock = threading.Lock()
        self._local = threading.local()
        self._spans = []
        self._dropped = 0
        self._max_spans = max_spans
        self._sink = None
        self._flight = FlightRecorder(flight_depth or DEFAULT_FLIGHT_DEPTH)
        # name of the most recently entered open span, process-wide —
        # the "what is this worker doing" field the health heartbeat
        # reports. Plain attribute write on span enter/exit (no lock:
        # an approximate label, read racily by the heartbeat thread).
        self._phase_name = None
        # open cache-miss seam spans (any thread): while one is open
        # every span is recorded, whatever is switched on
        self.seam_open = 0
        # the open first-call span JAX's durations are charged to
        self._first_call = None
        self._listening = False

    # -- record -----------------------------------------------------------
    def _stack(self):
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    def _add(self, rec):
        with self._lock:
            self._flight.add(rec)
            sink = self._sink
            if sink is not None:
                # Streaming mode: the span goes to the sink, RAM keeps
                # only the flight-recorder window — an unbounded loop
                # never drops and never grows.
                try:
                    sink.emit_span(rec)
                except Exception:
                    self._dropped += 1
                return
            if len(self._spans) >= self._max_spans:
                self._dropped += 1
                return
            self._spans.append(rec)

    def add_record(self, rec):
        """Record an externally built SpanRecord through the normal
        sink/flight/in-memory routing — the request tracer
        (observability/reqtrace) emits a kept trace's buffered spans
        through this, so ``trace.*`` spans reach the JSONL sink, the
        flight recorder, and the chrome-trace export exactly like
        natively recorded spans."""
        self._add(rec)

    # -- sink / flight recorder -------------------------------------------
    def attach_sink(self, sink):
        """Route finished spans to ``sink`` (export.JsonlSink protocol:
        ``emit_span(rec)``). Returns the previously attached sink (not
        closed — the caller owns lifecycle)."""
        with self._lock:
            prev, self._sink = self._sink, sink
            return prev

    def detach_sink(self):
        with self._lock:
            prev, self._sink = self._sink, None
            return prev

    @property
    def sink(self):
        return self._sink

    def flight(self):
        """The flight recorder's current window (most recent last)."""
        return self._flight.records()

    def set_flight_depth(self, depth):
        with self._lock:
            self._flight.resize(depth)

    @property
    def flight_depth(self):
        return self._flight.depth

    def span(self, name, **args):
        return _Span(self, name, args)

    def step_span(self, name, step):
        """A span of one step: in the profiler's trace it is a
        ``StepTraceAnnotation`` with ``step_num`` = ``step``."""
        return _Span(self, name, {"step": step}, step_num=step)

    def seam_span(self, name, fun_name=None, **args):
        """A span of the cache-miss seam (once an executable, never on a
        steady step): recorded whatever is switched on, and so is every
        span opened while it is open. With ``fun_name`` (a jitted
        function's ``__name__``) it is that function's first call: the
        seconds JAX reports for tracing, lowering and backend-compiling
        ``fun_name`` while the span is open become its arguments
        (``JAX_DURATIONS``), and so does what the compilation cache did
        on this thread meanwhile (``CACHE_EVENTS``). Its duration less the
        three ``JAX_DURATIONS`` is the rest of the first call: the
        arguments' transfer, the first execution's dispatch, whatever JAX
        does unnamed."""
        if fun_name is not None:
            self._listen()
        return _SeamSpan(self, name, args, fun_name)

    def _listen(self):
        with self._lock:
            if self._listening:
                return
            self._listening = True
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(
            self._charge_jax_duration)
        jax.monitoring.register_event_listener(self._charge_jax_count)

    def _charge_jax_duration(self, event, seconds, fun_name=None, **_):
        """The program's one ``jax.monitoring`` listener of durations: JAX
        names the tracing event by the function and the two later ones by
        ``jit(<function>)``; anything else (the benchmark's own jits,
        eager ops) is not the engine's."""
        span, key = self._first_call, JAX_DURATIONS.get(event)
        if key is None:
            return self._charge_cache(event, seconds)
        if span is None or fun_name not in (
                span.fun_name, "jit(%s)" % span.fun_name):
            return
        span.args[key] = span.args.get(key, 0.0) + seconds

    def _charge_jax_count(self, event, **_):
        """... and its one listener of plain events."""
        self._charge_cache(event, 1)

    def _charge_cache(self, event, amount):
        """The compilation cache's verdict (``CACHE_EVENTS``), from either
        listener: counts and seconds alike are summed into the argument."""
        span, key = self._first_call, CACHE_EVENTS.get(event)
        if (span is None or key is None
                or span.tid != threading.get_ident()):
            return
        span.args[key] = span.args.get(key, 0) + amount

    def current_phase(self):
        """The innermost open span's name (any thread), or None."""
        return self._phase_name

    def event(self, name, **args):
        """Zero-duration instant marker (chrome-trace "i" events) — e.g.
        a nan/inf-guard trip, a cache eviction."""
        now_us = (_EPOCH_ANCHOR_NS + time.perf_counter_ns()) / 1e3
        self._add(SpanRecord(name, now_us, 0.0, threading.get_ident(),
                             len(self._stack()), args or None))

    # -- read -------------------------------------------------------------
    def spans(self):
        """Recorded spans: the in-memory list, or — in streaming mode,
        where spans live on disk — the flight recorder's window."""
        with self._lock:
            if self._sink is not None:
                return self._flight.records()
            return list(self._spans)

    def dropped(self):
        with self._lock:
            return self._dropped

    def reset(self):
        with self._lock:
            self._spans = []
            self._dropped = 0
            self._flight.clear()
            self._phase_name = None

    def chrome_trace_events(self, pid=1, process_name="paddle_tpu host"):
        """Chrome-trace event dicts for every recorded span: per-process
        and per-thread name metadata, "X" slices for spans, "i" instants
        for zero-duration events."""
        spans = self.spans()
        events = [{"name": "process_name", "ph": "M", "pid": pid,
                   "args": {"name": process_name}}]
        tids = {}
        for s in spans:
            if s.tid not in tids:
                tids[s.tid] = len(tids)
                events.append({"name": "thread_name", "ph": "M", "pid": pid,
                               "tid": tids[s.tid],
                               "args": {"name": "host thread %d"
                                        % tids[s.tid]}})
        for s in spans:
            ev = {"name": s.name, "pid": pid, "tid": tids[s.tid],
                  "ts": s.ts_us}
            if s.dur_us > 0.0:
                ev["ph"] = "X"
                ev["dur"] = s.dur_us
            else:
                ev["ph"] = "i"
                ev["s"] = "t"
            if s.args:
                ev["args"] = dict(s.args)
            events.append(ev)
        return events

    def chrome_trace(self, xplane_dir=None):
        """Full chrome-trace dict. With ``xplane_dir`` the device planes
        (``xplane_to_chrome_trace`` below) are merged in as further
        processes — one file, host spans above the device lanes, shared
        wall clock."""
        events = self.chrome_trace_events()
        if xplane_dir is not None:
            device = xplane_to_chrome_trace(xplane_dir)["traceEvents"]
            for ev in device:
                ev = dict(ev)
                ev["pid"] = ev.get("pid", 1) + 1  # host trace owns pid 1
                events.append(ev)
        return {"traceEvents": events, "displayTimeUnit": "ms"}

    def dump_chrome_trace(self, path, xplane_dir=None):
        trace = self.chrome_trace(xplane_dir=xplane_dir)
        with open(path, "w") as f:
            json.dump(trace, f)
        return path

    def summary(self):
        """Aggregate by span name: {name: {calls, total_ms, self_ms,
        min_ms, max_ms, ave_ms}} — the reference profiler's summary-table
        rows (reference: platform/profiler.cc PrintProfiler); ``self_ms``
        is ``self_time``'s."""
        agg = {}
        spans = self.spans()
        own = self_time(spans)
        for s in spans:
            row = agg.setdefault(s.name, {"calls": 0, "total_ms": 0.0,
                                          "self_ms": own[s.name] / 1e3,
                                          "min_ms": None, "max_ms": None})
            ms = s.dur_us / 1e3
            row["calls"] += 1
            row["total_ms"] += ms
            row["min_ms"] = ms if row["min_ms"] is None else min(
                row["min_ms"], ms)
            row["max_ms"] = ms if row["max_ms"] is None else max(
                row["max_ms"], ms)
        for row in agg.values():
            row["ave_ms"] = row["total_ms"] / row["calls"]
        return agg


def self_time(spans):
    """{span name: microseconds} of self time: each span's duration minus
    the part of it that its child spans on the same thread cover, summed
    by name. A child is a span that lies within another of its thread;
    zero-duration events count for nothing."""
    out = {}
    by_tid = {}
    for s in spans:
        out.setdefault(s.name, 0.0)
        if s.dur_us > 0.0:
            by_tid.setdefault(s.tid, []).append(s)
    for rows in by_tid.values():
        # a parent starts no later and ends no earlier than its child;
        # of two spans with one start the longer is the parent
        rows.sort(key=lambda s: (s.ts_us, -s.dur_us))
        stack = []  # the open ancestors: (end_us, name)
        for s in rows:
            end = s.ts_us + s.dur_us
            # the clock's rounding may end a child a hair after its parent
            while stack and stack[-1][0] < end - 1e-3:
                stack.pop()
            if stack:
                out[stack[-1][1]] -= s.dur_us
            out[s.name] += s.dur_us
            stack.append((end, s.name))
    return out


def xplane_to_chrome_trace(trace_dir, line_filter=None):
    """-> chrome-trace dict {"traceEvents": [...], "displayTimeUnit":
    "ms"} from every distinct .xplane.pb under ``trace_dir``
    (byte-identical duplicate dumps are skipped by the shared plane
    iterator). Every plane becomes a chrome "process", every line a
    "thread", events map to complete ("X") slices with microsecond
    timestamps on the profiler's own clock (``ProfileData`` counts from
    the session's start, not from the epoch): the host spans that line up
    with the device lanes are the ``pt.<name>`` events the session wrote
    on ``/host:CPU``, in the same file.
    ``line_filter`` (substring, e.g. "XLA Ops") keeps matching lines
    only. The package owns ONE trace-export entry point
    (``dump_chrome_trace(path, xplane_dir)``)."""
    from paddle_tpu.observability.opprof import iter_planes

    events = []
    for pid, plane in enumerate(iter_planes(trace_dir), start=1):
        events.append({"name": "process_name", "ph": "M", "pid": pid,
                       "args": {"name": plane.name}})
        for tid, line in enumerate(plane.lines):
            if line_filter and line_filter not in line.name:
                continue
            events.append({"name": "thread_name", "ph": "M",
                           "pid": pid, "tid": tid,
                           "args": {"name": line.name}})
            for e in line.events:
                events.append({
                    "name": e.name,
                    "ph": "X",
                    "pid": pid,
                    "tid": tid,
                    "ts": e.start_ns / 1e3,     # us
                    "dur": e.duration_ns / 1e3,  # us
                })
    return {"traceEvents": events, "displayTimeUnit": "ms"}


class _Span:
    """RAII span: start on __enter__, record on __exit__ (also usable as
    a decorator-free plain object for manual begin/end). While a profiler
    session is on it is written into the profiler's trace too."""

    __slots__ = ("tracer", "name", "args", "step_num", "_t0_ns", "_depth",
                 "_annotation")

    def __init__(self, tracer, name, args, step_num=None):
        self.tracer = tracer
        self.name = name
        self.args = args or None
        self.step_num = step_num

    def __enter__(self):
        stack = self.tracer._stack()
        self._depth = len(stack)
        stack.append(self)
        self.tracer._phase_name = self.name
        self._annotation = None
        if session_on():
            if self.step_num is None:
                self._annotation = TraceAnnotation(
                    "pt." + self.name, **(self.args or {}))
            else:
                self._annotation = StepTraceAnnotation(
                    "pt." + self.name, step_num=self.step_num)
            self._annotation.__enter__()
        self._t0_ns = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc, tb):
        dur_ns = time.perf_counter_ns() - self._t0_ns
        if self._annotation is not None:
            self._annotation.__exit__(exc_type, exc, tb)
        stack = self.tracer._stack()
        if stack and stack[-1] is self:
            stack.pop()
        self.tracer._phase_name = stack[-1].name if stack else None
        self.tracer._add(SpanRecord(
            self.name, (_EPOCH_ANCHOR_NS + self._t0_ns) / 1e3,
            dur_ns / 1e3, threading.get_ident(), self._depth, self.args))
        return False


class _SeamSpan(_Span):
    """A span of the cache-miss seam (``SpanTracer.seam_span``)."""

    __slots__ = ("fun_name", "tid", "_outer")

    def __init__(self, tracer, name, args, fun_name=None):
        super().__init__(tracer, name, args)
        self.fun_name = fun_name
        if fun_name is not None:
            self.args = dict(args, fun_name=fun_name)

    def __enter__(self):
        self.tracer.seam_open += 1
        if self.fun_name is not None:
            self.tid = threading.get_ident()
            self._outer, self.tracer._first_call = (
                self.tracer._first_call, self)
        return super().__enter__()

    def __exit__(self, exc_type, exc, tb):
        self.tracer.seam_open -= 1
        if self.fun_name is not None:
            self.tracer._first_call = self._outer
        return super().__exit__(exc_type, exc, tb)
