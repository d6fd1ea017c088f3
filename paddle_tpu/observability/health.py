"""Health & liveness layer: heartbeats, the hung-worker classifier, and
the serving SLO monitor.

The PR 5 gang supervisor only learns a worker is sick when its process
exits — a rank that deadlocks in a collective or silently stalls hangs
the whole gang forever, the failure mode pod-scale training says
dominates in production (PAPERS.md arXiv:1909.09756; the TF
fault-tolerance design, arXiv:1605.08695 §4.3). This module turns the
passive telemetry layer (PR 3/4) into active supervision. Three pieces:

* **HeartbeatEmitter** — a per-rank daemon thread that periodically
  writes ``health.heartbeat`` events (monotonic step counter, current
  span phase, host RSS, ``hbm.*`` watermark, serving queue depth)
  through the existing sink/flight-recorder path and flushes the sink
  so a live tail sees them. Gated by ``PADDLE_TPU_HEARTBEAT_MS``;
  ``distributed/launch.py supervise`` auto-enables it for workers when
  a metrics sink is configured. Heartbeats bypass the
  ``PADDLE_TPU_METRICS`` gate on purpose: liveness is not optional
  telemetry (the ``health.heartbeats`` *counter* still rides the gate).

* **RankHealth / HealthMonitor** — the supervisor side: one
  rotation-safe ``SinkTail`` per rank (export.py) feeding a stall
  classifier. A rank is **hung** when its heartbeats stay fresh but its
  step counter has not advanced past ``PADDLE_TPU_HANG_TIMEOUT_S``
  (default 0 = auto: ``HANG_EWMA_MULT`` × the rank's recent
  step-latency EWMA, floored at a few heartbeat intervals — and at a
  conservative ``DEFAULT_HANG_TIMEOUT_S`` before any step has completed,
  so a long first compile is never misread as a hang). **Dead** = no
  heartbeat within ``DEAD_INTERVALS`` expected gaps; a rank that has
  not beaten *this incarnation* gets a ``START_GRACE_S`` grace
  (heartbeats older than the monitor's ``started_at`` are a previous
  incarnation's and never count). ``wait_gang(monitor=...)`` terminates
  a gang with a hung/dead-but-running rank and returns
  ``HUNG_EXIT_CODE`` so ``supervise`` restarts it like any failure.

* **SloMonitor** — serving-side multi-window burn-rate alerting (the
  SRE fast/slow-window recipe) over per-request latencies against a
  configured SLO (``PADDLE_TPU_SERVING_SLO_MS``): burn rate = the
  window's violation fraction over the error budget (1 − target);
  sustained burn in BOTH windows fires an edge-triggered
  ``health.slo_burn`` event and flips ``InferenceServer.health()``
  unhealthy — the load-balancer readiness probe.

Everything here is deliberately cheap on the step path: the engine's
only per-step calls are ``note_step()`` — or, under multi-step dispatch,
``note_step_enqueued()``/``note_step_retired()`` — one int increment +
one clock read each; emitting and classifying run on daemon/supervisor
threads. The hang classifier reads the RETIRED counter (the heartbeat's
``step`` field), so an N-deep async dispatch window (engine/pipeline.py)
never reads as a stall while results are legitimately in flight.
"""

import collections
import os
import threading
import time

from paddle_tpu.observability.export import SinkTail  # noqa: F401

HEARTBEAT_EVENT = "health.heartbeat"

STATUS_STARTING = "starting"
STATUS_ALIVE = "alive"
STATUS_HUNG = "hung"
STATUS_DEAD = "dead"

#: wait_gang's rc for "terminated because the HealthMonitor classified a
#: live rank hung/dead" (faultinject.KILLED_EXIT_CODE is 43).
HUNG_EXIT_CODE = 44

#: heartbeat interval supervise auto-enables for workers when a metrics
#: sink is configured and PADDLE_TPU_HEARTBEAT_MS is not set.
DEFAULT_SUPERVISED_HEARTBEAT_MS = 1000.0

#: hang threshold before any step-latency EWMA exists: a worker's first
#: step legitimately carries the whole XLA compile, so the pre-EWMA
#: default must comfortably exceed a cold compile.
DEFAULT_HANG_TIMEOUT_S = 300.0
#: auto hang threshold once an EWMA exists: this many recent-step-times
#: without the counter moving.
HANG_EWMA_MULT = 20.0
#: ...floored at this many heartbeat gaps (step advances are only
#: *observed* once per heartbeat, so a timeout under a few gaps would
#: misfire on sampling jitter alone).
HANG_MIN_INTERVALS = 3.0
#: dead = no heartbeat for this many expected gaps (>= DEAD_MIN_S).
DEAD_INTERVALS = 5.0
DEAD_MIN_S = 2.0
#: grace before a rank that never heartbeated this incarnation is dead:
#: covers interpreter + jax import before observability comes up.
START_GRACE_S = 60.0

EWMA_ALPHA = 0.3

# -- the per-rank step counter the heartbeat reports ------------------------
# Plain dict mutation under the GIL: these notes are the only calls on
# the engine's step path and must stay in the ns regime. Multi-step
# dispatch (engine/pipeline.py)
# splits "a step happened" into two edges: ENQUEUED when the host hands
# the step to the device queue, RETIRED when its results materialize.
# The hang classifier reads RETIRED ("step" in the heartbeat payload) —
# an N-deep in-flight window advances its enqueue counter ahead of
# retirement without ever reading as a stall, while a genuinely wedged
# device stalls the retire edge no matter how deep the window is.
_step_state = {"steps": 0, "enqueued": 0, "ts": None, "enq_ts": None}


def note_step():
    """Record one synchronously completed engine step (enqueue and
    retire are the same edge at dispatch depth 1)."""
    note_step_enqueued()
    note_step_retired()


def note_step_enqueued():
    """The host dispatched a step into the device queue (results may
    still be in flight)."""
    _step_state["enqueued"] += 1
    _step_state["enq_ts"] = time.monotonic()


def note_step_retired():
    """A dispatched step's results materialized (window retire/sync)."""
    _step_state["steps"] += 1
    _step_state["ts"] = time.monotonic()


def step_count():
    """Retired steps — the liveness counter the watchdog classifies."""
    return _step_state["steps"]


def enqueued_count():
    return _step_state["enqueued"]


def reset_steps():
    """Test/bench isolation for the process-local step counters."""
    _step_state["steps"] = 0
    _step_state["enqueued"] = 0
    _step_state["ts"] = None
    _step_state["enq_ts"] = None


def host_rss_bytes():
    """This process's resident set size, or None where unreadable."""
    try:
        with open("/proc/self/statm") as f:
            pages = int(f.read().split()[1])
        return pages * os.sysconf("SC_PAGE_SIZE")
    except (OSError, ValueError, IndexError):
        pass
    try:
        import resource

        # ru_maxrss is KiB on Linux (a peak, not current — close enough
        # for the trend the heartbeat carries)
        return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
    except Exception:
        return None


# -- heartbeat emitter -------------------------------------------------------
class HeartbeatEmitter:
    """Daemon thread writing one ``health.heartbeat`` event per interval
    through the tracer (sink + flight recorder), flushing the sink so a
    supervisor tailing the file sees the beat immediately."""

    def __init__(self, interval_ms=None, host=None):
        from paddle_tpu import flags
        from paddle_tpu.observability import export

        if interval_ms is None:
            interval_ms = float(flags.get_flag("heartbeat_ms"))
        self.interval_ms = float(interval_ms)
        self.host = export.host_tag() if host is None else int(host)
        self._seq = 0
        self._stop = threading.Event()
        self._thread = None

    @property
    def running(self):
        t = self._thread
        return t is not None and t.is_alive()

    def start(self):
        if self.running:
            return self
        self._stop.clear()
        self._thread = threading.Thread(
            target=self._loop, name="paddle-tpu-heartbeat", daemon=True)
        self._thread.start()
        return self

    def stop(self):
        self._stop.set()
        t = self._thread
        if t is not None and t is not threading.current_thread():
            t.join(timeout=5.0)
        self._thread = None

    def emit_now(self):
        """Build and emit one heartbeat; returns the payload dict."""
        from paddle_tpu import observability as obs

        self._seq += 1
        # "step" is the RETIRED count — what RankHealth classifies hangs
        # on; "enqueued" rides along so a tailing supervisor can see the
        # in-flight dispatch window depth (enqueued - step).
        payload = {"seq": self._seq, "step": _step_state["steps"],
                   "enqueued": _step_state["enqueued"],
                   "interval_ms": self.interval_ms}
        payload["phase"] = obs.tracer.current_phase() or "idle"
        rss = host_rss_bytes()
        if rss:
            payload["rss_bytes"] = int(rss)
        try:
            from paddle_tpu.observability import memory

            peak = memory.peak_hbm_bytes()
            if peak:
                payload["hbm_peak_bytes"] = int(peak)
        except Exception:
            pass
        depth = obs.registry.gauge_value("serving.queue_depth")
        if depth is not None:
            payload["queue_depth"] = depth
        # direct tracer call, NOT obs.event: liveness must flow even with
        # PADDLE_TPU_METRICS down. The counter below does ride the gate.
        obs.tracer.event(HEARTBEAT_EVENT, **payload)
        obs.inc("health.heartbeats")
        try:
            obs.flush_sink()
        except Exception:
            pass
        return payload

    def _loop(self):
        interval = max(0.01, self.interval_ms / 1000.0)
        while not self._stop.wait(interval):
            try:
                self.emit_now()
            except Exception:
                # a sick emitter must never take the worker down with it
                pass


_emitter = None


def heartbeat_emitter():
    """The process's singleton emitter, or None."""
    return _emitter


def ensure_heartbeat(interval_ms=None):
    """Start/retune/stop the singleton from ``interval_ms`` (default:
    the ``heartbeat_ms`` flag; <= 0 stops). The flags change-hook and
    the observability import both route here, so the env var the
    supervised launcher sets takes effect at worker import."""
    global _emitter
    from paddle_tpu import flags

    if interval_ms is None:
        interval_ms = float(flags.get_flag("heartbeat_ms"))
    interval_ms = float(interval_ms)
    if interval_ms <= 0:
        stop_heartbeat()
        return None
    if _emitter is not None and _emitter.running \
            and _emitter.interval_ms == interval_ms:
        return _emitter
    stop_heartbeat()
    _emitter = HeartbeatEmitter(interval_ms=interval_ms).start()
    return _emitter


def stop_heartbeat():
    global _emitter
    if _emitter is not None:
        _emitter.stop()
        _emitter = None


# -- stall classifier --------------------------------------------------------
def _ewma(prev, x, alpha=EWMA_ALPHA):
    return x if prev is None else alpha * x + (1.0 - alpha) * prev


class RankHealth:
    """One rank's liveness state, fed from its heartbeat events.

    Timestamps are epoch seconds (sink event ``ts`` fields are epoch
    microseconds; ``observe`` converts). The classifier is pure state +
    ``status(now)`` so tests drive it with synthetic clocks.
    """

    def __init__(self, rank, heartbeat_ms=None):
        self.rank = rank
        self.heartbeat_ms = (float(heartbeat_ms)
                             if heartbeat_ms else
                             DEFAULT_SUPERVISED_HEARTBEAT_MS)
        self.hb_count = 0
        self.first_hb_ts = None
        self.last_hb_ts = None
        self.last_step = None
        self.step_advance_ts = None   # when the counter last CHANGED
        self.ewma_step_s = None       # recent seconds-per-step
        self.ewma_hb_gap_s = None     # observed heartbeat cadence

    def observe(self, ev):
        """Consume one sink event dict (ignores non-heartbeats)."""
        if ev.get("name") != HEARTBEAT_EVENT:
            return
        ts = float(ev.get("ts") or 0.0) / 1e6
        args = ev.get("args") or {}
        if self.last_hb_ts is not None and ts > self.last_hb_ts:
            self.ewma_hb_gap_s = _ewma(self.ewma_hb_gap_s,
                                       ts - self.last_hb_ts)
        if self.first_hb_ts is None:
            self.first_hb_ts = ts
        self.hb_count += 1
        step = args.get("step")
        if step is not None:
            step = int(step)
            # ANY change counts as an advance (a respawned worker's
            # process-local counter restarts lower — still progress);
            # only a forward move feeds the step-latency EWMA.
            if self.last_step is None or step != self.last_step:
                if (self.last_step is not None and step > self.last_step
                        and self.step_advance_ts is not None
                        and ts > self.step_advance_ts):
                    self.ewma_step_s = _ewma(
                        self.ewma_step_s,
                        (ts - self.step_advance_ts)
                        / (step - self.last_step))
                self.last_step = step
                self.step_advance_ts = ts
        self.last_hb_ts = ts if self.last_hb_ts is None \
            else max(self.last_hb_ts, ts)

    # -- derived thresholds ----------------------------------------------
    def hb_gap_s(self):
        """Expected seconds between heartbeats (observed cadence when
        known, the configured interval otherwise)."""
        return self.ewma_hb_gap_s or self.heartbeat_ms / 1000.0

    def dead_timeout(self):
        return max(DEAD_INTERVALS * self.hb_gap_s(), DEAD_MIN_S)

    def hang_timeout(self, configured=0.0):
        """Seconds of step-counter stall that mean hung. An explicit
        ``configured`` (> 0) wins; otherwise derive from the EWMA."""
        if configured and configured > 0:
            return float(configured)
        derived = (HANG_EWMA_MULT * self.ewma_step_s
                   if self.ewma_step_s is not None
                   else DEFAULT_HANG_TIMEOUT_S)
        return max(derived, HANG_MIN_INTERVALS * self.hb_gap_s())

    def status(self, now, hang_timeout_s=0.0, started_at=None):
        """-> one of STATUS_STARTING/ALIVE/HUNG/DEAD at epoch ``now``.

        ``started_at`` is the monitor's incarnation start: heartbeats
        older than it belong to a previous incarnation of the sink file
        and never vouch for (or condemn) the current process."""
        last = self.last_hb_ts
        if last is None or (started_at is not None and last < started_at):
            if started_at is not None and now - started_at > max(
                    self.dead_timeout(), START_GRACE_S):
                return STATUS_DEAD
            return STATUS_STARTING
        if now - last > self.dead_timeout():
            return STATUS_DEAD
        ref = self.step_advance_ts if self.step_advance_ts is not None \
            else self.first_hb_ts
        if started_at is not None:
            ref = max(ref, started_at)
        if now - ref > self.hang_timeout(hang_timeout_s):
            return STATUS_HUNG
        return STATUS_ALIVE


class HealthMonitor:
    """Supervisor-side watchdog: one rotation-safe tail + RankHealth per
    rank over the workers' host-tagged sink files. Construct a FRESH
    monitor per gang incarnation (workers append to the same paths; the
    monitor's ``started_at`` fences off the previous life's events)."""

    def __init__(self, sink_paths, heartbeat_ms=None, hang_timeout_s=None,
                 started_at=None, poll_min_interval_s=0.25):
        from paddle_tpu import flags

        if hang_timeout_s is None:
            hang_timeout_s = float(flags.get_flag("hang_timeout_s"))
        self.hang_timeout_s = float(hang_timeout_s or 0.0)
        self.started_at = (time.time() if started_at is None
                           else float(started_at))
        self.tails = {r: SinkTail(p) for r, p in dict(sink_paths).items()}
        self.ranks = {r: RankHealth(r, heartbeat_ms=heartbeat_ms)
                      for r in self.tails}
        self._poll_min = float(poll_min_interval_s)
        self._last_poll = 0.0
        self.classify_wall_s = 0.0  # cumulative (bench counters.health)

    def poll(self, force=False):
        """Drain new sink events into the classifiers (throttled to
        ``poll_min_interval_s`` so wait_gang's tight loop stays cheap);
        returns the number of heartbeats consumed."""
        nowm = time.monotonic()
        if not force and nowm - self._last_poll < self._poll_min:
            return 0
        self._last_poll = nowm
        n = 0
        for rank, tail in self.tails.items():
            rh = self.ranks[rank]
            for ev in tail.poll():
                if ev.get("name") == HEARTBEAT_EVENT:
                    rh.observe(ev)
                    n += 1
        return n

    def classify(self, now=None, ranks=None):
        """{rank: status} for ``ranks`` (default: all)."""
        t0 = time.perf_counter()
        now = time.time() if now is None else now
        sel = self.ranks if ranks is None else {
            r: self.ranks[r] for r in ranks if r in self.ranks}
        out = {r: rh.status(now, self.hang_timeout_s, self.started_at)
               for r, rh in sel.items()}
        self.classify_wall_s += time.perf_counter() - t0
        return out

    def unhealthy(self, now=None, ranks=None):
        """The hung/dead subset of ``classify``. Callers must restrict
        ``ranks`` to processes still running: a rank that exited cleanly
        stops heartbeating and would otherwise read as dead."""
        return {r: s for r, s in self.classify(now, ranks).items()
                if s in (STATUS_HUNG, STATUS_DEAD)}


# -- serving SLO monitor -----------------------------------------------------
#: retained latency samples are pruned to the slow window AND this cap.
MAX_SLO_SAMPLES = 65536


class SloMonitor:
    """Multi-window burn-rate monitor over request latencies.

    burn = (window violation fraction) / (1 − target): 1.0 means the
    error budget is being spent exactly at the sustainable rate. The
    alert condition requires BOTH windows over threshold — the fast
    window for detection speed, the slow window so a brief spike that
    already ended does not page (the SRE multiwindow recipe; defaults
    14.4×/6× are the classic fast/slow page thresholds). State flips
    are edge-triggered ``health.slo_burn`` / ``health.slo_recovered``
    events through the (gated) telemetry layer.

    ``now`` parameters default to ``time.monotonic()`` and exist so
    tests drive a synthetic clock.
    """

    def __init__(self, slo_ms, target=0.999, fast_window_s=60.0,
                 slow_window_s=600.0, fast_burn=14.4, slow_burn=6.0,
                 name="serving"):
        self.slo_ms = float(slo_ms)
        self.target = float(target)
        self.budget = max(1e-9, 1.0 - self.target)
        self.fast_window_s = float(fast_window_s)
        self.slow_window_s = float(slow_window_s)
        self.fast_burn = float(fast_burn)
        self.slow_burn = float(slow_burn)
        self.name = name
        self._samples = collections.deque()  # (ts_s, latency_ms)
        self._lock = threading.Lock()
        self._burning = False
        # worst SLO-violating (latency_ms, trace_id) seen so far — the
        # exemplar a burn event names, linking the page to the request
        # trace that spent the budget
        self._exemplar = None

    # -- record ----------------------------------------------------------
    def record(self, latency_ms, now=None, trace_id=None):
        now = time.monotonic() if now is None else now
        with self._lock:
            self._samples.append((now, float(latency_ms)))
            if trace_id is not None and latency_ms > self.slo_ms \
                    and (self._exemplar is None
                         or latency_ms >= self._exemplar[0]):
                self._exemplar = (float(latency_ms), trace_id)
            exemplar = self._exemplar
            self._prune(now)
            fast = self._burn(now, self.fast_window_s)
            slow = self._burn(now, self.slow_window_s)
            burning = fast >= self.fast_burn and slow >= self.slow_burn
            flipped = burning != self._burning
            self._burning = burning
        if flipped:
            from paddle_tpu import observability as obs

            if burning:
                obs.inc("health.slo_burn")
                kw = {}
                if exemplar is not None:
                    kw["exemplar_ms"] = round(exemplar[0], 2)
                    kw["exemplar_trace"] = exemplar[1]
                obs.event("health.slo_burn", monitor=self.name,
                          slo_ms=self.slo_ms, burn_fast=round(fast, 2),
                          burn_slow=round(slow, 2), **kw)
            else:
                obs.event("health.slo_recovered", monitor=self.name,
                          slo_ms=self.slo_ms)

    def _prune(self, now):
        horizon = now - self.slow_window_s
        q = self._samples
        while q and (q[0][0] < horizon or len(q) > MAX_SLO_SAMPLES):
            q.popleft()

    def _burn(self, now, window_s):
        horizon = now - window_s
        total = bad = 0
        for ts, ms in self._samples:
            if ts >= horizon:
                total += 1
                if ms > self.slo_ms:
                    bad += 1
        if not total:
            return 0.0
        return (bad / total) / self.budget

    # -- read ------------------------------------------------------------
    def burn_rate(self, window_s, now=None):
        now = time.monotonic() if now is None else now
        with self._lock:
            return self._burn(now, window_s)

    def burning(self, now=None):
        """Live alert condition (recomputed, so burn that aged out of
        the fast window reads recovered even with no new requests)."""
        now = time.monotonic() if now is None else now
        with self._lock:
            return (self._burn(now, self.fast_window_s) >= self.fast_burn
                    and self._burn(now, self.slow_window_s)
                    >= self.slow_burn)

    def snapshot(self, now=None):
        now = time.monotonic() if now is None else now
        with self._lock:
            fast = self._burn(now, self.fast_window_s)
            slow = self._burn(now, self.slow_window_s)
            lats = sorted(ms for _, ms in self._samples)
            n = len(lats)
            p99 = lats[min(n - 1, int(0.99 * n))] if n else None
            bad = sum(1 for _, ms in self._samples if ms > self.slo_ms)
            out = {"slo_ms": self.slo_ms, "target": self.target,
                   "requests": n, "violations": bad,
                   "burn_fast": fast, "burn_slow": slow,
                   "burning": fast >= self.fast_burn
                   and slow >= self.slow_burn,
                   "p99_ms": p99}
            if self._exemplar is not None:
                out["exemplar"] = {"ms": round(self._exemplar[0], 2),
                                   "trace_id": self._exemplar[1]}
            return out
