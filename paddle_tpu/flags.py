"""Unified runtime flags (reference: the gflags-backed FLAGS_* system —
paddle/fluid/platform/init.cc InitGflags + python/paddle/fluid/__init__.py
__bootstrap__ reading env into gflags). Every PADDLE_TPU_* knob is
declared here with its default and help text; values come from (highest
precedence first) programmatic set_flags, the environment, the default.

Usage, mirroring the reference's fluid.core.globals-style access::

    from paddle_tpu import flags
    flags.set_flags({"check_nan_inf": True})
    flags.get_flag("rpc_deadline_ms")
    flags.describe()          # name -> (value, source, help)
"""

import os

__all__ = ["DEFS", "get_flag", "set_flags", "reset_flag", "describe",
           "env_name", "on_change", "flags_doc_issues"]

# name -> (type, default, help)
DEFS = {
    "check_nan_inf": (
        bool, False,
        "Verify every fetch/state tensor is finite after each step "
        "(reference: FLAGS_check_nan_inf)."),
    "verify": (
        bool, False,
        "Run the static program verifier (paddle_tpu.analysis) before "
        "each block is lowered — once per compiled executable, raising "
        "on ERROR-severity findings (use-before-def, dtype clashes, "
        "orphan gradients, bad sharding axes...). Source-level "
        "diagnostics instead of a deep XLA traceback."),
    "opt_level": (
        int, 1,
        "Desc-level optimization applied once per compiled executable at "
        "the engine's cache-miss seam (analysis/transforms.py "
        "optimize_program): 0 = off, 1 = attention-pattern rewrite to "
        "the fused flash-attention op, 2 = + elementwise+activation "
        "fusion, constant folding, and CSE, 3 = + memory planning "
        "(analysis/memory.py): liveness-driven state donation and "
        "automatic rematerialization under the HBM budget "
        "(PADDLE_TPU_HBM_BUDGET_FRAC); a level above 3 raises "
        "ValueError. Rewrites operate on a clone; the program desc is "
        "never mutated."),
    "replan_tolerance": (
        float, 0.0,
        "Measured-feedback memory re-planning: when the realized XLA "
        "peak (memory_plan_delta telemetry, first run of a planned "
        "executable) misses the prediction by more than this relative "
        "tolerance, re-plan the remat segment count from the measured "
        "peak and re-jit once (bounded; counted in memory.replan). "
        "Requires PADDLE_TPU_METRICS=1. <=0 disables."),
    "spmd_predict": (
        bool, False,
        "Validate the static SPMD collective schedule "
        "(analysis/spmd.py) against the compiled executable on the "
        "first run of every mesh-compiled block: parse the jitted HLO, "
        "compare predicted psum/all-gather counts and payload bytes, "
        "and emit spmd.prediction_delta telemetry — the collective-"
        "schedule analog of memory_plan_delta. Requires "
        "PADDLE_TPU_METRICS=1; no-op without a mesh."),
    "zero": (
        bool, False,
        "ZeRO-1 weight-update sharding over the mesh's data axes "
        "(engine cache-miss seam, mesh compiles only): optimizer-state "
        "slots (Adam moments, Momentum velocity) are partitioned "
        "across dp ranks, each parameter gradient is reduce-scattered "
        "to its owning shard (parallel/sharding.py zero1_plan), the "
        "update runs on the local shard, and the updated parameter is "
        "all-gathered back replicated. Parameters whose dims the "
        "data-axis product does not divide (scalars, beta-pow "
        "accumulators) keep the replicated all-reduce path. Keyed into "
        "the executable cache; the static analyzer predicts the new "
        "schedule with analyze_spmd(zero1=True). No-op without a "
        "mesh, under gradient accumulation, and under remat."),
    "grad_bucket_mb": (
        float, 0.0,
        "Bucketed gradient reduction under the ZeRO-1 sharded update "
        "(PADDLE_TPU_ZERO): gradients are grouped in backward "
        "production order into buckets of roughly this many MB and "
        "each full bucket is fenced with jax.lax.optimization_barrier, "
        "so XLA schedules earlier buckets' reduce-scatters while the "
        "remaining backward still computes instead of paying one "
        "end-of-step reduction barrier. Collective counts and payloads "
        "are unchanged — only scheduling freedom moves — so "
        "spmd.prediction_delta stays exact at every bucket size. "
        "<=0 = one unbucketed schedule (XLA's default placement)."),
    "hbm_budget_frac": (
        float, 0.9,
        "Fraction of device memory (observability.memory."
        "device_memory_limit — allocator bytes_limit, overridable via "
        "PADDLE_TPU_DEVICE_MEMORY_BYTES) the opt-level-3 memory planner "
        "budgets a step against: when the liveness peak estimate "
        "exceeds budget, automatic rematerialization picks the "
        "smallest jax.checkpoint segment count that fits. <=0 or an "
        "unknowable device limit disables auto-remat (donation "
        "planning still runs)."),
    "dispatch_steps": (
        int, 1,
        "Depth of the engine's async dispatch window "
        "(engine/pipeline.py): Executor.run enqueues up to this many "
        "compiled-block steps without blocking on device results — "
        "donated scope state stays in flight as device arrays, fetches "
        "of intermediate steps come back as DeferredFetch placeholders "
        "resolved by Executor.sync(), the window-overflow retire, or "
        "first host use (np.asarray/float). 1 = the classic synchronous "
        "feed->step->fetch loop. check_nan_inf under a deeper window "
        "defers its verdict to retire time and reports the ORIGINAL "
        "step index; the heartbeat watchdog classifies hangs on "
        "RETIRED steps so an N-deep window never false-trips."),
    "prefetch_depth": (
        int, 2,
        "Bounded depth of the PrefetchingFeeder's device-side input "
        "queue (engine/pipeline.py): a background thread converts + "
        "jax.device_put-s batch k+1..k+depth while step k runs. 2 = "
        "classic double buffering. Iterator exhaustion and reader "
        "exceptions propagate to the consuming thread in order."),
    "executable_cache_size": (
        int, 128,
        "LRU capacity of the engine's compiled-executable cache "
        "(reference: the Executor program cache)."),
    "rpc_deadline_ms": (
        float, 180000.0,
        "Deadline for pserver RPC replies; <=0 disables (reference: "
        "FLAGS_rpc_deadline)."),
    "flash_min_seq": (
        int, 256,
        "Minimum key length at which fused_attention dispatches to the "
        "Pallas flash kernels instead of the XLA composition."),
    "flash_bwd": (
        str, "",
        "Backward path for fused_attention: '' = Pallas flash backward "
        "kernels, 'xla' = recompute-based XLA backward."),
    "data": (
        str, "",
        "Root directory of real dataset files; empty serves synthetic "
        "data (dataset/ loaders)."),
    "trace_dir": (
        str, "",
        "Profiler trace output directory (profiler.py)."),
    "metrics": (
        bool, False,
        "Runtime telemetry (paddle_tpu.observability): engine "
        "cache/compile/run counters + timing histograms and host-side "
        "spans exportable as chrome-trace JSON. Off = no-op stubs at "
        "every instrumented seam (near-zero overhead). The spans alone "
        "are also switched on by a JAX profiler session "
        "(jax.profiler.start_trace, fluid.profiler): while one is on "
        "they are recorded with this flag down, and written into the "
        "profiler's trace as pt.<name> on the device's clock. The "
        "cache-miss seam's spans (trace, lower, first-call compile) are "
        "always recorded."),
    "goodput": (
        bool, False,
        "Goodput ledger (observability/goodput.py): charge every "
        "wall-clock second of the run to one category — compute, "
        "compile, input_wait, host_sync, ckpt_critical, rollback_replay, "
        "restart_downtime, shrink_rejit, preempt_drain, idle — via "
        "sequential marks at the existing engine/pipeline/driver seams; "
        "publishes goodput.* and mfu.* gauges. Conservation (categories "
        "sum to wall clock) holds by construction. Off = one bool check "
        "per seam."),
    "peak_flops": (
        float, 0.0,
        "Peak accelerator FLOP/s for MFU attribution (mfu.mfu = achieved "
        "/ peak, mfu.goodput_mfu discounts badput wall). Required on CPU "
        "probes where jax reports no peak; <=0 skips the MFU ratio "
        "gauges (model_flops_per_step / achieved_flops_per_s still "
        "publish)."),
    "peak_membw_bytes": (
        float, 0.0,
        "Peak device memory bandwidth in bytes/s for the op-level "
        "roofline (observability/opprof.py): an op is compute-bound "
        "when its arithmetic intensity (FLOPs/byte) sits at or above "
        "the ridge point PEAK_FLOPS / PEAK_MEMBW_BYTES, memory-bound "
        "below it. <=0 (or PEAK_FLOPS unset) downgrades every verdict "
        "to 'unknown' — device time and intensity still report."),
    "opprof": (
        bool, True,
        "Op-level profiling provenance (observability/opprof.py): wrap "
        "every op's lowering in jax.named_scope('pt.<type>.<blk>_<idx>') "
        "so XLA op_metadata carries framework-op identity through "
        "fusion. On the first step an executable runs under the metrics "
        "flag or a JAX profiler session the engine leaves a note of it; "
        "the instruction->(op, phase) map is made from the notes when asked "
        "for (opprof.instruction_phases, profiler.stop_profiler), after "
        "the profiled window and never on a step. named_scope is "
        "metadata-only (lowering stays bit-identical — test_opprof.py "
        "asserts it); off skips the scope wrap and the note. The engine "
        "keys its executable cache on the value."),
    "metrics_sink": (
        str, "",
        "Streaming telemetry export (observability/export.py): path of a "
        "JSONL sink file finished spans, instant events, and periodic "
        "metric snapshots stream to as one-line JSON events. With a sink "
        "attached the tracer's in-memory span list stays bounded (the "
        "flight recorder holds the recent window) and dropped() stays 0 "
        "on an unbounded loop. Multi-process runs tag the file per host "
        "(<base>.h<rank>.jsonl). Empty = no sink."),
    "metrics_sink_rotate_mb": (
        float, 64.0,
        "Size-based rotation threshold for the JSONL sink, in MiB: when "
        "the live file crosses it, it is atomically renamed to "
        "<path>.<seq> and a fresh file is opened. <=0 disables "
        "rotation."),
    "metrics_sink_keep": (
        int, 8,
        "Rotated JSONL files kept per sink (oldest pruned); the live "
        "file is always kept. <=0 keeps every rotation."),
    "flight_recorder_depth": (
        int, 2048,
        "Depth of the always-on in-memory flight recorder ring buffer: "
        "the last N finished spans/events survive in RAM even after the "
        "tracer would have dropped them or a sink streamed them out — "
        "the post-mortem window a crashed run is diagnosed from."),
    "memory_pressure_frac": (
        float, 0.9,
        "Fraction of device memory at which a step's live bytes raise a "
        "memory_pressure telemetry event (observability/memory.py). "
        "Device capacity comes from device.memory_stats() where the "
        "backend reports it, else from device_memory_bytes."),
    "device_memory_bytes": (
        int, 0,
        "Device memory capacity override in bytes for the "
        "memory-pressure check, for backends whose memory_stats() "
        "reports no bytes_limit (e.g. the CPU emulation mesh). "
        "0 = trust the backend / disable the check when unreported."),
    "mesh": (
        str, "",
        "Device mesh for the GSPMD executor path, as 'axis=size' pairs "
        "('dp=8', 'dp=4,tp=2'; one axis may be -1 = all remaining "
        "devices). When set, plain Executor.run jits the step with "
        "jax.sharding specs over this mesh — batch sharded over the "
        "data axes, state per the sharding rules (replicated without "
        "rules) — with XLA deriving every gradient collective. Empty = "
        "single-device compilation (the default; bit-identical to a "
        "1-device mesh)."),
    "dist_strategy": (
        str, "",
        "Distributed-training transport ParallelExecutor and the "
        "distribute transpiler select: '' or 'dp' = in-process SPMD "
        "data parallelism over local devices (the default), 'mesh' = "
        "GSPMD over the PADDLE_TPU_MESH mesh with in-graph psum "
        "gradient reduction (no pserver round-trip), 'pserver'/'nccl2' "
        "= the legacy transpiler transports."),
    "max_restarts": (
        int, 0,
        "Gang-restart budget of the supervised launcher "
        "(paddle_tpu.distributed.launch): on the first worker failure "
        "the supervisor terminates the gang and, while the budget "
        "lasts, re-launches it after exponential backoff + jitter; "
        "0 = no restarts (fail fast, but still terminate the "
        "surviving gang and propagate the rc)."),
    "max_shrinks": (
        int, 0,
        "Gang-shrink budget of the supervised launcher "
        "(paddle_tpu.distributed.launch): when a rank is PERMANENTLY "
        "lost (worker_loss exit, rc 45, or the restart budget is "
        "exhausted) and this budget remains, the supervisor relaunches "
        "the surviving gang one worker smaller instead of giving up — "
        "capacity degrades, the job completes. Each shrink emits a "
        "health.mesh_shrunk event. 0 = never shrink (a permanent loss "
        "fails the job once restarts run out)."),
    "ckpt_replicas": (
        int, 0,
        "Cross-root checkpoint replication factor (checkpoint.py): "
        "after each local atomic publish the writer mirrors the step "
        "dir to up to this many peer roots (CheckpointManager "
        "replica_roots), latest_step() becomes a majority vote across "
        "the local root + replicas (a torn local-only save loses), and "
        "restore() falls back to a peer's byte-identical replica when "
        "the local root is gone or poisoned (disk_fail). 0 = off "
        "(single-root behavior, exactly as before)."),
    "sdc": (
        bool, False,
        "Silent-data-corruption sentinel (resilience/sentinel.py): fuse "
        "a per-step digest (abs-sum + finite-count + order-independent "
        "uint32 checksum over gradients and updated params) into the "
        "jitted step as one extra fetch, recompute it eagerly at the "
        "engine seam, and raise SDCSuspect at that step's retire when "
        "the two disagree, replicas disagree under a dp mesh, or the "
        "abs-sum leaves the seeded EWMA band. The ResilientDriver "
        "replays the suspect step bit-exactly from retained inputs and "
        "votes: transient / genuine anomaly / blamed device (which is "
        "quarantined via elastic.mark_device_lost). Off = zero new ops "
        "in the compiled step."),
    "sdc_band": (
        float, 12.0,
        "EWMA band width of the sentinel's statistical tier: a step's "
        "digest abs-sum is suspect when it deviates from the running "
        "EWMA mean by more than sdc_band * ewma_stddev + 0.25 * |mean|. "
        "The band only catches gross corruption; single-bit flips are "
        "caught by the exact checksum / replica-vote tiers."),
    "sdc_warmup": (
        int, 20,
        "Steps per compiled executable before the sentinel's EWMA band "
        "starts flagging (the exact-checksum and replica tiers are "
        "active from step 1; warmup only gates the statistical tier "
        "while the gradient-scale statistics settle)."),
    "sdc_retain": (
        int, 12,
        "How many recent steps the sentinel retains replay records for "
        "(inputs + rng seed + donated-state snapshot references). Must "
        "cover the dispatch window depth, or a deferred suspect cannot "
        "be replayed and the driver falls back to checkpoint rollback."),
    "lost_devices": (
        str, "",
        "Comma-separated device ids the elastic layer treats as "
        "permanently lost (resilience/elastic.py): mesh_from_flag "
        "re-plans any 'dp=-1' axis over the surviving devices only, so "
        "the engine re-jits on the shrunk mesh (new mesh_signature "
        "cache entry) and donated state is resharded on the next step. "
        "Normally set via elastic.mark_device_lost(); empty = all "
        "devices healthy."),
    "fleet_min_workers": (
        int, 1,
        "Lower bound of the SLO-driven serving fleet "
        "(resilience/elastic.FleetRouter): scale-in never retires the "
        "fleet below this many InferenceServer workers."),
    "fleet_max_workers": (
        int, 4,
        "Upper bound of the SLO-driven serving fleet: scale-out on a "
        "fast-window burn stops adding workers at this size."),
    "fleet_cooldown_s": (
        float, 5.0,
        "Hysteresis window of the serving fleet autoscaler: after any "
        "scale action the router makes no further scaling decision for "
        "this long, so a burn that flaps around the threshold cannot "
        "thrash the fleet."),
    "fault_spec": (
        str, "",
        "Deterministic fault-injection schedule "
        "(paddle_tpu.resilience.faultinject): ';'-separated "
        "point@cond:cond entries, e.g. "
        "'step_nan@7;worker_kill@rank1:step12'. Points: step_nan, "
        "step_fail, compile, ckpt_write, worker_kill, worker_hang, "
        "worker_loss (permanent — the supervisor shrinks instead of "
        "restarting), disk_fail (poisons the local checkpoint root). "
        "Empty = no faults (the production default; the check is one "
        "env read)."),
    "recovery_ckpt": (
        str, "",
        "Checkpoint root a restarted worker resumes from. The "
        "supervised launcher sets it for every (re)spawn when given "
        "--recovery-dir; training scripts pass it to a "
        "CheckpointManager + resilience.ResilientDriver, which "
        "restores the latest complete step on startup."),
    "heartbeat_ms": (
        float, 0.0,
        "Per-rank liveness heartbeat interval in ms "
        "(observability/health.py): a daemon thread writes "
        "health.heartbeat events (monotonic step counter, current span "
        "phase, host RSS, hbm watermark, serving queue depth) through "
        "the telemetry sink / flight recorder and flushes the sink, so "
        "a supervisor tailing the file sees liveness without waiting "
        "for an exit code. Bypasses the PADDLE_TPU_METRICS gate. "
        "0 = off; the supervised launcher auto-enables it for workers "
        "whenever a metrics sink is configured."),
    "hang_timeout_s": (
        float, 0.0,
        "Hung-worker threshold of the supervisor's HealthMonitor "
        "(observability/health.py): a rank whose heartbeats stay fresh "
        "but whose step counter has not advanced for this long is "
        "classified hung; wait_gang terminates the gang (rc 44) and "
        "supervise restarts it within the restart budget. 0 = auto: a "
        "multiple of the rank's recent step-latency EWMA, floored at a "
        "few heartbeat intervals (300s before any step has completed, "
        "so a cold XLA compile never reads as a hang)."),
    "serving_slo_ms": (
        float, 0.0,
        "Per-request latency SLO of the continuous-batching "
        "InferenceServer, in ms: requests slower than this spend error "
        "budget in the fast/slow burn-rate windows "
        "(observability/health.SloMonitor); sustained burn in both "
        "windows emits an edge-triggered health.slo_burn event and "
        "flips InferenceServer.health() to unhealthy (the readiness "
        "probe). 0 = no SLO monitor."),
    "serving_buckets": (
        str, "1,2,4,8,16,32",
        "Padded batch-size bucket edges of the continuous-batching "
        "server (paddle_tpu.inference.serving), comma-separated and "
        "ascending. Coalesced requests are padded up to the smallest "
        "edge that fits; each edge compiles exactly one executable "
        "(LRU-cached in the engine), so more edges = less padding "
        "waste but more compile cache pressure."),
    "serving_max_wait_ms": (
        float, 5.0,
        "Max time the serving batcher holds the oldest queued request "
        "while waiting to fill a bigger bucket, in ms. This timer is "
        "the p99 bound at low QPS: a lone request is dispatched after "
        "at most this wait. 0 = dispatch immediately (no "
        "coalescing beyond what is already queued)."),
    "serving_calibration_batches": (
        int, 8,
        "Representative batches the post-training-quantization "
        "calibrator (paddle_tpu.inference.quantize) runs through the "
        "frozen fp32 program to collect per-tensor abs-max ranges "
        "before rewriting conv/fc/matmul ops to int8."),
    "int8_native": (
        str, "auto",
        "Lowering mode of quantized_conv2d/quantized_matmul: '1' = "
        "native int8 dot_general/conv with int32 accumulation (the "
        "TPU MXU path), '0' = numerically exact fp32 emulation "
        "(int8 values cast to f32; products <= 127^2 and per-dot "
        "partial sums stay inside the f32 mantissa). 'auto' = native "
        "everywhere except the CPU backend, where XLA's int8 codegen "
        "is slower than fp32."),
    "trace_sample": (
        float, 0.0,
        "Head-sampling rate of the request tracer "
        "(observability/reqtrace): this fraction of requests is kept "
        "end to end regardless of the tail verdict, decided "
        "deterministically from the trace ID so every process in a "
        "distributed trace agrees. Tracing is active when this or "
        "PADDLE_TPU_TRACE_SLOW_MS is > 0; both 0 (the default) keeps "
        "the request path bit-exact untraced."),
    "trace_slow_ms": (
        float, 0.0,
        "Tail-sampling latency threshold of the request tracer, in "
        "ms: a completed request slower than this keeps its full "
        "span buffer. Independent of the threshold, the tail verdict "
        "also keeps errored requests and requests slower than 2x the "
        "EWMA-smoothed p99 of recent completions. 0 = no fixed "
        "threshold (the adaptive p99 rule still applies when tracing "
        "is enabled via PADDLE_TPU_TRACE_SAMPLE)."),
    "trace_buffer": (
        int, 256,
        "Max in-flight (started, not yet finished) traces the request "
        "tracer buffers spans for; the oldest trace is evicted (and "
        "counted in reqtrace.evicted) when a new one would exceed the "
        "bound, so an abandoned request can never grow tracer memory "
        "without limit. Each trace additionally caps its own span "
        "list at 512 entries."),
    "queue_limit": (
        int, 0,
        "Bound on the continuous-batching server's request queue "
        "(paddle_tpu.inference.admission): a submit that would push the "
        "queue past this many entries first evicts already-expired "
        "requests (CoDel-style, resolved with DeadlineExceeded), then "
        "sheds a lower-priority entry if PADDLE_TPU_SERVING_SHED is on, "
        "and finally raises Rejected('queue_full'). 0 = unbounded (the "
        "exact pre-admission behavior)."),
    "submit_retries": (
        int, 0,
        "Retry budget of FleetRouter.submit: a request whose worker "
        "fails (dead at pick time, rejecting, or erroring mid-flight) "
        "is re-submitted to another live worker up to this many times, "
        "keeping one trace id across attempts with a trace.retry span "
        "per relaunch. DeadlineExceeded is never retried (the deadline "
        "is global). 0 = fail fast on the first worker's answer."),
    "hedge_after_ms": (
        float, 0.0,
        "Straggler hedging threshold of FleetRouter.submit, in ms: a "
        "routed request still unresolved after this long is "
        "speculatively re-issued to a second live worker; the first "
        "result wins and the loser is cancelled. Set it near the "
        "fleet's p99 so only stragglers pay the duplicate compute. "
        "0 = no hedging."),
    "serving_shed": (
        bool, False,
        "Priority load shedding in the serving admission gate: while "
        "the SLO fast window is burning, priority<=0 submissions are "
        "shed (Rejected('shed')) — after the degraded executable has "
        "been engaged, if one is configured — and a full bounded queue "
        "may evict its lowest-priority entry to admit a "
        "higher-priority newcomer. Off = priorities are recorded but "
        "never acted on."),
    "serving_degraded": (
        bool, False,
        "Degraded-mode fallback of the InferenceServer: when armed and "
        "a degraded_program (e.g. the PR 8 int8 quantized program) was "
        "passed at construction, a fast-window SLO burn switches "
        "dispatch to the cheaper executable (own compile-cache entry "
        "per bucket) and a confirmed slow-window recovery switches "
        "back, emitting edge-triggered health.degraded_mode events. "
        "Off = the fallback program is ignored."),
    "fleet_breaker_failures": (
        int, 0,
        "Consecutive-failure trip threshold of the per-worker circuit "
        "breaker in FleetRouter: this many failures in a row opens the "
        "breaker and removes the worker from rotation until a "
        "half-open probe succeeds after "
        "PADDLE_TPU_FLEET_BREAKER_RESET_S. 0 = no breaker (workers "
        "leave rotation only by dying or burning)."),
    "fleet_breaker_reset_s": (
        float, 5.0,
        "Cool-down of an OPEN per-worker circuit breaker, in seconds: "
        "after this long the breaker goes half-open and routes exactly "
        "one probe request to the worker — success closes it, failure "
        "re-opens it and restarts the cool-down."),
}

_overrides = {}
_env_backup = {}
# name -> [callables] invoked with the new value after set_flags /
# reset_flag touches that flag (observability caches its gate off this).
_change_hooks = {}


def on_change(name, fn):
    if name not in DEFS:
        raise KeyError("unknown flag %r" % name)
    _change_hooks.setdefault(name, []).append(fn)


def _notify(name):
    for fn in _change_hooks.get(name, ()):
        fn(get_flag(name))


def env_name(name):
    return "PADDLE_TPU_" + name.upper()


def _parse(typ, raw):
    if typ is bool:
        return raw not in ("0", "", "false", "False", False, 0, None)
    return typ(raw)


def get_flag(name):
    typ, default, _ = DEFS[name]
    if name in _overrides:
        return _overrides[name]
    raw = os.environ.get(env_name(name))
    if raw is None:
        return default
    return _parse(typ, raw)


def set_flags(flags_dict):
    """Programmatic override (reference: fluid.core.globals setter /
    __bootstrap__). Also mirrors into the environment so subprocesses
    (dist workers) inherit the setting."""
    for name, value in flags_dict.items():
        if name not in DEFS:
            raise KeyError(
                "unknown flag %r; known: %s" % (name, sorted(DEFS)))
        typ = DEFS[name][0]
        value = _parse(typ, value) if not isinstance(value, typ) else value
        if name not in _env_backup:
            _env_backup[name] = os.environ.get(env_name(name))
        _overrides[name] = value
        os.environ[env_name(name)] = (
            ("1" if value else "0") if typ is bool else str(value))
        _notify(name)


def reset_flag(name):
    """Undo a set_flags override, restoring any pre-existing env value
    (the documented set_flags > env > default precedence survives)."""
    _overrides.pop(name, None)
    prev = _env_backup.pop(name, None)
    if prev is None:
        os.environ.pop(env_name(name), None)
    else:
        os.environ[env_name(name)] = prev
    _notify(name)


def describe():
    out = {}
    for name, (typ, default, help_text) in DEFS.items():
        if name in _overrides:
            src = "set_flags"
        elif env_name(name) in os.environ:
            src = "env"
        else:
            src = "default"
        out[name] = (get_flag(name), src, help_text)
    return out


def flags_doc_issues(readme_path=None):
    """Cross-reference the README flags table against DEFS: every
    registered flag needs a documented row, every row a live flag, no
    flag documented twice. Returns a list of human-readable issue
    strings (empty = in sync) — shared by ``tests/test_flags_doc.py``
    and ``tools/lint_program.py --flags``, so the table cannot drift
    silently again."""
    import re

    if readme_path is None:
        readme_path = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "README.md")
    try:
        with open(readme_path, "r") as f:
            text = f.read()
    except OSError as e:
        return ["README not readable at %s: %s" % (readme_path, e)]
    rows = re.findall(r"^\|\s*`([A-Za-z0-9_]+)`\s*\|", text, re.M)
    documented = set(rows)
    issues = []
    for name in sorted(set(DEFS) - documented):
        issues.append("flag %r (default %r) is registered in flags.py "
                      "but has no row in the README flags table"
                      % (name, DEFS[name][1]))
    for name in sorted(documented - set(DEFS)):
        issues.append("README flags table documents %r but flags.py "
                      "registers no such flag (stale row)" % name)
    for name in sorted(n for n in documented if rows.count(n) > 1):
        issues.append("README flags table documents %r %d times"
                      % (name, rows.count(name)))
    return issues
