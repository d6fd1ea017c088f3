"""Engine: compiles blocks to cached XLA executables and runs them.

Replaces the reference's C++ ``Executor`` interpreter (reference:
paddle/fluid/framework/executor.cc:185-456) — instead of looping ops with
per-op kernel dispatch, the block is lowered once (see lowering.py), jitted,
cached by (program, feed-signature) key, and each ``run`` is one device
execution. Persistable state (parameters, optimizer moments, BN running
stats) stays resident on device between runs as jax Arrays held by the Scope,
mirroring how the reference keeps them in device Tensors.
"""

import dataclasses

import numpy as np

import jax

from paddle_tpu import observability as obs
from paddle_tpu.core.types import convert_dtype_to_np
from paddle_tpu.engine.lowering import BlockProgram, lower_block
from paddle_tpu.engine.pipeline import (DeferredFetch, DispatchWindow,
                                        _StepRecord, finite_probes)
from paddle_tpu.resilience import faultinject


@dataclasses.dataclass(frozen=True)
class Lowering:
    """How one executable is lowered, and what rides on that choice."""

    kind: str  # "plain" (lower_block), "remat" or "accumulated"
    accumulate_steps: int
    remat_segments: int
    # ZeRO-1 sharded update and its gradient buckets (plain mesh training
    # steps only: the scan and remat lowerings keep the replicated update)
    zero: bool
    grad_bucket_mb: float
    # whether the SDC digest covers the parameter gradients: the scan and
    # remat lowerings cannot fetch them, so there it covers the updated
    # state only
    sdc_grads: bool


def lowering_for(is_test, accumulate_steps, remat_segments, mesh, sdc):
    """The one place that decides which block lowering a compile runs and
    which features it excludes; ``Engine.get_compiled`` keys its cache on
    the result and ``Engine._compile`` builds from it."""
    if accumulate_steps > 1 and remat_segments:
        raise NotImplementedError(
            "accumulate_steps and remat_segments cannot combine yet; "
            "pick one memory lever per program")
    from paddle_tpu import flags

    if accumulate_steps > 1:
        kind = "accumulated"
    elif remat_segments:
        kind = "remat"
    else:
        kind = "plain"
    plain = kind == "plain"
    zero = bool(plain and mesh is not None and not is_test
                and flags.get_flag("zero"))
    return Lowering(
        kind=kind, accumulate_steps=accumulate_steps,
        remat_segments=remat_segments, zero=zero,
        grad_bucket_mb=(float(flags.get_flag("grad_bucket_mb"))
                        if zero else 0.0),
        sdc_grads=bool(sdc) and plain)


class CompiledBlock:
    def __init__(self, block_program, jitted, mutated_names, readonly_names,
                 in_shardings=None):
        self.block_program = block_program
        self.jitted = jitted
        # executions so far: 0 means the next jitted call pays the XLA
        # compile (jax.jit compiles lazily) — telemetry books that call
        # as "compile", later ones as "run"
        self.run_count = 0
        # state vars both read and re-emitted -> donated to XLA (functional
        # form of the reference's in-place ParamOut/MomentOut updates)
        self.mutated_names = mutated_names
        # state vars only read (e.g. params in a test program) -> not donated
        self.readonly_names = readonly_names
        # (feed, mutated, readonly) NamedShardings under SPMD — the
        # multi-host run path needs them to build global jax.Arrays from
        # host values (None when compiled without a mesh)
        self.in_shardings = in_shardings
        # SDC sentinel (resilience/sentinel.py): when compiled with
        # sdc=True the jitted step returns one extra uint32[4] digest
        # fetch and grad fetches ride behind the user fetch_list;
        # sdc_band is the per-executable EWMA band of the digest abs-sum
        self.sdc = False
        self.sdc_band = None
        # model FLOPs per execution from XLA's cost_analysis(), captured
        # once at the first run (goodput ledger / MFU attribution);
        # None until captured, 0.0 when the backend reports nothing
        self.flops = None
        # the executable-cache key it is stored under (set by get_compiled)
        self._cache_key = None


class Engine:
    """One engine per Executor; owns the executable cache."""

    def __init__(self, place=None):
        import collections
        import os

        self.place = place
        # LRU-bounded executable cache (reference: Executor's program cache
        # with explicit drop semantics, executor.py:552 + the bounded
        # kernel caches of execution_strategy.h) — a long-lived serving
        # process with drifting shapes must not leak compiled executables.
        from paddle_tpu import flags

        self._cache = collections.OrderedDict()
        self._cache_capacity = int(flags.get_flag("executable_cache_size"))
        self._run_counter = 0
        # Async dispatch window (engine/pipeline.py): run_block with
        # dispatch_steps>1 enqueues steps here instead of materializing
        # their fetches; the window retires the oldest step once depth
        # is exceeded, sync() drains it, discard() drops it (rollback).
        self.window = DispatchWindow()
        # SDC sentinel state (resilience/sentinel.py), created lazily on
        # the first PADDLE_TPU_SDC step: retained replay records + the
        # observe/recover seam entry points.
        self.sentinel = None
        # Debug guard (reference: FLAGS_check_nan_inf,
        # framework/operator.cc:972-982): verify every fetch and persisted
        # state tensor is finite after each step. Whole-step granularity —
        # per-op checking would break XLA fusion; this catches the blast-up
        # at the same user-visible seam.
        self.check_nan_inf = bool(flags.get_flag("check_nan_inf"))

    # -- public ------------------------------------------------------------
    def run_block(self, program_desc, block_idx, scope, **kwargs):
        """One engine step, wrapped in the telemetry step span (a no-op
        ctx mgr unless PADDLE_TPU_METRICS is up or a JAX profiler session
        is on; in the profiler's trace it is the ``StepTraceAnnotation``
        ``pt.step``, with the phases of ``_run_block_impl`` as children).

        ``dispatch_steps=N`` (N>1) enqueues the step into the async
        dispatch window instead of materializing its fetches: the call
        returns ``DeferredFetch`` placeholders immediately (JAX async
        dispatch — the jitted call itself never blocks) and the only
        host sync is the retire of the OLDEST step once more than N are
        in flight. ``sync()`` drains the window; deferred
        ``check_nan_inf`` verdicts surface at retire with the original
        step index."""
        dispatch_steps = int(kwargs.pop("dispatch_steps", 1) or 1)
        defer = dispatch_steps > 1
        if not defer and len(self.window):
            # depth changed mid-run (or a windowed run is followed by a
            # plain one): serialize cleanly before the synchronous step
            self.window.sync()
        with obs.step_span("step", self._run_counter + 1), \
                obs.time_block("engine.step_ms"):
            out = self._run_block_impl(program_desc, block_idx, scope,
                                       dispatch_steps=dispatch_steps,
                                       **kwargs)
        if not defer:
            # liveness: the heartbeat reports this monotonic counter; a
            # rank whose heartbeats stay fresh while it stops moving is
            # hung. The windowed path notes enqueue inside the impl and
            # retire inside the window instead.
            obs.health.note_step()
        return out

    def sync(self):
        """Barrier: retire every in-flight windowed step (deferred
        fetches resolve; deferred nan/inf verdicts raise here)."""
        self.window.sync()

    def discard_window(self):
        """Drop the in-flight window without materializing or raising —
        the rollback path (stale deferred verdicts from a faulted window
        must not re-raise after the state was restored). Sentinel replay
        records are dropped too: after a rollback/adoption the retained
        state references no longer describe the live scope."""
        if self.sentinel is not None:
            self.sentinel.discard()
        return self.window.discard()

    def _sdc(self):
        if self.sentinel is None:
            from paddle_tpu.resilience.sentinel import StepSentinel

            self.sentinel = StepSentinel()
        return self.sentinel

    def sdc_recover(self, step, reason=None):
        """Deterministic re-execution + vote for a suspect engine step
        (resilience/sentinel.py). KeyError when no replay record is
        retained — the caller falls back to checkpoint rollback."""
        if self.sentinel is None:
            raise KeyError(step)
        return self.sentinel.recover(step, reason=reason)

    def _run_block_impl(
        self,
        program_desc,
        block_idx,
        scope,
        feed=None,
        fetch_list=None,
        is_test=False,
        return_numpy=True,
        cache_key_extra=None,
        seed=0,
        donate_state=True,
        state_writeback=True,
        mesh=None,
        shard_rules=None,
        data_axes=("dp",),
        amp=False,
        accumulate_steps=1,
        remat_segments=0,
        verify=None,
        opt_level=None,
        dispatch_steps=1,
    ):
        feed = feed or {}
        fetch_list = fetch_list or []
        block = program_desc.block(block_idx)
        # the step's phases, each a child span of ``step`` carrying the
        # step's number: feed, lookup, gather, run (compile on an
        # executable's first call), writeback, fetch
        step = self._run_counter + 1
        with obs.span("feed", step=step):
            feed_names, feed_values = self._coerce_feed(block, feed)
        if obs.enabled():
            obs.inc("engine.feed_bytes",
                    sum(int(getattr(v, "nbytes", 0)) for v in feed_values))
        from paddle_tpu import flags as _flags

        sdc = bool(_flags.get_flag("sdc")) and not is_test
        if sdc:
            # The sentinel's replay re-invokes the SAME executable on the
            # retained pre-step arguments; those must stay alive after
            # the step, so donation is off under SDC (keyed into the
            # executable cache — toggling the flag never aliases).
            donate_state = False
        with obs.span("lookup", step=step):
            compiled = self.get_compiled(
                program_desc, block_idx, feed_names, feed_values,
                fetch_list, is_test, donate_state, amp, accumulate_steps,
                cache_key_extra=cache_key_extra, mesh=mesh,
                shard_rules=shard_rules, data_axes=data_axes,
                remat_segments=remat_segments, verify=verify,
                opt_level=opt_level, sdc=sdc)

        with obs.span("gather", step=step):
            feed_values, mutated, readonly = self._gather(
                compiled, scope, feed_values, mesh)

        self._run_counter += 1
        # The PRNG key is derived INSIDE the jitted function from two scalar
        # operands — eager ops (PRNGKey/fold_in) would each be a separate
        # host dispatch per step (cost on the chip: not measured).
        rng_seed = (np.uint32(seed), np.uint32(self._run_counter))

        # jax.jit compiles on the executable's FIRST call — telemetry
        # books that wall as "compile" (the honest XLA-compile time the
        # cache-miss build above does not see), later calls as "run"
        # (async dispatch wall). The first call belongs to the cache-miss
        # seam: its span is always recorded, and carries the seconds JAX
        # reports for tracing, lowering and backend-compiling this
        # function (observability/tracing.py JAX_DURATIONS).
        first = compiled.run_count == 0
        args = (feed_values, mutated, readonly, rng_seed)
        if first and compiled.provenance is not None:
            # the device join, lazy: what it needs of this executable (its
            # arguments' shapes, before the call donates them) is put
            # together here, at the seam
            compiled.opprof_note = obs.opprof.make_note(
                compiled.jitted, args, compiled.provenance,
                block=compiled.block_program.block,
                feed_names=compiled.block_program.feed_names)
        if compiled.opprof_note is not None and obs.spans_live():
            # ... and handed over on the first step that runs while spans
            # are live or the flag is up; opprof lowers, compiles and
            # parses it when the map is asked for, after the window —
            # never on a step
            obs.opprof.keep_note(compiled.opprof_note)
            compiled.opprof_note = None
        with (obs.seam_span("compile", fun_name=compiled.name,
                            step=self._run_counter) if first
              else obs.span("run", step=self._run_counter)), \
                obs.time_block("engine.compile_ms" if first
                               else "engine.run_ms"):
            fetches, state_out = compiled.jitted(*args)
        compiled.run_count += 1

        if obs.goodput.enabled():
            if first:
                # once per executable: model FLOPs from cost_analysis()
                # (same lowering-cache retrace record_compile_memory
                # uses), then charge the first-call wall — the honest
                # XLA compile — to the ledger's "compile" category
                if compiled.flops is None:
                    compiled.flops = obs.goodput.record_compile_flops(
                        compiled.jitted,
                        (feed_values, mutated, readonly, rng_seed)) or 0.0
                obs.goodput.mark("compile")
            obs.goodput.note_flops(compiled.flops or 0.0)

        sdc_probe = None
        digest_dev = None
        if sdc:
            # pop the fused in-graph digest (always the LAST output)
            # BEFORE any seam-level corruption can touch the list: the
            # digest must reflect what the device computed inside the jit
            fetches = list(fetches)
            digest_dev = fetches.pop()

        if faultinject.active():
            # step-seam fault points (one env read when no spec is set):
            # step_fail raises out of the step; step_nan multiplies the
            # step's float outputs by NaN so the real nan/inf guard
            # below trips exactly as a numeric blow-up would
            faultinject.fault_point("step_fail", step=self._run_counter)
            if faultinject.fault_point("step_nan", step=self._run_counter):
                fetches = [_poison_nan(v) for v in fetches]
                state_out = [_poison_nan(v) for v in state_out]
            # bitflip: SILENT corruption of the stored updated params —
            # one mantissa bit, no exception, no NaN. Exactly what the
            # sentinel exists to catch; with PADDLE_TPU_SDC off it goes
            # undetected by design (that is the failure being modeled).
            entry = faultinject.fault_point("bitflip",
                                            step=self._run_counter)
            if entry:
                from paddle_tpu.resilience import sentinel as _sentinel

                state_out = _sentinel.apply_bitflip(
                    list(state_out),
                    list(compiled.block_program.state_out_names), entry)

        if sdc:
            # dispatched NOW (eager device reductions over the seam
            # arrays + per-replica shard checksums), compared at retire:
            # composes with the dispatch window like the nan/inf probes
            sdc_probe = self._sdc().observe(
                step=self._run_counter, compiled=compiled,
                digest=digest_dev,
                state_out=list(state_out), user_fetches=list(fetches),
                args=(feed_values, mutated, readonly, rng_seed),
                writeback=state_writeback, scope=scope, mesh=mesh)

        if obs.enabled():
            if first:
                # Once per executable: the compile-time peak estimate
                # (argument/output/temp bytes from XLA's own
                # memory_analysis) — reuses jax's lowering caches for
                # the executable that just ran, so this is a retrace,
                # not a second XLA compile.
                measured = obs.memory.record_compile_memory(
                    compiled.jitted,
                    (feed_values, mutated, readonly, rng_seed),
                    label="block%d" % block_idx)
                spmd_plan = getattr(compiled, "spmd_plan", None)
                if (mesh is not None and spmd_plan is not None
                        and not spmd_plan.empty
                        and _flags.get_flag("spmd_predict")):
                    # Parse the HLO of the executable that just ran
                    # (lower() hits jax's caches — a retrace, not a
                    # second XLA compile) and hold the static prediction
                    # accountable against the partitioner's actual
                    # collectives.
                    try:
                        from paddle_tpu.analysis import (
                            spmd as spmd_analysis)

                        hlo = compiled.jitted.lower(
                            feed_values, mutated, readonly,
                            rng_seed).compile().as_text()
                        meas = spmd_analysis.measured_collectives(hlo)
                        obs.set_gauge("spmd.predicted_psums",
                                      spmd_plan.psum_count)
                        obs.set_gauge("spmd.measured_psums",
                                      meas["psum_count"])
                        obs.set_gauge("spmd.predicted_collective_bytes",
                                      spmd_plan.total_bytes)
                        obs.set_gauge("spmd.measured_collective_bytes",
                                      meas["total_bytes"])
                        obs.event(
                            "spmd.prediction_delta",
                            psums_predicted=spmd_plan.psum_count,
                            psums_measured=meas["psum_count"],
                            all_gathers_predicted=(
                                spmd_plan.all_gather_count),
                            all_gathers_measured=(
                                meas["all_gather_count"]),
                            bytes_predicted=spmd_plan.total_bytes,
                            bytes_measured=meas["total_bytes"],
                            bytes_delta=(meas["total_bytes"]
                                         - spmd_plan.total_bytes),
                            peak_bytes_predicted=int(
                                spmd_plan.per_device_peak_bytes),
                            peak_bytes_measured=int(measured or 0))
                    except Exception:
                        obs.inc("spmd.predict_crashes")
            # Every step: live-buffer census (scope-resident params vs
            # transient feed/fetch/activation bytes), allocator stats,
            # watermark, and the edge-triggered memory_pressure event.
            obs.memory.record_step_memory(scope, step=self._run_counter)

        defer = dispatch_steps > 1
        probes = []
        if self.check_nan_inf:
            if defer:
                # Deferred guard: the verdict scalars are dispatched NOW
                # (in-flight device reductions — the mutated state
                # buffers are DONATED into the next step, so they cannot
                # be re-read at retire time) and only materialized when
                # the window retires this step, where a trip raises with
                # THIS step's index (engine/pipeline.py _resolve).
                probes = finite_probes(
                    zip(compiled.block_program.state_out_names,
                        state_out), kind="state")
                probes += finite_probes(zip(fetch_list, fetches),
                                        kind="fetch")
            else:
                _check_finite(
                    zip(compiled.block_program.state_out_names,
                        state_out),
                    step=self._run_counter, kind="state")
                _check_finite(zip(fetch_list, fetches),
                              step=self._run_counter, kind="fetch")

        if state_writeback:
            with obs.span("writeback", step=step):
                for name, val in zip(
                        compiled.block_program.state_out_names, state_out):
                    scope.set(name, val)
        else:
            # Inference mode (serving): a frozen test program only
            # re-emits state values it read unchanged, so skipping the
            # write-back keeps the scope immutable — submitter threads
            # may read it concurrently with the worker's run. Pairs with
            # donate_state=False (no donation bookkeeping for params).
            obs.inc("engine.infer_runs")

        if defer:
            # Multi-step dispatch: hand back placeholders and keep the
            # fetches in flight — the scope state written back above
            # stays an un-materialized device array too (JAX async
            # dispatch), so the NEXT run_block dispatches immediately
            # instead of waiting for this step's results. nbytes is
            # metadata — no sync in the accounting.
            if obs.enabled():
                obs.inc("engine.fetch_bytes",
                        sum(int(getattr(v, "nbytes", 0))
                            for v in fetches))
            record = _StepRecord(
                step=self._run_counter, fetch_names=list(fetch_list),
                fetches=list(fetches), probes=probes,
                return_numpy=return_numpy, sentinel=sdc_probe)
            record.placeholders = tuple(
                DeferredFetch(self.window, record, i, name=n)
                for i, n in enumerate(record.fetch_names))
            obs.health.note_step_enqueued()
            # async-window tracing: the enqueue half of the step, named
            # with the ORIGINAL step so it correlates with the retire
            # event that fires when the window resolves it (no-op
            # unless a trace context is active on this thread)
            obs.reqtrace.step_event("step_enqueue", self._run_counter,
                                    depth=len(self.window))
            self.window.push(record, depth=dispatch_steps)
            return list(record.placeholders)

        if sdc_probe is not None:
            # synchronous path: the digest verdict surfaces here, after
            # the state write-back (an SDCSuspect's recovery replaces the
            # suspect scope state wholesale, so ordering is safe) and
            # after check_nan_inf (a NaN blow-up keeps its own verdict)
            sdc_probe.check()

        if return_numpy:
            # one batched host transfer for all fetches (device_get on the
            # list) — per-value np.asarray syncs serially
            with obs.span("fetch", step=step):
                fetches = list(jax.device_get(list(fetches)))
        else:
            fetches = list(fetches)
        if obs.enabled():
            obs.inc("engine.fetch_bytes",
                    sum(int(getattr(v, "nbytes", 0)) for v in fetches))
        return fetches

    def _gather(self, compiled, scope, feed_values, mesh):
        """The executable's arguments: the state it reads from the scope,
        and under a mesh the feeds and the state laid out as its
        in_shardings declare. -> (feed_values, mutated, readonly)."""
        mutated = [self._state_value(scope, n)
                   for n in compiled.mutated_names]
        readonly = [self._state_value(scope, n)
                    for n in compiled.readonly_names]
        if mesh is not None and jax.process_count() > 1:
            # Multi-host SPMD: the jit's in_shardings span devices of
            # OTHER processes, so every argument must arrive as a GLOBAL
            # jax.Array. Host values carry the same global value on
            # every process (the gen_nccl_id-era data contract), so each
            # process materializes its local shards of the declared
            # sharding via make_array_from_callback; a jax.Array still
            # committed to this process's local devices (params right
            # after the un-meshed startup run) round-trips through the
            # host once. After the first step the state comes back
            # globally sharded and passes through untouched.
            mesh_devs = frozenset(mesh.devices.flat)

            def _globalize(v, sharding):
                if (isinstance(v, jax.Array)
                        and frozenset(v.sharding.device_set) == mesh_devs):
                    return v
                host = np.asarray(v)
                return jax.make_array_from_callback(
                    host.shape, sharding, lambda idx: host[idx])

            feed_sh, mut_sh, ro_sh = compiled.in_shardings
            feed_values = [_globalize(v, s)
                           for v, s in zip(feed_values, feed_sh)]
            mutated = [_globalize(v, s)
                       for v, s in zip(mutated, mut_sh)]
            readonly = [_globalize(v, s)
                        for v, s in zip(readonly, ro_sh)]
        elif mesh is not None:
            # Single-process mesh: jit reshards undonated args freely,
            # but the DONATED state buffers must already match the
            # declared in_shardings — a live array laid out by a
            # previous rule table trips pjit's donation check otherwise
            # (the "two rule tables, one scope" sequence). Reshard only
            # on mismatch; steady-state steps pass through untouched.
            # This same seam migrates live donated state onto a SHRUNK
            # mesh after an elastic device loss (resilience/elastic.py):
            # mesh_from_flag re-plans over the survivors, mesh_signature
            # keys a fresh executable, and the mismatch branch moves the
            # arrays — counted so shrink recovery is observable.
            _, mut_sh, _ = compiled.in_shardings
            moved = 0
            resharded = []
            for v, s in zip(mutated, mut_sh):
                if isinstance(v, jax.Array) and v.sharding != s:
                    v = jax.device_put(v, s)
                    moved += 1
                resharded.append(v)
            mutated = resharded
            if moved:
                obs.inc("engine.state_resharded", moved)
                obs.event("engine.state_resharded", arrays=moved,
                          mesh=dict((str(k), int(n))
                                    for k, n in mesh.shape.items()))
        return feed_values, mutated, readonly

    @staticmethod
    def _coerce_feed(block, feed):
        """-> (names, values) sorted by name, host values coerced to the
        feed var's declared dtype; device-resident jax arrays pass
        through untouched (pre-staged input pipelines)."""
        feed_names, feed_values = [], []
        for name, value in sorted(feed.items()):
            feed_names.append(name)
            if isinstance(value, jax.Array):
                feed_values.append(value)
                continue
            vd = block.find_var_recursive(name)
            if (vd is not None and vd.dtype is not None
                    and not hasattr(value, "dtype")):
                value = np.asarray(value, dtype=convert_dtype_to_np(vd.dtype))
            else:
                value = np.asarray(value)
            feed_values.append(value)
        return feed_names, feed_values

    def get_compiled(self, program_desc, block_idx, feed_names, feed_values,
                     fetch_list, is_test, donate_state, amp,
                     accumulate_steps, cache_key_extra=None, mesh=None,
                     shard_rules=None, data_axes=("dp",), remat_segments=0,
                     verify=None, opt_level=None, sdc=False):
        """LRU-cached executable lookup/compile for one (program, feed
        signature) — shared by ``run_block`` and the Executor's
        ``cost_analysis`` so an analysis compiles exactly the executable
        a subsequent run reuses (and vice versa)."""
        from paddle_tpu import flags

        if opt_level is None:
            opt_level = int(flags.get_flag("opt_level"))
        else:
            opt_level = int(opt_level)
        lowering = lowering_for(is_test, accumulate_steps, remat_segments,
                                mesh, sdc)
        # Mesh-targeted compiles key on the mesh identity (axis
        # names/sizes + device ids) and the sharding-rule table, so the
        # same program compiled for two meshes — or two rule tables —
        # yields two executables; the no-mesh path keys on None and
        # keeps hitting its existing entry. ZeRO and its bucket size key
        # the cache so toggling them never serves a stale executable.
        if mesh is not None:
            from paddle_tpu.parallel.mesh import mesh_signature

            mesh_key = (mesh_signature(mesh),
                        shard_rules.signature()
                        if shard_rules is not None else None,
                        tuple(data_axes), lowering.zero,
                        lowering.grad_bucket_mb)
        else:
            mesh_key = None
        key = (
            program_desc.cached_fingerprint(),
            block_idx,
            tuple((n, v.shape, str(v.dtype))
                  for n, v in zip(feed_names, feed_values)),
            tuple(fetch_list),
            is_test,
            donate_state,
            amp,
            accumulate_steps,
            remat_segments,
            cache_key_extra,
            opt_level,
            mesh_key,
            sdc,
            bool(flags.get_flag("opprof")),
        )
        compiled = self._cache.get(key)
        if compiled is None:
            obs.inc("engine.cache_miss")
            # the jitted step's name, from the program's content: the
            # same in every process that runs the same program
            name = "pt_%s_b%d" % (key[0][:10], block_idx)
            if faultinject.active():
                # transient compile failure (a real pod sees these as
                # coordinator hiccups / OOM-ed compile servers); the
                # resilience driver retries the step, which re-enters
                # this cache-miss path
                faultinject.fault_point("compile")
            # the cache-miss seam: this span and its children (transform,
            # verify, lower, the plans) are recorded whatever is switched
            # on — it runs once an executable, never on a steady step
            with obs.seam_span("trace", block=block_idx,
                               opt_level=opt_level), \
                    obs.time_block("engine.trace_ms"):
                run_desc = program_desc
                if opt_level > 0:
                    # Desc-level rewrites, once per compiled executable
                    # (cache misses only). optimize_program works on a
                    # clone and returns the original untouched when
                    # nothing fires; the cache stays keyed on the
                    # ORIGINAL desc + opt level, so differently-optimized
                    # executables never alias.
                    from paddle_tpu.analysis.transforms import (
                        optimize_program)

                    run_desc, _report = optimize_program(
                        program_desc, level=opt_level,
                        feed_names=feed_names, fetch_names=fetch_list)
                if verify is None:
                    verify = flags.get_flag("verify")
                if verify:
                    # Pre-lowering static verification, once per
                    # executable (cache misses only — zero steady-state
                    # overhead). ERROR findings raise VerificationError
                    # with source-level coordinates instead of a deep
                    # trace-time failure. Runs on the POST-transform
                    # desc: every rewrite the pipeline produced is
                    # itself verified.
                    from paddle_tpu.analysis import verify_program

                    with obs.span("verify"):
                        verify_program(
                            run_desc, feed_names=feed_names,
                            fetch_names=fetch_list, mesh=mesh,
                            shard_rules=shard_rules, data_axes=data_axes,
                            raise_on_error=True)
                with obs.span("lower"):
                    compiled = self._compile(
                        run_desc.block(block_idx), feed_names, fetch_list,
                        is_test, donate_state, lowering, mesh=mesh,
                        feed_values=feed_values, shard_rules=shard_rules,
                        data_axes=data_axes, amp=amp, sdc=sdc, name=name)
            # Static SPMD plan on the POST-transform desc (mesh compiles
            # only), crash-isolated: the predicted collective schedule
            # rides on the executable and is validated against the jitted
            # HLO on its first run (spmd.prediction_delta — see
            # _run_block_impl).
            compiled.spmd_plan = None
            if mesh is not None:
                from paddle_tpu.analysis import spmd as spmd_analysis

                try:
                    with obs.span("spmd-plan"):
                        compiled.spmd_plan = spmd_analysis.analyze_spmd(
                            run_desc, mesh=mesh,
                            shard_rules=shard_rules,
                            data_axes=data_axes,
                            feed_names=feed_names,
                            feed_shapes={
                                n: tuple(v.shape) for n, v in
                                zip(feed_names, feed_values)},
                            fetch_names=fetch_list,
                            block_idx=block_idx, zero1=lowering.zero)
                    if obs.enabled() and compiled.spmd_plan is not None:
                        plan = compiled.spmd_plan
                        obs.event(
                            "spmd_plan",
                            psums=plan.psum_count,
                            all_gathers=plan.all_gather_count,
                            collective_bytes=plan.total_bytes,
                            per_device_peak_bytes=int(
                                plan.per_device_peak_bytes))
                except Exception:
                    obs.inc("spmd.plan_crashes")
                    compiled.spmd_plan = None
            compiled._cache_key = key
            # the cache-miss build (trace/transform/verify/lower) is
            # wall the step did not spend computing — charge it now so
            # the step-boundary mark books only the remainder as compute
            obs.goodput.mark("compile")
            self._cache[key] = compiled
            while len(self._cache) > self._cache_capacity:
                self._cache.popitem(last=False)
                obs.inc("engine.cache_evict")
        else:
            self._cache.move_to_end(key)
            obs.inc("engine.cache_hit")
        return compiled

    @staticmethod
    def _state_value(scope, name):
        val = scope.get(name)
        if val is None:
            raise RuntimeError(
                "Variable %r is used before initialization; run the startup "
                "program first (reference semantics: PADDLE_ENFORCE "
                "holder_ != nullptr, paddle/fluid/framework/tensor.h)" % name
            )
        return val

    # -- internals ---------------------------------------------------------
    def _compile(self, block, feed_names, fetch_list, is_test, donate_state,
                 lowering, mesh=None, feed_values=None, shard_rules=None,
                 data_axes=("dp",), amp=False, sdc=False, name="pt_block"):
        extra_live = ()
        if lowering.kind == "remat":
            # keep the loss-computing ops alive: the remat lowering
            # differentiates the loss VALUE, which the explicit grad
            # chain never reads (its seed is a fill op), so plain DCE
            # would prune it whenever the loss is not fetched
            extra_live = tuple(
                n[: -len("@GRAD")]
                for op in block.ops
                if op.attrs.get("__is_loss_grad__")
                for n in op.output_arg_names() if n.endswith("@GRAD"))
        sdc_grad_names = []
        if lowering.sdc_grads:
            # Fetch the parameter gradients alongside the user fetches so
            # the in-graph digest covers them AND the seam can recompute
            # the same digest eagerly over the materialized arrays.
            seen = set(fetch_list)
            for op in block.ops:
                for n in op.output_arg_names():
                    if not n.endswith("@GRAD") or n in seen:
                        continue
                    base = block.find_var_recursive(n[: -len("@GRAD")])
                    if base is not None and getattr(base, "is_parameter",
                                                    False):
                        seen.add(n)
                        sdc_grad_names.append(n)
        bp = BlockProgram(block, feed_names,
                          list(fetch_list) + sdc_grad_names, (),
                          extra_live_vars=extra_live)
        # ZeRO-1 plan: which params' updates shard over the data axes,
        # which slot vars live partitioned, and where the grads get
        # constrained so the partitioner reduce-scatters instead of
        # all-reducing
        zplan = None
        if lowering.zero:
            from paddle_tpu.parallel.sharding import zero1_plan

            zplan = zero1_plan(block, mesh.shape, data_axes=data_axes,
                               shard_rules=shard_rules)
            if not zplan.param_specs:
                zplan = None
            elif obs.enabled():
                obs.event("zero1_plan",
                          params=len(zplan.param_specs),
                          slots=len(zplan.slot_specs),
                          bucket_mb=lowering.grad_bucket_mb)
        # opprof provenance collection: a dict the lowering fills at jit
        # trace time (tag -> OpDesc) — lazily, on the wrapped fn's first
        # trace, so the recorded tags always match exactly what was
        # emitted (including the accumulated lowering's once-op index
        # offset). None = the named-scope wrap is skipped entirely.
        from paddle_tpu import flags as _flags

        prov = {} if _flags.get_flag("opprof") else None
        if lowering.kind == "accumulated":
            from paddle_tpu.engine.lowering import lower_block_accumulated

            fn = lower_block_accumulated(
                bp, lowering.accumulate_steps, is_test=is_test,
                executor=self, amp=amp, prov=prov)
        elif lowering.kind == "remat":
            from paddle_tpu.engine.lowering import lower_block_remat

            fn = lower_block_remat(
                bp, lowering.remat_segments, is_test=is_test,
                executor=self, amp=amp, prov=prov)
        else:
            grad_sh = None
            if zplan is not None:
                from jax.sharding import NamedSharding as _NS

                grad_sh = {n: _NS(mesh, spec)
                           for n, spec in zplan.grad_specs.items()}
            fn = lower_block(
                bp, is_test=is_test, executor=self, amp=amp,
                grad_shardings=grad_sh,
                grad_bucket_bytes=int(lowering.grad_bucket_mb * 2 ** 20),
                prov=prov)

        out_set = set(bp.state_out_names)
        mutated = [n for n in bp.state_in_names if n in out_set]
        readonly = [n for n in bp.state_in_names if n not in out_set]
        mutated_idx = {n: i for i, n in enumerate(mutated)}
        readonly_idx = {n: i for i, n in enumerate(readonly)}

        def wrapped(feed_values, mutated_vals, readonly_vals, rng_seed):
            seed, ctr = rng_seed
            rng_key = jax.random.fold_in(jax.random.PRNGKey(seed), ctr)
            state_values = [
                mutated_vals[mutated_idx[n]]
                if n in mutated_idx
                else readonly_vals[readonly_idx[n]]
                for n in bp.state_in_names
            ]
            # runs at jit-trace time: mesh-aware op lowerings (the
            # shard_map flash-attention dispatch) read the ambient
            # (mesh, data_axes) instead of a threaded argument
            from paddle_tpu.parallel.mesh import spmd_lowering

            # live on a first call (a cache-miss seam): the ops' spans lie
            # inside this one, its self time is the lowering's own Python,
            # and the rest of JAX's tracing seconds is JAX's
            with obs.span("traced-fn"), spmd_lowering(mesh, data_axes):
                fetches, state_out = fn(feed_values, state_values, rng_key)
                if sdc:
                    # fuse the step digest INTO the executable: abs-sum +
                    # finite-count over (param grads, updated state) plus
                    # an order-independent uint32 checksum over the
                    # updated state, one extra uint32[4] fetch. The grad
                    # fetches exist only as digest operands — they are
                    # dropped here, so XLA never materializes them as
                    # outputs. Pure observation — no operand of the step
                    # reads the digest, so the computed trajectory is
                    # bit-identical with the sentinel on or off.
                    from paddle_tpu.resilience.sentinel import graph_digest

                    n_grads = len(fetches) - len(fetch_list)
                    digest = graph_digest(
                        list(fetches[len(fetch_list):]) + list(state_out),
                        exact_start=n_grads)
                    fetches = list(fetches[:len(fetch_list)]) + [digest]
                return fetches, state_out

        donate = (1,) if (donate_state and mutated) else ()
        jit_kwargs = {}
        if mesh is not None:
            # SPMD: batch-shard the feeds over the data axes and lay out
            # state per the declared sharding rules (replicated when no rule
            # matches); XLA's partitioner derives every collective —
            # all-reduce for replicated params, reduce-scatter for sharded —
            # compiled onto ICI (replaces the reference's
            # details/all_reduce_op_handle.cc NCCL calls and the whole
            # multi_devices_graph_pass mode zoo).
            from jax.sharding import NamedSharding, PartitionSpec as P
            from paddle_tpu.parallel.sharding import batch_sharding

            rep = NamedSharding(mesh, P())

            def state_sharding(name):
                # ZeRO-1 slot override: optimizer-state vars (moments,
                # velocity) live dp-partitioned, in AND out (the update
                # ops write the same var name in place, so one
                # name-keyed lookup covers both sides). Params are NOT
                # in slot_specs — their replicated out_sharding is what
                # makes the partitioner all-gather the updated shard.
                if zplan is not None and name in zplan.slot_specs:
                    return NamedSharding(mesh, zplan.slot_specs[name])
                if shard_rules is None:
                    return rep
                vd = block.find_var_recursive(name)
                # a trainable param with a rule table but no matching
                # rule silently replicates — surface that (once per
                # name) as an observability event + warning
                spec = shard_rules.spec_for(
                    name, warn_unmatched=bool(
                        vd is not None and getattr(vd, "is_parameter",
                                                   False)))
                if not len(spec):
                    return rep
                ndim = (len(vd.shape) if vd is not None
                        and vd.shape is not None else None)
                # a rule matching a lower-rank var (e.g. an optimizer's
                # scalar beta-pow accumulator named after the param) falls
                # back to replicated
                if ndim is None or len(spec) > ndim:
                    return rep
                return NamedSharding(mesh, spec)

            feed_sh = [
                batch_sharding(mesh, v, data_axes)
                for v in (feed_values or [])
            ]
            jit_kwargs["in_shardings"] = (
                feed_sh,
                [state_sharding(n) for n in mutated],
                [state_sharding(n) for n in readonly],
                rep,
            )
            # the sdc digest rides as one extra replicated fetch (and
            # the grad digest operands are never outputs)
            jit_kwargs["out_shardings"] = (
                [rep] * (len(bp.fetch_names) - len(sdc_grad_names)
                         + (1 if sdc else 0)),
                [state_sharding(n) for n in bp.state_out_names],
            )
        # A stable name for the jitted step: XLA names the module after
        # it (``jit_pt_<program>_b<idx>`` on the trace's ``XLA Modules``
        # line) and JAX names its compile events by it, so both can be
        # matched to this executable.
        wrapped.__name__ = wrapped.__qualname__ = name
        jitted = jax.jit(wrapped, donate_argnums=donate, **jit_kwargs)
        in_sh = (tuple(jit_kwargs["in_shardings"][:3])
                 if "in_shardings" in jit_kwargs else None)
        cb = CompiledBlock(bp, jitted, mutated, readonly,
                           in_shardings=in_sh)
        cb.name = wrapped.__name__
        cb.provenance = prov
        cb.opprof_note = None
        if sdc:
            from paddle_tpu.resilience.sentinel import EWMABand

            cb.sdc = True
            cb.sdc_band = EWMABand()
        return cb


def _poison_nan(val):
    """NaN-fill a float array (fault injection's step_nan); non-float
    values pass through untouched."""
    import jax.numpy as jnp

    if not hasattr(val, "dtype") or not jnp.issubdtype(
            jnp.asarray(val).dtype, jnp.floating):
        return val
    return jnp.asarray(val) * jnp.nan


def _check_finite(named_values, step=None, kind="tensor"):
    """Raise naming the FIRST non-finite float tensor with its shape,
    dtype, nan/inf breakdown, and the step counter (reference error
    contract: operator.cc:976 'Operator %s output Tensor %s contains Inf'
    — here at step granularity). The trip is recorded as an
    observability event + counter before raising, so a telemetry
    snapshot from a crashed run still shows what blew up and when."""
    import jax.numpy as jnp

    for name, val in named_values:
        if not hasattr(val, "dtype") or not jnp.issubdtype(
                jnp.asarray(val).dtype, jnp.floating):
            continue
        if not bool(jnp.isfinite(val).all()):
            arr = jnp.asarray(val)
            n_nan = int(jnp.isnan(arr).sum())
            n_inf = int(jnp.isinf(arr).sum())
            obs.inc("engine.nan_inf_trips")
            obs.event("nan_inf_trip", var=name, kind=kind,
                      shape=str(tuple(arr.shape)), dtype=str(arr.dtype),
                      step=step, nan=n_nan, inf=n_inf)
            raise RuntimeError(
                "check_nan_inf: %s %r (shape %s, dtype %s) contains "
                "%d NaN / %d Inf value(s) after step %s (reference: "
                "FLAGS_check_nan_inf, framework/operator.cc:972)"
                % (kind, name, tuple(arr.shape), arr.dtype, n_nan, n_inf,
                   "?" if step is None else step))
