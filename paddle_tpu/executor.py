"""Executor — the user-facing run loop (reference:
python/paddle/fluid/executor.py — Executor:262, run:451, program cache +
feed/fetch injection :319-363). Dispatches whole blocks to the XLA engine;
CompiledProgram runs go through the SPMD path (compiler.py)."""

import numpy as np

from paddle_tpu.core.scope import Scope
from paddle_tpu.engine.executor import Engine
from paddle_tpu.framework import Program, default_main_program
from paddle_tpu.platform import CPUPlace, default_accelerator_place

_global_scope = Scope()


class EOFException(Exception):
    """Raised when a PyReader-fed program exhausts its epoch
    (reference: fluid.core.EOFException from the C++ reader ops)."""


def global_scope():
    return _global_scope


def scope_guard(scope):
    import contextlib

    @contextlib.contextmanager
    def _guard():
        global _global_scope
        old = _global_scope
        _global_scope = scope
        try:
            yield
        finally:
            _global_scope = old

    return _guard()


def _as_feed_dict(feed):
    import jax

    if feed is None:
        return {}
    if isinstance(feed, dict):
        return {
            k: v if isinstance(v, jax.Array) else np.asarray(v)
            for k, v in feed.items()
        }
    raise TypeError("feed must be a dict of name -> ndarray")


class Executor:
    def __init__(self, place=None):
        self.place = place if place is not None else default_accelerator_place()
        self.engine = Engine(self.place)

    def close(self):
        """Graceful shutdown (reference: executor.py close — notifies
        pservers). The in-flight dispatch window is dropped without
        materializing (nothing will read the placeholders) and engine
        caches are cleared."""
        self.engine.discard_window()
        self.engine._cache.clear()

    def sync(self):
        """Barrier for multi-step dispatch (``run(...,
        dispatch_steps=N)``): retires every in-flight step, resolving
        the outstanding ``DeferredFetch`` placeholders. Deferred
        ``check_nan_inf`` verdicts raise here, oldest step first, each
        naming its ORIGINAL step index. A no-op when nothing is in
        flight (dispatch_steps=1 loops never pay it)."""
        self.engine.sync()

    def cost_analysis(self, program=None, feed=None, fetch_list=None,
                      scope=None, accumulate_steps=1, remat_segments=0,
                      opt_level=None):
        """XLA's cost and memory analysis of the compiled step — the
        roofline workflow as a first-class API. Compiles the same
        executable ``run`` would (without executing — no state is
        mutated, no cache entry added) and returns::

            {"bytes_accessed": float, "flops": float,
             "cost": <full XLA cost dict>,
             "memory": <CompiledMemoryStats>}

        Divide ``bytes_accessed`` by the measured step time for achieved
        HBM bandwidth; compare ``flops``/time to the chip's peak for MFU.
        ``accumulate_steps`` must match the value passed to ``run`` or
        the analysis describes a different (single-micro-batch)
        executable. The scope must hold initialized state (run the
        startup program first). Analysis availability depends on the
        backend; fields whose query fails are None."""
        from paddle_tpu.compiler import CompiledProgram

        scope = scope if scope is not None else global_scope()
        if program is None:
            program = default_main_program()
        if isinstance(program, CompiledProgram):
            raise TypeError(
                "cost_analysis takes the plain Program (SPMD-compiled "
                "program analysis is not supported yet); pass the "
                "program you built, not the CompiledProgram wrapper")
        feed = _as_feed_dict(feed)
        fetch_names = [
            f.name if hasattr(f, "name") else str(f)
            for f in (fetch_list or [])
        ]
        block = program.desc.block(0)
        feed_names, feed_values = self.engine._coerce_feed(block, feed)
        # the SHARED engine cache: analysis compiles exactly the
        # executable a subsequent run reuses, and reuses one a prior run
        # compiled
        compiled = self.engine.get_compiled(
            program.desc, 0, feed_names, feed_values, fetch_names,
            getattr(program, "_is_test", False), True,
            getattr(program, "_amp", False), accumulate_steps,
            remat_segments=remat_segments, opt_level=opt_level)
        mutated = [self.engine._state_value(scope, n)
                   for n in compiled.mutated_names]
        readonly = [self.engine._state_value(scope, n)
                    for n in compiled.readonly_names]
        comp = compiled.jitted.lower(
            feed_values, mutated, readonly,
            (np.uint32(0), np.uint32(1))).compile()
        out = {"bytes_accessed": None, "flops": None, "cost": None,
               "memory": None}
        try:
            cost = comp.cost_analysis()
            if isinstance(cost, list):
                cost = cost[0]
            out["cost"] = dict(cost)
            out["bytes_accessed"] = float(cost.get("bytes accessed", 0.0))
            out["flops"] = float(cost.get("flops", 0.0))
        except Exception:  # pragma: no cover - backend-dependent
            pass
        try:
            out["memory"] = comp.memory_analysis()
        except Exception:  # pragma: no cover - backend-dependent
            pass
        if out["memory"] is not None:
            # The analysis feeds the same HBM gauges the engine seams
            # record, so a roofline pass and a training run publish one
            # consistent hbm.compile_* series.
            from paddle_tpu import observability as obs

            if obs.enabled():
                obs.memory.record_compile_stats(out["memory"],
                                                label="cost_analysis")
        return out

    def run(self, program=None, feed=None, fetch_list=None, feed_var_name="feed",
            fetch_var_name="fetch", scope=None, return_numpy=True,
            use_program_cache=True, accumulate_steps=1, remat_segments=0,
            verify=None, opt_level=None, mesh=None, shard_rules=None,
            data_axes=("dp",), dispatch_steps=None):
        """``accumulate_steps=k`` runs the feed as k micro-batches through a
        compiled scan with one optimizer update on the averaged gradients —
        the batch-merge capability (reference:
        framework/ir/multi_batch_merge_pass.cc; see
        engine/lowering.py lower_block_accumulated).

        ``remat_segments=s`` compiles the training step with the forward
        partitioned into ``s`` ``jax.checkpoint`` segments and gradients
        taken through them — only segment-boundary activations survive to
        the backward pass, trading recompute for the activation memory
        that bounds long-context/large-batch training (see
        engine/lowering.py lower_block_remat; the TPU-native form of the
        reference's memory-optimization passes).

        ``verify=True`` (default: the PADDLE_TPU_VERIFY flag) statically
        verifies the program pre-lowering — once per compiled executable
        — and raises ``analysis.VerificationError`` on ERROR-severity
        findings (see paddle_tpu.analysis).

        ``opt_level`` (default: the PADDLE_TPU_OPT_LEVEL flag) selects the
        desc-level transform pipeline applied once per compiled
        executable — 0 off, 1 attention-pattern→flash rewrite, 2 + fusion
        / constant folding / CSE (see paddle_tpu.analysis.transforms).

        ``mesh``/``shard_rules``/``data_axes`` select the GSPMD path on a
        plain Program: the step is jitted with ``jax.sharding`` in/out
        specs over the mesh — feeds batch-sharded over ``data_axes``,
        state laid out per the ``parallel.sharding.ShardingRules`` table
        (replicated when no rule matches) — and XLA's partitioner
        derives every gradient collective in-graph (no pserver
        round-trip). Default: the ``PADDLE_TPU_MESH`` flag when set,
        else single-device compilation. A 1-device mesh is bit-identical
        to no mesh.

        ``dispatch_steps=N`` (default: the ``PADDLE_TPU_DISPATCH_STEPS``
        flag) enqueues up to N steps into the engine's async dispatch
        window without blocking on device results: each run returns
        ``DeferredFetch`` placeholders immediately (shape/dtype readable
        without blocking; any host use — ``np.asarray``, ``float()`` —
        resolves them), the only host sync in steady state is the retire
        of the OLDEST in-flight step, and ``Executor.sync()`` is the
        barrier that drains the window. Bit-exact with
        ``dispatch_steps=1``: the same executables run with the same rng
        counters — only host-materialization timing changes. With
        ``check_nan_inf`` the verdict is deferred to retire time and
        reports the original step index; scope state past a blown-up
        step may be non-finite until a rollback restores it (pair deep
        windows with ``resilience.ResilientDriver``).

        Every run is wrapped in a top-level ``executor.run`` telemetry
        span when ``PADDLE_TPU_METRICS`` is up (paddle_tpu.observability)
        — the outermost host lane of the step timeline."""
        from paddle_tpu import observability as obs
        from paddle_tpu.compiler import CompiledProgram

        with obs.span("executor.run"):
            try:
                return self._run_impl(
                    program=program, feed=feed, fetch_list=fetch_list,
                    scope=scope, return_numpy=return_numpy,
                    accumulate_steps=accumulate_steps,
                    remat_segments=remat_segments, verify=verify,
                    opt_level=opt_level, mesh=mesh,
                    shard_rules=shard_rules,
                    data_axes=data_axes, dispatch_steps=dispatch_steps)
            finally:
                # goodput ledger step boundary: everything since the
                # last seam mark (compile / input_wait / host_sync /
                # driver charges) was forward progress — charge it as
                # compute and refresh the goodput.*/mfu.* gauges. The
                # widest per-step envelope, so inter-seam host work
                # counts as compute, not idle.
                obs.goodput.step_boundary()

    def _run_impl(self, program=None, feed=None, fetch_list=None,
                  scope=None, return_numpy=True, accumulate_steps=1,
                  remat_segments=0, verify=None, opt_level=None,
                  mesh=None, shard_rules=None, data_axes=("dp",),
                  dispatch_steps=None):
        from paddle_tpu.compiler import CompiledProgram

        scope = scope if scope is not None else global_scope()
        fetch_list = fetch_list or []
        explicit_depth = dispatch_steps is not None
        if dispatch_steps is None:
            # zero-code-change entry, like PADDLE_TPU_MESH: the flag
            # turns an existing training loop into a windowed one
            from paddle_tpu import flags

            dispatch_steps = int(flags.get_flag("dispatch_steps"))
        dispatch_steps = max(1, int(dispatch_steps))

        if isinstance(program, CompiledProgram):
            if dispatch_steps > 1 and explicit_depth:
                raise NotImplementedError(
                    "dispatch_steps>1 is not supported on the "
                    "CompiledProgram (legacy SPMD) path; use the plain "
                    "Program with mesh=/PADDLE_TPU_MESH — the GSPMD "
                    "path composes with the dispatch window")
            if remat_segments:
                raise NotImplementedError(
                    "remat_segments is not supported on the CompiledProgram "
                    "(SPMD) path yet; pass the plain Program, or combine "
                    "sharding with accumulate_steps for memory headroom")
            return program._run(self, feed, fetch_list, scope, return_numpy,
                                verify=verify, opt_level=opt_level)

        if program is None:
            program = default_main_program()

        if feed is None and getattr(program, "_py_readers", None):
            # decoupled feeding: pull the next prefetched batch
            feed = {}
            for rdr in program._py_readers:
                nxt = rdr.next_feed()
                if nxt is None:
                    raise EOFException(
                        "py_reader epoch exhausted; call reader.start() "
                        "for the next epoch")
                feed.update(nxt)

        feed = _as_feed_dict(feed)
        fetch_names = [
            f.name if hasattr(f, "name") else str(f) for f in fetch_list
        ]
        if mesh is None:
            # zero-code-change entry: PADDLE_TPU_MESH selects the GSPMD
            # path for every plain run (startup programs included —
            # their state lands pre-sharded per the same rules)
            from paddle_tpu.parallel.mesh import mesh_from_flag

            mesh = mesh_from_flag()
        return self.engine.run_block(
            program.desc,
            0,
            scope,
            feed=feed,
            fetch_list=fetch_names,
            is_test=getattr(program, "_is_test", False),
            return_numpy=return_numpy,
            seed=getattr(program, "random_seed", 0) or 0,
            amp=getattr(program, "_amp", False),
            accumulate_steps=accumulate_steps,
            remat_segments=remat_segments,
            verify=verify,
            opt_level=opt_level,
            mesh=mesh,
            shard_rules=shard_rules,
            data_axes=tuple(data_axes),
            dispatch_steps=dispatch_steps,
        )
