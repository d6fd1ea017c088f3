"""The ``train`` driver: a training loop through the user's entry point.

    fluid.Executor().run(main, feed=batch, fetch_list=[loss], return_numpy=False)

on the Program that the configuration's builder makes (bf16 AMP where the
configuration says so), from a fresh ``Scope`` initialised by
``exe.run(startup)``. No flag of the program is set and no op is pinned: on
the chip the dispatch picks its kernels itself.

One object — executor, scope, compiled step — is built in set-up, driven
from the seed through its first three steps (the warm-up, on pool batches
0, 1, 2, through the very call the window makes), read for what
``compare.py`` holds it to, and handed to the window. The window dispatches
steps back to back, reads the loss of every ``log_every``-th step to the
host, and ends at the first such read after ``--seconds``. Once it has
closed, the device's peak is read, the program's state is freed, and the
plain reference follows the same three steps from the same seed.

The weights come from the seed by the reference's own specification
(``reference/common.py`` ``init_params``: one jitted call on the device) and
are put into the scope after ``exe.run(startup)``, so the reference takes
nothing that the program has made.

A traced run keeps its profile only where ``BENCH_KEEP_TRACE`` names a
directory (to look at a trace by hand); otherwise it is reduced and deleted.
"""

import gc
import importlib
import os
import shutil
import sys
import tempfile
import time

import numpy as np

WARMUP_STEPS = 3
TRACED_STEPS = 20
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def _fail(message):
    print("benchmark: " + message, file=sys.stderr)
    sys.exit(3)


def _scalar(value):
    return float(np.asarray(value).reshape(-1)[0])


class _Compiles:
    """Seconds and count of XLA backend compiles (or the persistent-cache
    reads that replace them), from JAX's own monitoring events — the
    listener of ``chip_smoke.py``, copied."""

    def __init__(self, jax):
        self.seconds, self.count = 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._listen)

    def _listen(self, event, seconds, **_):
        if event == COMPILE_EVENT:
            self.seconds += seconds
            self.count += 1


def sized(config, workload, rehearse):
    """The configuration as it is run: the workload's traffic beside it,
    and in a rehearsal the tiny sizes of its ``rehearsal`` block."""
    cfg = dict(config, traffic=dict(workload["traffic"]))
    rows = workload["traffic"]["rows"]
    if rehearse:
        tiny = config["rehearsal"]
        cfg["model"] = dict(config["model"], **tiny.get("model", {}))
        cfg["input"] = dict(config.get("input", {}), **tiny.get("input", {}))
        rows = tiny["rows"]
    return cfg, rows


def limits(workload, rehearse):
    """The limits a run is held to: the cell's own, set from readings on
    the chip at its own size; in a rehearsal those of the workload's
    ``rehearsal`` block, set the same way at the rehearsal size."""
    return workload["rehearsal"]["limits"] if rehearse \
        else workload["limits"]


class Trainer:
    """The compiled step with its state: built once, started from a seed
    (fresh scope, ``exe.run(startup)``, weights and batch pool from the
    seed), warmed up through its first steps, and then stepped by the
    window — the same object throughout. ``tools/prove.py`` starts one
    Trainer from many seeds; the executable is compiled once."""

    def __init__(self, cfg, rows, workload, rehearse=False):
        import paddle_tpu.fluid as fluid

        self.fluid, self.cfg, self.rows = fluid, cfg, rows
        self.pool_size = workload["traffic"]["pool"]
        self.model = importlib.import_module(
            "benchmarks.reference." + cfg["reference"])
        self.specs = self.model.param_specs(cfg)
        self.state_specs = self.model.state_specs(cfg)
        prog = cfg["program"]
        builder = getattr(importlib.import_module(prog["module"]),
                          prog["builder"])
        self.main, self.startup, handle = builder(
            batch_size=rows, lr=cfg["optimizer"]["lr"],
            **dict(cfg["model"], **prog.get("args", {})))
        self.loss_var = handle["loss"]
        if prog.get("amp") == "bf16":
            fluid.contrib.mixed_precision.enable_bf16(self.main)
        if rehearse:
            # off the chip the dispatch would take the XLA composition; the
            # rehearsal walks the kernels' path in interpret mode instead
            for op in self.main.desc.global_block().ops:
                if op.type.startswith("fused_attention"):
                    op.attrs["force_flash"] = True
        declared = {p.name: tuple(p.shape)
                    for p in self.main.all_parameters()}
        expected = {n: tuple(s) for n, (s, _) in self.specs.items()}
        if declared != expected:
            odd = sorted(set(declared.items()) ^ set(expected.items()))[:6]
            _fail("the reference's leaves are not the program's "
                  "parameters: %r" % (odd,))
        self.exe = fluid.Executor()
        self.scope = self.pool = None

    def start(self, seed):
        import jax

        from benchmarks.reference import common

        self.seed = seed
        self.main.random_seed = self.startup.random_seed = seed % (2 ** 32)
        self.scope = self.fluid.Scope()
        self.exe.run(self.startup, scope=self.scope)
        for name, value in common.init_params(self.specs, seed).items():
            self.scope.set(name, value)
        for name, (shape, _) in self.state_specs.items():
            held = self.scope.get(name)
            if held is None or tuple(held.shape) != tuple(shape):
                _fail("the program keeps no state %r of shape %r"
                      % (name, shape))
        self.pool = [
            {k: jax.device_put(v) for k, v in self.model.make_batch(
                self.cfg, self.rows, common.batch_rng(seed, i)).items()}
            for i in range(self.pool_size)]

    def step(self, i):
        """Step ``i`` of the run, on pool batch ``i`` round-robin: the one
        call that warm-up and window both make. Returns the loss, still
        on the device."""
        return self.exe.run(
            self.main, feed=self.pool[i % self.pool_size],
            fetch_list=[self.loss_var], scope=self.scope,
            return_numpy=False)[0]

    def warm_up(self):
        """The first steps, drained, and what ``compare.py`` wants of them:
        each loss, every leaf's first gradient as the optimizer got it
        (from its state after one step) and every leaf's change after the
        last. -> (readings, seconds of the first step)."""
        import jax
        import jax.numpy as jnp

        from benchmarks.reference import common

        names = list(self.specs)
        t = time.perf_counter()
        losses = [_scalar(self.step(0))]
        first_step_s = time.perf_counter() - t
        firsts = [self.model.first_gradient_state(n, self.cfg)
                  for n in names]
        got = jax.jit(lambda xs: [
            jnp.sqrt(jnp.sum(jnp.square(x))) for x in xs])(
                [self.scope.get(state) for state, _ in firsts])
        grad_norms = {n: float(g) * factor
                      for n, g, (_, factor) in zip(names, got, firsts)}
        for i in range(1, WARMUP_STEPS):
            losses.append(_scalar(self.step(i)))
        start = dict(common.init_params(self.specs, self.seed),
                     **common.init_state(self.state_specs))
        moved = list(start)
        got = jax.jit(lambda now, was: [
            jnp.sqrt(jnp.sum(jnp.square(a - b)))
            for a, b in zip(now, was)])(
                [self.scope.get(n) for n in moved],
                [start[n] for n in moved])
        change_norms = {n: float(c) for n, c in zip(moved, got)}
        return ({"losses": losses, "grad_norms": grad_norms,
                 "change_norms": change_norms}, first_step_s)

    def free(self):
        """Drop the state of this seed (the executable stays)."""
        self.scope = self.pool = None
        gc.collect()


def check_devices(jax, chips, rehearse):
    """The devices as JAX reports them; a measured run without the chips
    the cell asks for ends here, non-zero, with no result line."""
    devices = jax.devices()
    platform = devices[0].platform
    if rehearse:
        if platform != "cpu":
            _fail("--rehearse runs on the CPU only")
    elif platform != "tpu" or len(devices) < chips:
        _fail("needs %d TPU chip(s); JAX found %d %s device(s)"
              % (chips, len(devices), platform))
    return devices


def use_cache(jax):
    """JAX's persistent compilation cache where the program keeps it
    (``JAX_COMPILATION_CACHE_DIR``, else ``<checkout>/.jax_cache``), for
    every executable however small."""
    from paddle_tpu.platform import use_compilation_cache

    use_compilation_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def run(ctx):
    args, workload, config = ctx["args"], ctx["workload"], ctx["config"]

    import jax

    from benchmarks import compare, trace_reduce
    from benchmarks.reference import common

    # where set-up's seconds go, for the line on standard error: a set-up
    # that reads long says in which phase
    phases, mark = [], [ctx["t0"]]

    def phase(name):
        now = time.perf_counter()
        phases.append("%s %.1f" % (name, now - mark[0]))
        mark[0] = now

    use_cache(jax)
    compiles = _Compiles(jax)
    devices = check_devices(jax, workload["chips"], args.rehearse)
    platform = devices[0].platform
    cfg, rows = sized(config, workload, args.rehearse)
    seed = args.seed
    phase("import jax, find the devices")

    # -- set-up: build, initialise, stage, warm up ---------------------------
    trainer = Trainer(cfg, rows, workload, args.rehearse)
    phase("import and build the program")
    trainer.start(seed)
    phase("startup, weights, pool")
    program, first_step_s = trainer.warm_up()
    phase("three steps and readings")
    step, model = trainer.step, trainer.model
    setup_compile_s = compiles.seconds

    # -- the window -----------------------------------------------------------
    log_every = workload["traffic"]["log_every"]
    trace_dir, spans = None, []
    steps, failed = WARMUP_STEPS, 0
    compiled_before = compiles.count
    t_start = time.perf_counter()
    setup_s = t_start - ctx["t0"]
    while True:
        if (args.trace and trace_dir is None
                and time.perf_counter() - t_start >= args.seconds / 2):
            # the traced slice: whole logging periods, begun and ended on a
            # host read, so that the device holds nothing else
            trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0  # the driver's spans are enough
            options.host_tracer_level = 2
            jax.profiler.start_trace(trace_dir, profiler_options=options)
            for k in range(TRACED_STEPS):
                with jax.profiler.StepTraceAnnotation(
                        "bench_step", step_num=steps):
                    t = time.perf_counter()
                    loss = step(steps)
                    spans.append(time.perf_counter() - t)
                steps += 1
                if (k + 1) % log_every == 0:
                    with jax.profiler.TraceAnnotation("bench_host_read"):
                        failed += not np.isfinite(_scalar(loss))
            jax.profiler.stop_trace()
        for _ in range(log_every):
            loss = step(steps)
            steps += 1
        failed += not np.isfinite(_scalar(loss))  # the host read
        now = time.perf_counter()
        if now - t_start >= args.seconds:
            break
    window_s = now - t_start
    done = steps - WARMUP_STEPS
    if compiles.count != compiled_before:
        _fail("%d compilation(s) inside the measured window"
              % (compiles.count - compiled_before))

    # -- after the window: the device's peak, then the reference --------------
    stats = devices[0].memory_stats() or {}
    in_use = stats.get("peak_bytes_in_use")
    reserved = stats.get("peak_bytes_reserved")
    device = {"platform": platform, "kind": devices[0].device_kind,
              "count": len(devices),
              # live buffers and executables' temporaries are separate
              # high-water marks on this backend (PERF.md section 3): the
              # step's footprint is the larger of the two
              "memory_peak_bytes": max(in_use or 0, reserved or 0),
              "peak_bytes_in_use": in_use, "peak_bytes_reserved": reserved}

    trainer.exe.close()
    trainer.free()
    del trainer, step
    gc.collect()

    facts = {"cfg": cfg, "rows": rows, "device_kind": devices[0].device_kind,
             "first_step_s": first_step_s,
             "setup_compile_s": setup_compile_s,
             "dispatch_spans_s": spans,
             "traced_steps": TRACED_STEPS if spans else 0, "trace": None}
    breakdown = None
    if trace_dir is not None:
        try:
            facts["trace"] = trace_reduce.reduce_dir(trace_dir)
        finally:
            keep = os.environ.get("BENCH_KEEP_TRACE")
            if keep:
                shutil.copytree(trace_dir, keep, dirs_exist_ok=True)
            shutil.rmtree(trace_dir, ignore_errors=True)
        if facts["trace"] is not None:  # None: no device plane (rehearsal)
            device["busy_s"] = facts["trace"]["busy_s"]
            device["window_s"] = facts["trace"]["window_s"]
            breakdown = {"device_ops": facts["trace"]["top_ops"],
                         "idle_gaps": facts["trace"]["idle_gaps"]}

    t = time.perf_counter()
    reference = common.follow(model, cfg, rows, seed, steps=WARMUP_STEPS)
    reference_s = time.perf_counter() - t
    correct, compared = compare.judge(program, reference,
                                      limits(workload, args.rehearse))
    correct = correct and failed == 0
    print("benchmark: %d steps in %.3f s, set-up %.1f s (%s; of that the "
          "first step %.1f s, backend compile %.1f s), reference %.1f s"
          % (done, window_s, setup_s, ", ".join(phases), first_step_s,
             setup_compile_s, reference_s), file=sys.stderr)

    unit = workload["traffic"].get("sample_unit", "samples")
    end_to_end = {
        "train_samples_per_s": {"value": done * rows / window_s,
                                "unit": unit + "/s"},
        "setup_s": {"value": setup_s, "unit": "s"},
    }
    return {"correct": correct, "attempted": done, "failed": int(failed),
            "end_to_end": end_to_end, "device": device, "facts": facts,
            "breakdown": breakdown, "compared": compared}
