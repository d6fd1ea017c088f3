"""Set-up from the inside (PR 37).

- every Fluid op's lowering is the span ``op:<type>`` while JAX traces an
  executable's first call: inside the ``compile`` seam span, recorded with
  everything off, its ``role`` the device join's; a steady step adds none;
- ``append_backward`` and ``Optimizer.minimize`` are seam spans of the
  Program's construction, with the ops they appended;
- the first-call span carries what JAX's compilation cache did meanwhile,
  and nothing is charged where no first call is open.
"""

import threading

import numpy as np
import pytest

import jax

import paddle_tpu.fluid as fluid
from paddle_tpu import observability as obs
from paddle_tpu.framework import OpRole, Program, program_guard
from paddle_tpu.observability import opprof

HITS = "/jax/compilation_cache/cache_hits"
MISSES = "/jax/compilation_cache/cache_misses"
RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
SAVED = "/jax/compilation_cache/compile_time_saved_sec"


def _two_layers(width=16):
    """Two identical layers under Adam -> (main, startup, loss)."""
    main, startup = Program(), Program()
    with program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[width], dtype="float32")
        label = fluid.layers.data(name="label", shape=[1], dtype="int64")
        hidden = x
        for _ in range(2):
            hidden = fluid.layers.fc(input=hidden, size=width, act="relu")
        loss = fluid.layers.mean(fluid.layers.softmax_with_cross_entropy(
            logits=fluid.layers.fc(input=hidden, size=4), label=label))
        fluid.optimizer.Adam(learning_rate=1e-2).minimize(loss)
    return main, startup, loss


def _feed(width=16):
    rng = np.random.RandomState(0)
    return {"x": rng.randn(8, width).astype(np.float32),
            "label": rng.randint(0, 4, size=(8, 1)).astype(np.int64)}


def _named(name):
    return [s for s in obs.spans() if s.name == name]


def _within(child, parent):
    return (child.tid == parent.tid and parent.ts_us <= child.ts_us
            and child.ts_us + child.dur_us
            <= parent.ts_us + parent.dur_us + 1)


# -- (a) a span for every Fluid op, on the first call alone -----------------

def test_every_op_is_a_span_inside_the_first_call_and_no_step_adds_one():
    main, startup, loss = _two_layers()
    exe, scope = fluid.Executor(), fluid.Scope()
    exe.run(startup, scope=scope)
    obs.reset()
    assert not obs.spans_live()
    exe.run(main, feed=_feed(), fetch_list=[loss], scope=scope)

    (first,) = _named("compile")
    compiled = list(exe.engine._cache.values())[-1]  # startup's is first
    ops = compiled.block_program.ops
    spans = {s.args["idx"]: s for s in obs.spans()
             if s.name.startswith("op:")}
    assert len(spans) == len(ops) > 20
    roles = set()
    for index, op in enumerate(ops):
        span = spans["0_%d" % index]
        assert span.name == "op:" + op.type
        assert _within(span, first), op.type
        assert span.args["role"] == opprof.role_phase(op.attrs["op_role"])
        roles.add(span.args["role"])
    assert roles == set(opprof.PHASES)
    by_type = {s.name: s.args["role"] for s in spans.values()}
    assert by_type["op:mul"] == "forward"
    assert by_type["op:mul_grad"] == "backward"
    assert by_type["op:adam"] == "optimizer"
    # by op type with no code of its own: the summary's rows
    row = obs.tracer.summary()["op:mul_grad"]
    # (three childless spans: self time is the total but for the sums' last
    # digit, which read 8.438229000000002 against 8.438229 once)
    assert row["calls"] == 3
    assert 0.0 < row["self_ms"] <= row["total_ms"] * (1 + 1e-9)
    # the ops' seconds are inside the engine's traced function, and that
    # inside what JAX reports for the jaxpr tracing
    (body,) = _named("traced-fn")
    assert _within(body, first)
    assert all(_within(s, body) for s in spans.values())
    assert (sum(s.dur_us for s in spans.values()) <= body.dur_us
            <= first.args["jax_trace_s"] * 1e6)

    # a steady step, the flag down and no profiler session: not one more
    held = len(obs.spans())
    assert not obs.spans_live()
    exe.run(main, feed=_feed(), fetch_list=[loss], scope=scope)
    assert len(obs.spans()) == held
    assert obs.tracer.dropped() == 0


def test_a_retrace_outside_a_seam_records_nothing():
    main, startup, loss = _two_layers()
    exe, scope = fluid.Executor(), fluid.Scope()
    exe.run(startup, scope=scope)
    exe.run(main, feed=_feed(), fetch_list=[loss], scope=scope)
    compiled = list(exe.engine._cache.values())[-1]  # startup's is first
    feed = _feed()
    args = ([feed[n] for n in compiled.block_program.feed_names],
            [scope.get(n) for n in compiled.mutated_names],
            [scope.get(n) for n in compiled.readonly_names],
            (np.uint32(0), np.uint32(1)))
    step = compiled.jitted.__wrapped__
    obs.reset()
    jax.make_jaxpr(step)(*args)
    assert obs.spans() == []


# -- (b) the Program's construction -----------------------------------------

def test_append_backward_and_minimize_are_seam_spans_with_their_ops():
    obs.reset()
    assert not obs.spans_live()
    main, _, _ = _two_layers()
    (backward,) = _named("append_backward")
    (minimize,) = _named("minimize")
    assert _within(backward, minimize)
    ops = main.desc.global_block().ops
    roles = [int(op.attrs["op_role"]) for op in ops]
    appended = [r for r in roles
                if r & (OpRole.Backward | OpRole.Optimize | OpRole.LRSched)]
    assert backward.args["ops"] == sum(
        1 for r in roles if r & OpRole.Backward) > 0
    assert minimize.args["ops"] == len(appended) > backward.args["ops"]
    assert obs.self_time([backward, minimize])["minimize"] > 0.0
    assert not obs.spans_live()
    # once a Program
    _two_layers()
    assert len(_named("minimize")) == len(_named("append_backward")) == 2


# -- (c) the compilation cache's verdict on the first call ------------------

def test_the_listeners_charge_the_open_first_call_on_its_thread():
    tracer = obs.tracer
    # no first call open: charged to nothing
    tracer._charge_jax_count(HITS)
    tracer._charge_jax_duration(RETRIEVAL, 0.5)
    with obs.seam_span("trace"):  # a seam span, but no function's call
        tracer._charge_jax_count(MISSES)
    assert obs.spans()[-1].args is None
    with obs.seam_span("compile", fun_name="pt_f") as span:
        tracer._charge_jax_count(HITS)
        tracer._charge_jax_count(HITS, extra="ignored")
        tracer._charge_jax_count(MISSES)
        tracer._charge_jax_count("/jax/compilation_cache/tasks_using_cache")
        tracer._charge_jax_duration(RETRIEVAL, 0.25)
        tracer._charge_jax_duration(RETRIEVAL, 0.5)
        tracer._charge_jax_duration(SAVED, 2.0)
        # JAX's three durations still go by the function's name
        tracer._charge_jax_duration(
            "/jax/core/compile/backend_compile_duration", 1.0,
            fun_name="jit(pt_f)")
        tracer._charge_jax_duration(
            "/jax/core/compile/backend_compile_duration", 9.0,
            fun_name="jit(another)")
        # another thread's compile is not this call's
        other = threading.Thread(target=tracer._charge_jax_count,
                                 args=(HITS,))
        other.start()
        other.join()
    assert span.args == {
        "fun_name": "pt_f", "cache_hits": 2, "cache_misses": 1,
        "cache_retrieval_s": 0.75, "compile_saved_s": 2.0,
        "backend_compile_s": 1.0}
    tracer._charge_jax_count(HITS)
    assert span.args["cache_hits"] == 2


@pytest.fixture
def compile_cache(tmp_path):
    """JAX's persistent compilation cache in a directory of the test's
    own, for every executable however small."""
    from jax.experimental.compilation_cache import compilation_cache

    keys = {"jax_compilation_cache_dir": str(tmp_path),
            "jax_enable_compilation_cache": True,
            "jax_persistent_cache_min_compile_time_secs": 0.0,
            "jax_persistent_cache_min_entry_size_bytes": 0}
    was = {k: getattr(jax.config, k) for k in keys}
    for k, v in keys.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()
    yield tmp_path
    for k, v in was.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


def test_a_second_executor_reads_the_first_ones_entry(compile_cache):
    main, startup, loss = _two_layers(width=24)

    def first_call_of_the_step():
        exe, scope = fluid.Executor(), fluid.Scope()
        exe.run(startup, scope=scope)
        obs.reset()
        exe.run(main, feed=_feed(24), fetch_list=[loss], scope=scope)
        (span,) = _named("compile")
        return span.args

    cold = first_call_of_the_step()
    assert cold["cache_misses"] == 1 and "cache_hits" not in cold
    assert "cache_retrieval_s" not in cold
    # a new executor: the engine's and JAX's in-memory caches know nothing
    # of its function, the directory does
    warm = first_call_of_the_step()
    assert warm["cache_hits"] == 1 and "cache_misses" not in warm
    assert 0.0 < warm["cache_retrieval_s"] <= warm["backend_compile_s"]
    assert "compile_saved_s" in warm
    assert warm["fun_name"] == cold["fun_name"]
