"""One trace from the Fluid op to the device event.

- a JAX profiler session, and nothing else, switches the engine's spans
  on: they land on ``/host:CPU`` of the profiler's own trace as
  ``pt.<name>`` and in the in-memory tracer, and neither with the session
  and the flag both off;
- ``tracing.self_time``;
- the cache-miss seam (``trace`` and its children, the first call
  ``compile``) is recorded with everything off, and the first call
  carries the seconds JAX reports for that one function;
- the device join is lazy: a step leaves a note, ``instruction_phases``
  resolves it after ``Executor.close()``, and no step lowers or compiles.
"""

import glob
import os

import numpy as np
import pytest

import jax

import paddle_tpu.fluid as fluid
from paddle_tpu import flags, observability as obs
from paddle_tpu.framework import Program, program_guard
from paddle_tpu.observability import opprof, tracing
from paddle_tpu.observability.metrics import NULL_BLOCK
from paddle_tpu.observability.tracing import SpanRecord, self_time

PHASES = ("feed", "lookup", "gather", "run", "writeback", "fetch")


@pytest.fixture
def mlp():
    """A tiny Adam-trained MLP: (executor, scope, run one step)."""
    opprof.reset()
    main, startup = Program(), Program()
    with program_guard(main, startup):
        img = fluid.layers.data(name="img", shape=[32], dtype="float32")
        label = fluid.layers.data(name="label", shape=[1], dtype="int64")
        hidden = fluid.layers.fc(input=img, size=16, act="relu")
        pred = fluid.layers.fc(input=hidden, size=4)
        loss = fluid.layers.mean(fluid.layers.softmax_with_cross_entropy(
            logits=pred, label=label))
        fluid.optimizer.Adam(learning_rate=1e-2).minimize(loss)
    exe, scope = fluid.Executor(), fluid.Scope()
    rng = np.random.RandomState(0)
    feed = {"img": rng.randn(8, 32).astype(np.float32),
            "label": rng.randint(0, 4, size=(8, 1)).astype(np.int64)}

    def step():
        return exe.run(main, feed=feed, fetch_list=[loss], scope=scope)[0]

    exe.run(startup, scope=scope)
    yield exe, scope, step
    opprof.reset()


def _host_events(trace_dir):
    """{event name: [(start_ns, end_ns, {stat: value})]} of /host:CPU."""
    from jax.profiler import ProfileData

    path = glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb"))[-1]
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("pt."):
                    out.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns,
                         dict(ev.stats)))
    return out


# -- A, B: the session is the switch; the step's phases ---------------------

def test_session_puts_the_steps_spans_on_the_profilers_host_plane(
        mlp, tmp_path):
    exe, scope, step = mlp
    step()  # the first call compiles, outside the session
    obs.reset()
    assert not obs.enabled() and not obs.spans_live()

    jax.profiler.start_trace(str(tmp_path))
    try:
        assert obs.spans_live() and not obs.enabled()
        for _ in range(3):
            step()
    finally:
        jax.profiler.stop_trace()
    recorded = obs.spans()
    step()  # session over, flag down: nothing more is recorded
    assert len(obs.spans()) == len(recorded)
    assert obs.snapshot()["counters"] == {}  # spans, and nothing heavier

    # in memory, under the names the tests of the flag know
    by_name = {}
    for s in recorded:
        by_name.setdefault(s.name, []).append(s)
    assert {n: len(v) for n, v in by_name.items()} == dict(
        {"executor.run": 3, "step": 3}, **{n: 3 for n in PHASES})
    numbers = [s.args["step"] for s in by_name["step"]]
    assert numbers == [numbers[0], numbers[0] + 1, numbers[0] + 2]
    for name in PHASES:  # children carry their step's number
        assert [s.args["step"] for s in by_name[name]] == numbers

    # in the profiler's trace, as pt.<name>, children inside pt.step
    host = _host_events(str(tmp_path))
    assert {n: len(v) for n, v in host.items()} == dict(
        {"pt.executor.run": 3, "pt.step": 3},
        **{"pt." + n: 3 for n in PHASES})
    steps = sorted(host["pt.step"])
    assert [int(st["step_num"]) for _, _, st in steps] == numbers
    for name in PHASES:
        for (s, e, _), (ps, pe, _) in zip(sorted(host["pt." + name]),
                                          steps):
            assert ps <= s and e <= pe, name
    runs = sorted(host["pt.executor.run"])
    assert all(rs <= ps and pe <= re_
               for (rs, re_, _), (ps, pe, _) in zip(runs, steps))


def test_with_session_and_flag_off_a_span_is_the_shared_no_op(mlp):
    _, _, step = mlp
    step()
    obs.reset()
    assert obs.span("step") is NULL_BLOCK
    assert obs.step_span("step", 1) is NULL_BLOCK
    step()
    step()
    assert obs.spans() == []


def test_flag_alone_still_records_the_phases(mlp):
    _, _, step = mlp
    step()
    flags.set_flags({"metrics": True})
    try:
        obs.reset()
        step()
    finally:
        flags.reset_flag("metrics")
    names = [s.name for s in obs.spans()]
    assert sorted(names) == sorted(("executor.run", "step") + PHASES)
    summary = obs.snapshot()["spans"]
    assert summary["step"]["self_ms"] <= summary["step"]["total_ms"]
    assert summary["run"]["self_ms"] == pytest.approx(
        summary["run"]["total_ms"])


# -- self time --------------------------------------------------------------

def _rec(name, ts, dur, tid=1):
    return SpanRecord(name, float(ts), float(dur), tid, 0, None)


@pytest.mark.parametrize("spans, expected", [
    # nested: a child's time leaves its parent's, a grandchild's its own
    ([_rec("outer", 0, 100), _rec("mid", 10, 50), _rec("leaf", 20, 10)],
     {"outer": 50.0, "mid": 40.0, "leaf": 10.0}),
    # siblings, one of them ending with its parent
    ([_rec("outer", 0, 100), _rec("a", 0, 30), _rec("b", 30, 70)],
     {"outer": 0.0, "a": 30.0, "b": 70.0}),
    # one name at two depths and on two threads adds up; another
    # thread's span inside the interval is no child
    ([_rec("step", 0, 100), _rec("run", 10, 20), _rec("step", 200, 50),
      _rec("run", 20, 5, tid=2)],
     {"step": 130.0, "run": 25.0}),
    # an instant event takes nothing from the span around it
    ([_rec("outer", 0, 10), _rec("trip", 5, 0)],
     {"outer": 10.0, "trip": 0.0}),
])
def test_self_time(spans, expected):
    got = self_time(spans)
    assert got == pytest.approx(expected)
    assert self_time(list(reversed(spans))) == pytest.approx(expected)


# -- C: the cache-miss seam -------------------------------------------------

def test_cache_miss_seam_is_recorded_with_everything_off(mlp):
    _, _, step = mlp
    obs.reset()
    assert not obs.spans_live()
    step()  # a cache miss: desc build, then the first call
    step()
    by_name = {}
    for s in obs.spans():
        by_name.setdefault(s.name, []).append(s)
    # the seam and its children, once; nothing of the steady step
    assert set(by_name) >= {"trace", "transform", "lower", "compile"}
    assert not set(by_name) & {"executor.run", "step", "run", "feed"}
    assert len(by_name["trace"]) == len(by_name["compile"]) == 1
    trace, first = by_name["trace"][0], by_name["compile"][0]
    for child in by_name["transform"] + by_name["lower"]:
        assert trace.ts_us <= child.ts_us
        assert (child.ts_us + child.dur_us
                <= trace.ts_us + trace.dur_us + 1)
    # the first call carries JAX's seconds for this one function
    assert first.args["fun_name"].startswith("pt_")
    for key in tracing.JAX_DURATIONS.values():
        assert 0.0 < first.args[key] < first.dur_us / 1e6, key
    assert not obs.spans_live()


def test_first_call_is_charged_for_the_engines_function_alone(mlp):
    _, _, step = mlp
    obs.reset()
    with obs.seam_span("compile", fun_name="pt_no_such_function") as span:
        # a jit of the benchmark's own, compiled while the span is open
        jax.jit(lambda x: x * 2 + 1)(np.arange(3.0)).block_until_ready()
    assert not set(span.args) & set(tracing.JAX_DURATIONS.values())
    step()
    assert obs.spans()[-1].name == "compile"
    assert obs.spans()[-1].args["jax_trace_s"] > 0.0


# -- D: the device join, lazy -----------------------------------------------

def test_note_resolves_after_close_and_no_step_lowers_or_compiles(
        mlp, tmp_path):
    exe, _, step = mlp
    step()
    seen = []

    def listen(event, secs, **kw):
        seen.append((event, kw.get("fun_name")))

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        jax.profiler.start_trace(str(tmp_path))
        try:
            for _ in range(3):
                step()
        finally:
            jax.profiler.stop_trace()
        step()
        mine = [s for s in seen if "pt_" in str(s[1])]
        assert mine == [], "a step traced, lowered or compiled: %r" % mine
        assert len(opprof._NOTES) == 1  # one executable, noted once

        exe.close()
        phases = opprof.instruction_phases()
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert not opprof._NOTES
    tagged = {i: v for i, v in phases.items() if v[0] is not None}
    assert tagged
    for instr, (tag, op_type, phase) in tagged.items():
        assert phase in opprof.PHASES, (instr, tag)
        assert op_type == opprof.tag_op_type(tag)
    for instr, row in phases.items():
        if row[0] is None:
            assert row == (None, None, None)
    by_type = {}
    for _, op_type, phase in tagged.values():
        by_type.setdefault(op_type, set()).add(phase)
    assert by_type["adam"] == {"optimizer"}
    assert by_type["mul_grad"] == {"backward"}
    assert by_type["mul"] == {"forward"}


@pytest.mark.parametrize("role, phase", [
    ("Forward", "forward"), ("Backward", "backward"),
    ("Optimize", "optimizer"), ("LRSched", "optimizer"),
    ("Loss", "forward")])
def test_role_phase(role, phase):
    from paddle_tpu.framework import OpRole

    assert opprof.role_phase(getattr(OpRole, role)) == phase
    assert opprof.role_phase(None) is None
    # the loss's gradient op is Backward | Loss
    assert opprof.role_phase(OpRole.Backward | OpRole.Loss) == "backward"


def test_instruction_phases_from_hlo_text():
    """A registered executable's instructions get their op's phase; a
    fusion whose members come from two phases is listed as mixed."""
    from paddle_tpu.framework import OpRole

    class _Op:
        def __init__(self, op_type, role):
            self.type, self.attrs = op_type, {"op_role": int(role)}

    hlo = """\
HloModule jit_pt_x_b0

%fused_update (p0: f32[8], p1: f32[8]) -> f32[8] {
  %p0 = f32[8] parameter(0)
  %p1 = f32[8] parameter(1)
  %multiply.5 = f32[8] multiply(%p0, %p1), metadata={op_name="jit(pt_x_b0)/pt.mul_grad.0_4/mul"}
  ROOT %subtract.6 = f32[8] subtract(%p0, %multiply.5), metadata={op_name="jit(pt_x_b0)/pt.adam.0_9/sub"}
}

ENTRY %main (a: f32[8], b: f32[8]) -> f32[8] {
  %a = f32[8] parameter(0)
  %b = f32[8] parameter(1)
  %dot.1 = f32[8] multiply(%a, %b), metadata={op_name="jit(pt_x_b0)/pt.mul.0_0/dot_general"}
  %copy.2 = f32[8] copy(%dot.1)
  ROOT %fusion.3 = f32[8] fusion(%copy.2, %b), kind=kLoop, calls=%fused_update, metadata={op_name="jit(pt_x_b0)/pt.adam.0_9/sub"}
}
"""
    opprof.reset()
    try:
        opprof.register_executable(hlo, {
            "pt.mul.0_0": _Op("mul", OpRole.Forward),
            "pt.mul_grad.0_4": _Op("mul_grad", OpRole.Backward),
            "pt.adam.0_9": _Op("adam", OpRole.Optimize)})
        phases = opprof.instruction_phases()
        assert phases["dot.1"] == ("pt.mul.0_0", "mul", "forward")
        assert phases["fusion.3"] == ("pt.adam.0_9", "adam", "optimizer")
        assert phases["multiply.5"][2] == "backward"
        assert phases["copy.2"] == (None, None, None)
        assert opprof.registry_snapshot()["mixed_phase"] == {
            "fusion.3": "backward+optimizer"}
    finally:
        opprof.reset()


# -- the operator's path ----------------------------------------------------

def test_fluid_profiler_prints_device_time_by_op_type_and_phase(
        mlp, tmp_path, monkeypatch):
    _, _, step = mlp
    step()
    monkeypatch.setenv("PADDLE_TPU_TRACE_DIR", str(tmp_path / "trace"))
    path = str(tmp_path / "profile.txt")
    with fluid.profiler.profiler(profile_path=path):
        for _ in range(3):
            step()
    text = open(path).read()
    assert "Device time by framework op" in text
    assert "Device time by op type" in text
    assert "Device time by phase" in text
    for phase in opprof.PHASES + ("unattributed",):
        assert phase in text.split("Device time by phase")[1]
    assert "adam" in text.split("Device time by op type")[1]
    # the host table has the step's phases, with their self time
    assert "writeback" in text and "Self(ms)" in text
