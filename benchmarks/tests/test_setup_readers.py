"""The nine set-up readers (PR 37) on span lists made here: the op spans
inside the first calls by role, a retrace outside any first call that must
not count, the Program's construction, the first calls' rest, the cache's
verdict and the count of executables; nothing to read on an empty tracer;
and all nine through a traced rehearsal of a decoder cell."""

import json
import os
import subprocess
import sys

import pytest

from benchmarks.run import load_module
from benchmarks.layer_metrics import _setup_spans as setup, _spans
from paddle_tpu import observability as obs
from paddle_tpu.observability.tracing import SpanRecord

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
READERS = ("forward_trace_s", "backward_trace_s", "optimizer_trace_s",
           "append_backward_s", "optimizer_build_s", "mlir_lower_s",
           "first_call_rest_s", "compile_cache_hit_pct",
           "engine_executables")
S = 1e6  # a second, in the tracer's microseconds


def rec(name, ts, dur, tid=1, **args):
    return SpanRecord(name, ts * S, dur * S, tid, 0, args or None)


def op(kind, role, ts, dur, idx="0_0", tid=1):
    return rec("op:" + kind, ts, dur, tid, idx=idx, role=role)


def recorded_run():
    """A run's spans: the Program built (minimize 2 s round append_backward
    0.5 s), startup's executable (first call 1 s, a cold cache) and the
    step's (first call 10 s, a warm one), a retrace after set-up, and a
    steady step's spans."""
    return [
        rec("minimize", 0, 2.0, ops=30), rec("append_backward", 0.2, 0.5,
                                             ops=12),
        rec("trace", 10, 0.1, block=0),
        rec("compile", 11, 1.0, fun_name="pt_a_b0", step=1, jax_trace_s=0.3,
            jax_lower_s=0.1, backend_compile_s=0.5, cache_misses=1),
        op("fill_constant", "forward", 11.0, 0.2),
        rec("trace", 20, 0.2, block=0),
        rec("compile", 21, 10.0, fun_name="pt_b_b0", step=2, jax_trace_s=4.0,
            jax_lower_s=3.0, backend_compile_s=2.5, cache_hits=1,
            cache_retrieval_s=2.0, compile_saved_s=50.0),
        op("mul", "forward", 21.0, 1.0),
        # a control-flow op, 1.5 s of it in its sub-block's two ops
        op("while", "forward", 22.0, 2.0),
        op("mul", "forward", 22.1, 1.0, idx="1_10000"),
        op("mul_grad", "backward", 23.2, 0.5, idx="1_10001"),
        op("mul_grad", "backward", 24.0, 1.25),
        op("adam", "optimizer", 25.5, 0.25),
        # another thread's span in the same interval is not this call's
        op("mul", "forward", 22.0, 1.0, tid=2),
        # a retrace outside any first call (a profiler session was on)
        op("mul", "forward", 40.0, 5.0), op("adam", "optimizer", 45.0, 5.0),
        rec("executor.run", 50, 0.003), rec("run", 50.001, 0.001, step=3),
    ]


def test_op_spans_inside_the_first_calls_by_role():
    spans = recorded_run()
    assert len(setup.first_calls(spans)) == 2
    assert len(setup.op_spans(spans)) == 7
    # 0.2 + 1.0 + (2.0 - 1.5) + 1.0; the sub-block's grad op; 1.25; 0.25
    assert setup.role_seconds("forward", spans) == pytest.approx(2.7)
    assert setup.role_seconds("backward", spans) == pytest.approx(1.75)
    assert setup.role_seconds("optimizer", spans) == pytest.approx(0.25)
    by_type = setup.self_seconds_by(setup.op_spans(spans),
                                    lambda s: s.name)
    assert by_type["op:while"] == pytest.approx(0.5)
    assert by_type["op:mul"] == pytest.approx(2.0)
    # a program that records the seams and no op span (the parent commit)
    bare = [s for s in spans if not s.name.startswith("op:")]
    assert setup.role_seconds("forward", bare) is None


def test_the_seams_of_the_first_calls_and_of_the_programs_construction():
    spans = recorded_run()
    assert setup.minimize_self_seconds(spans) == pytest.approx(1.5)
    assert setup.first_call_rest_seconds(spans) == pytest.approx(
        (1.0 - 0.9) + (10.0 - 9.5))
    assert setup.cache_hit_pct(spans) == pytest.approx(50.0)
    assert setup.executables(spans) == 2
    warm = [s for s in spans if not (s.args or {}).get("cache_misses")]
    assert setup.cache_hit_pct(warm) == pytest.approx(100.0)
    cold = [s for s in spans if not (s.args or {}).get("cache_hits")]
    assert setup.cache_hit_pct(cold) == 0.0
    # first calls that carry neither count: a parent commit, or no cache
    plain = [rec("compile", 0, 1.0, fun_name="pt_a_b0", jax_trace_s=0.5)]
    assert setup.cache_hit_pct(plain) is None
    assert setup.first_call_rest_seconds(plain) == pytest.approx(0.5)
    # the first call of a function that is not an executable's
    assert setup.first_calls([rec("compile", 0, 1.0)]) == []


def test_the_nine_readers_read_the_tracer_and_nothing_from_an_empty_one():
    obs.reset()
    for name in READERS:
        assert load_module("layer_metrics", name).compute({}) is None, name
    for s in recorded_run():
        obs.tracer.add_record(s)
    try:
        got = {name: load_module("layer_metrics", name).compute({})
               for name in READERS}
        # with the readers of PR 26 beside them the seams add up: the
        # build, JAX's three durations and the rest are the trace and
        # compile spans, whole
        parts = (_spans.seam_seconds("trace"),
                 load_module("layer_metrics", "jax_trace_s").compute({}),
                 _spans.seam_seconds("compile", ("backend_compile_s",)),
                 got["first_call_rest_s"])
        whole = (_spans.seam_seconds("trace")
                 + _spans.seam_seconds("compile"))
    finally:
        obs.reset()
    assert sum(parts) == pytest.approx(whole, abs=1e-3)
    assert got == {
        "forward_trace_s": pytest.approx(2.7),
        "backward_trace_s": pytest.approx(1.75),
        "optimizer_trace_s": pytest.approx(0.25),
        "append_backward_s": pytest.approx(0.5),
        "optimizer_build_s": pytest.approx(1.5),
        "mlir_lower_s": pytest.approx(3.1),
        "first_call_rest_s": pytest.approx(0.6),
        "compile_cache_hit_pct": pytest.approx(50.0),
        "engine_executables": 2,
    }


def test_a_traced_rehearsal_of_a_decoder_cell_prints_all_nine():
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", "trinity_mini.pretrain_b2", "--seed", "3700000007",
         "--seconds", "2", "--trace", "1", "--rehearse"],
        cwd=ROOT, capture_output=True, text=True, timeout=900)
    assert done.returncode == 0, done.stderr[-2000:]
    metrics = json.loads(done.stdout.splitlines()[-1])["metrics"]
    for name in READERS:
        assert name in metrics, name
    value = {k: v["value"] for k, v in metrics.items()}
    assert value["engine_executables"] == 2
    traced = value["jax_trace_s"] - value["mlir_lower_s"]
    by_role = (value["forward_trace_s"] + value["backward_trace_s"]
               + value["optimizer_trace_s"])
    assert 0.8 * traced < by_role <= traced
    assert value["first_call_rest_s"] > 0.0
    assert value["append_backward_s"] > 0.0 < value["optimizer_build_s"]
