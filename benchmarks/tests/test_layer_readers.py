"""The per-layer readers that read the program's own tracing (PR 26): the
phase join on the recorded v5e trace with a map made here (the recorded
event names are cut at 60 characters; the instruction names are whole),
and the span readers through a traced rehearsal of a cell."""

import json
import os
import subprocess
import sys

import pytest

from benchmarks import trace_reduce as tr
from benchmarks.run import load_module
from benchmarks.layer_metrics import _phases, _spans

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
TRACE = os.path.join(os.path.dirname(HERE), "testdata",
                     "bert_b8_s2048_two_steps.xplane.pb.gz")
DEVICE_READERS = ("forward_ms_per_step", "backward_ms_per_step",
                  "optimizer_ms_per_step", "unattributed_share_pct")


@pytest.fixture(scope="module")
def recorded():
    return tr.reduce(tr.load(TRACE))


def _made_up_map(trace):
    """A map as ``opprof.instruction_phases`` gives it, made from the
    recorded names: the flash kernels by their scope, and the fusion
    families that PERF.md read by eye as Adam's, as the optimizer's."""
    phases = {}
    for _, _, name in trace["ops"]["/device:TPU:0"]:
        instr = _phases.instruction_name(name)
        family = tr.family(name)
        if family == "pt.fused_attention":
            phases[instr] = ("pt.fused_attention.0_1", "fused_attention",
                             "forward")
        elif family == "pt.fused_attention_grad":
            phases[instr] = ("pt.fused_attention_grad.0_2",
                             "fused_attention_grad", "backward")
        elif family == "divide_subtract_fusion":
            phases[instr] = ("pt.adam.0_3", "adam", "optimizer")
        elif family == "copy":
            phases[instr] = (None, None, None)
        # every other instruction: not in the map at all
    return phases


def test_instruction_name_is_the_text_between_percent_and_equals():
    assert _phases.instruction_name(
        "%fusion.2024 = s32[1,16,8,128]{3,2,1,0:T(8,128)S(1)} fusion("
    ) == "fusion.2024"
    assert _phases.instruction_name(
        "%pt.fused_attention_grad.0_476.3 = (bf16[96,2048,64]"
    ) == "pt.fused_attention_grad.0_476.3"
    assert _phases.instruction_name("%copy-done.29") == "copy-done.29"


def test_phase_join_on_the_recorded_trace(recorded):
    seconds = _phases.join(recorded["ops"], _made_up_map(recorded))
    families = recorded["by_family_s"]
    assert seconds["forward"] == pytest.approx(
        families["pt.fused_attention"])
    assert seconds["backward"] == pytest.approx(
        families["pt.fused_attention_grad"])
    assert seconds["optimizer"] == pytest.approx(
        families["divide_subtract_fusion"])
    # the four parts are all of the device's operation time, which on
    # this trace (no operation overlaps another) is its busy time
    assert sum(seconds.values()) == pytest.approx(
        sum(families.values()))
    assert sum(seconds.values()) == pytest.approx(recorded["busy_s"],
                                                  rel=1e-3)
    # a map of another executable joins nothing
    assert _phases.join(recorded["ops"], {"fusion.999999": (
        "pt.mul.0_0", "mul", "forward")}) is None


def test_device_readers_read_the_join_and_nothing_without_it(recorded):
    facts = {"trace": recorded,
             "seconds_by_phase": _phases.join(recorded["ops"],
                                              _made_up_map(recorded))}
    values = {name: load_module("layer_metrics", name).compute(facts)
              for name in DEVICE_READERS}
    busy_ms = 1000.0 * recorded["busy_s"] / recorded["steps"]
    # Adam's updates: the 22.6 ms a step that PERF.md section 5 read
    assert values["optimizer_ms_per_step"] == pytest.approx(22.6, abs=0.3)
    assert (values["forward_ms_per_step"] + values["backward_ms_per_step"]
            + values["optimizer_ms_per_step"]
            + values["unattributed_share_pct"] / 100.0 * busy_ms
            ) == pytest.approx(busy_ms, rel=1e-3)
    # no device plane (a rehearsal), or a program with no map to give
    # (the notes are empty here: nothing ran): nothing to read
    for name in DEVICE_READERS:
        reader = load_module("layer_metrics", name)
        assert reader.compute({"trace": None}) is None
        assert reader.compute({"trace": recorded}) is None


def test_step_split_takes_the_jitted_call_out_of_the_engine():
    from paddle_tpu.observability.tracing import SpanRecord

    def rec(name, ts, dur, tid=1):
        return SpanRecord(name, float(ts), float(dur), tid, 0, None)

    spans = []
    for at in (0, 10000):  # two steps of 3 ms, 1 ms of them in JAX's call
        spans += [rec("executor.run", at, 3000), rec("step", at + 100, 2800),
                  rec("feed", at + 200, 300), rec("run", at + 600, 1000),
                  rec("writeback", at + 1700, 400)]
    # the seam's spans of set-up lie outside every executor.run
    spans += [rec("trace", -90000, 50000), rec("compile", -30000, 20000)]
    engine_ms, call_ms = _spans.step_split(spans)
    assert call_ms == pytest.approx(1.0)
    assert engine_ms == pytest.approx(2.0)
    assert _spans.step_split([rec("trace", 0, 5)]) is None


@pytest.fixture(scope="module")
def traced_rehearsal():
    """``--rehearse --trace 1`` of the cell whose rehearsal steps are
    short enough for the traced slice to fit: the result line."""
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", "bert_base_s2048.pretrain_b8", "--seed",
         "3000000019", "--seconds", "2", "--trace", "1", "--rehearse"],
        capture_output=True, text=True, timeout=900, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_span_readers_through_a_traced_rehearsal(traced_rehearsal):
    metrics = {k: v["value"] for k, v in traced_rehearsal["metrics"].items()}
    assert traced_rehearsal["correct"]
    assert traced_rehearsal["device"]["platform"] == "cpu"
    # no device plane in a rehearsal: the device readers are left out
    assert not set(DEVICE_READERS) & set(metrics)
    # the program's split of the step against the driver's stopwatch
    # round the same calls (a CPU's numbers: compared, never reported)
    inside = metrics["engine_self_ms_per_step"] \
        + metrics["jit_call_ms_per_step"]
    assert 0.0 < metrics["engine_self_ms_per_step"] < inside
    assert inside == pytest.approx(metrics["dispatch_ms_per_step.train"],
                                   rel=0.15)
    assert inside < metrics["dispatch_ms_per_step.train"]
    # the cache-miss seam, recorded with nothing switched on in set-up
    assert 0.0 < metrics["desc_build_s"] < metrics["first_step_s"]
    assert 0.0 < metrics["jax_trace_s"] < metrics["first_step_s"] + 5.0
