"""The reduction from a trace to numbers, on synthetic intervals and on a
recorded trace: two steps of ``bert_base_s2048.pretrain_b8`` on a v5e
(PR 25, cut to the ``XLA Ops``, ``XLA Modules`` and ``Steps`` lines, names
cut to 60 characters)."""

import os

import pytest

from benchmarks import trace_reduce as tr

TRACE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "testdata",
    "bert_b8_s2048_two_steps.xplane.pb.gz")


def test_union_counts_overlap_once():
    assert tr.union_ns([]) == 0
    assert tr.union_ns([(0, 10), (5, 15), (20, 30)]) == 25
    assert tr.union_ns([(0, 10), (2, 3)]) == 10
    assert tr.union_ns([(20, 30), (0, 10), (10, 12)]) == 22


def test_gaps_are_what_the_union_leaves():
    spans = [(10, 20), (15, 25), (40, 50)]
    gaps = tr.gaps_ns(spans, 0, 60)
    assert gaps == [(0, 10), (25, 40), (50, 60)]
    assert sum(e - s for s, e in gaps) + tr.union_ns(spans) == 60
    assert tr.gaps_ns(spans, 12, 45) == [(25, 40)]


def test_family_drops_the_counters():
    assert tr.family("%fusion.1206 = f32[768]{0} fusion(...)") == "fusion"
    assert tr.family("%pt.fused_attention_grad.0_476.3 = (bf16[96,2048,64]"
                     ) == "pt.fused_attention_grad"
    assert tr.family("%divide_subtract_fusion = (f32[768,30522]"
                     ) == "divide_subtract_fusion"
    assert tr.family("%copy-done.29 = bf16[8]") == "copy-done"


@pytest.fixture(scope="module")
def recorded():
    profile = tr.load(TRACE)
    return profile, tr.reduce(profile)


def test_recorded_trace_has_one_device_and_the_drivers_two_steps(recorded):
    _, r = recorded
    assert r["devices"] == 1
    assert r["steps"] == 2


def test_busy_time_agrees_with_the_devices_own_step_events(recorded):
    profile, r = recorded
    plane = profile.find_plane_with_name("/device:TPU:0")
    steps = [ev.duration_ns for line in plane.lines if line.name == "Steps"
             for ev in line.events]
    assert len(steps) == 2
    # the device's own span of a step holds its operations and the gaps
    # between them: busy is under it, and within 1 % of it
    assert r["busy_s"] <= sum(steps) / 1e9
    assert r["busy_s"] == pytest.approx(sum(steps) / 1e9, rel=1e-2)
    assert r["busy_s"] < r["window_s"] < 1.01 * r["busy_s"]
    # an independent count: a boolean timeline at 100 ns
    ops = r["ops"]["/device:TPU:0"]
    lo = min(s for s, _, _ in ops)
    hi = max(e for _, e, _ in ops)
    line = bytearray(int(hi - lo) // 100 + 2)
    for s, e, _ in ops:
        a, b = int(s - lo) // 100, int(e - lo) // 100
        line[a:b] = b"\x01" * (b - a)
    assert sum(line) * 100 / 1e9 == pytest.approx(r["busy_s"], rel=2e-2)


def test_attention_kernels_are_found_by_their_scope(recorded):
    _, r = recorded
    ops = r["ops"]["/device:TPU:0"]
    calls = [n for _, _, n in ops if n.startswith("%pt.fused_attention")]
    assert len(calls) == 2 * 3 * 12  # steps x kernels x layers
    seconds = tr.seconds_matching(r["ops"], ("pt.fused_attention",))
    families = r["by_family_s"]
    assert seconds == pytest.approx(
        families["pt.fused_attention"] + families["pt.fused_attention_grad"])
    # about 69 ms of a 177 ms step
    assert 0.35 < seconds / r["busy_s"] < 0.45
    assert r["top_ops"][0][0] == "pt.fused_attention_grad"


def test_idle_gaps_name_the_drivers_span(recorded):
    _, r = recorded
    total = sum(seconds for _, seconds in r["idle_gaps"])
    assert total == pytest.approx(r["window_s"] - r["busy_s"], rel=1e-6)
    assert {name for name, _ in r["idle_gaps"]} <= {
        "bench_step", "bench_host_read", "between_driver_spans"}
