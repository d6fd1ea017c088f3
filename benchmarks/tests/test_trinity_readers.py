"""The four readers of the Trinity-Mini cell on a made-up trace (events of
known length, a map as ``opprof.instruction_phases`` gives it, the counts
of ``work_moe`` at this cell's shapes), nothing to read where the program
has no such op (the parent commit), no map or no device plane, and the
configuration's count of a whole step against a count by hand."""

import json
import os

import pytest

from benchmarks import work, work_moe
from benchmarks.reference import trinity_mini
from benchmarks.run import load_module

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
MS = 1_000_000  # ns
READERS = ("sigmoid_moe_ms_per_step", "gated_mlp_ms_per_step",
           "moe_gmm_w1024_roofline_pct", "flash_w2048_roofline_pct")


def _cfg():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "trinity_mini.json")) as f:
        return json.load(f)


def _facts():
    ops = {"/device:TPU:0": [
        (0 * MS, 12 * MS, "%pt.fused_attention.0_9 = bf16[64,3072,128]"),
        (12 * MS, 40 * MS, "%pt.fused_attention_grad.0_90.1 = (bf16[64"),
        (40 * MS, 50 * MS, "%custom-call.3 = bf16[49152,1024]"),
        (50 * MS, 74 * MS, "%custom-call.7 = bf16[49152,2048]"),
        (74 * MS, 77 * MS, "%fusion.3 = s32[49152]"),
        (77 * MS, 78 * MS, "%fusion.5 = f32[128]"),
        (78 * MS, 79 * MS, "%fusion.6 = f32[6144,8]"),
        (79 * MS, 85 * MS, "%fusion.7 = bf16[6144,6144]"),
        (85 * MS, 100 * MS, "%fusion.8 = f32[2048,6144]"),
        (100 * MS, 120 * MS, "%fusion.4 = f32[2048,25024]"),
    ]}

    def tagged(op_type, index, phase):
        return ("pt.%s.0_%d" % (op_type, index), op_type, phase)

    phases = {
        "pt.fused_attention.0_9": tagged("fused_attention", 9, "forward"),
        "pt.fused_attention_grad.0_90.1": tagged("fused_attention_grad", 90,
                                                 "backward"),
        "custom-call.3": tagged("moe_expert_mlp", 20, "forward"),
        "custom-call.7": tagged("moe_expert_mlp_grad", 80, "backward"),
        "fusion.3": tagged("moe_dispatch", 19, "forward"),
        "fusion.5": tagged("moe_bias_update", 200, "optimizer"),
        "fusion.6": tagged("moe_router", 18, "forward"),
        "fusion.7": tagged("gated_mlp", 12, "forward"),
        "fusion.8": tagged("gated_mlp_grad", 95, "backward"),
        "fusion.4": tagged("mul_grad", 99, "backward"),
    }
    return {"cfg": _cfg(), "rows": 2, "device_kind": "TPU v5 lite",
            "trace": {"ops": ops, "steps": 2, "busy_s": 0.12,
                      "window_s": 0.12},
            "instruction_phases": phases}


def test_the_four_readers_on_the_made_up_trace():
    facts = _facts()
    m = facts["cfg"]["model"]
    peak = work.peaks("TPU v5 lite")
    read = {name: load_module("layer_metrics", name).compute(facts)
            for name in READERS}
    # router 1 + bias update 1 + dispatch 3 + expert MLP 10 + 24 ms, 2 steps
    assert read["sigmoid_moe_ms_per_step"] == pytest.approx(39.0 / 2)
    assert read["gated_mlp_ms_per_step"] == pytest.approx(21.0 / 2)
    assert (m["seq_len"], m["mlp_layer_types"].count("sparse")) == (3072, 4)
    least = work_moe.grouped_matmul_least_seconds(6144, 2048, 1024, 16, peak)
    assert read["moe_gmm_w1024_roofline_pct"] == pytest.approx(
        100 * 4 * 2 * least / 0.034)
    window = work_moe.masked_attention_least_seconds(2, 32, 4, 3072, 128,
                                                     2048, peak)
    full = work_moe.masked_attention_least_seconds(2, 32, 4, 3072, 128,
                                                   None, peak)
    assert m["layer_types"] == ["sliding_attention"] * 4 + ["full_attention"]
    assert read["flash_w2048_roofline_pct"] == pytest.approx(
        100 * 2 * (4 * window + full) / 0.040)


@pytest.mark.parametrize("reader", READERS)
def test_nothing_to_read_gives_none(reader):
    compute = load_module("layer_metrics", reader).compute
    facts = _facts()
    assert compute(dict(facts, trace=None)) is None
    # a program without these ops (the parent commit): no event of theirs
    bare = dict(facts, instruction_phases={}, trace=dict(
        facts["trace"], ops={"/device:TPU:0": [
            (0, MS, "%fusion.1 = f32[8]")]}))
    assert compute(bare) is None


@pytest.mark.parametrize("reader", READERS[2:])
def test_a_share_cannot_pass_100_where_the_events_take_the_least_time(
        reader):
    """The work is the least time of exactly the events divided by: a trace
    whose kernels ran at the peak reads 100, and no real kernel runs
    faster (an expert layer that got fewer pairs than the expectation
    could: the reader's docstring says so)."""
    facts = _facts()
    m = facts["cfg"]["model"]
    peak = work.peaks("TPU v5 lite")
    if reader.startswith("moe"):
        least = 4 * work_moe.grouped_matmul_least_seconds(
            6144, 2048, 1024, 16, peak)
        name, op_type = "custom-call.3", "moe_expert_mlp"
    else:
        least = sum(work_moe.masked_attention_least_seconds(
            2, 32, 4, 3072, 128,
            2048 if kind == "sliding_attention" else None, peak)
            for kind in m["layer_types"])
        name, op_type = "pt.fused_attention.0_9", "fused_attention"
    ops = {"/device:TPU:0": [(0, int(least * 1e9), "%" + name + " = bf16[8]")]}
    facts["trace"] = dict(facts["trace"], ops=ops, steps=1)
    facts["instruction_phases"] = {name: (name, op_type, "forward")}
    assert load_module("layer_metrics", reader).compute(facts) == \
        pytest.approx(100.0, rel=1e-6)


def test_the_work_at_this_cells_shapes_by_hand():
    # window 2048 over 3072 queries: 1 + ... + 2048, then 1024 x 2048
    assert work_moe.keys_seen(3072, 2048) == 2048 * 2049 // 2 + 1024 * 2048
    assert work_moe.keys_seen(3072) == 3072 * 3073 // 2
    assert work_moe.pairs_held(6144, 8, 16, 128) == 6144
    fwd, bwd = work_moe.grouped_matmul_flops(6144, 2048, 1024)
    assert fwd == 3 * 2 * 6144 * 2048 * 1024 and bwd == 2 * fwd
    peak = work.peaks("TPU v5 lite")
    # 16 experts' weights nine times over weigh more than 6,144 rows do,
    # but the products still bind: 232 GFLOP at 197 TFLOP/s = 1.18 ms
    assert work_moe.grouped_matmul_least_seconds(
        6144, 2048, 1024, 16, peak) == pytest.approx((fwd + bwd) / 197e12)


def test_a_step_of_the_cell_is_12_tflop():
    cfg = _cfg()
    tokens, d, q, kv = 2 * 3072, 2048, 32 * 128, 4 * 128
    per_token = 5 * (3 * 2 * d * q + 2 * 2 * d * kv)   # q, gate, o; k, v
    per_token += 3 * 2 * d * 6144                      # the dense layer
    per_token += 4 * (2 * d * 128 + 3 * 2 * d * 1024)  # router, shared
    per_token += 2 * d * 25024                         # the head
    assert per_token == pytest.approx(503.05e6, rel=1e-4)
    experts = 4 * 9 * 2 * 6144 * d * 1024
    attention = 12 * 2 * 32 * 128 * (
        4 * work_moe.keys_seen(3072, 2048) + work_moe.keys_seen(3072))
    flops = trinity_mini.step_flops(cfg, 2)
    assert flops == 3 * tokens * per_token + experts + attention
    assert flops == pytest.approx(12.31e12, rel=0.01)
