"""The four readers of the Mellum2 cell on a made-up trace: four events
of known length, a map as ``opprof.instruction_phases`` gives it, and the
counts of ``work_moe``; and nothing to read where the program has no such
op (the parent commit), no map, or no device plane."""

import json
import os

import pytest

from benchmarks import work, work_moe
from benchmarks.layer_metrics import _op_types
from benchmarks.run import load_module

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
MS = 1_000_000  # ns


def _facts():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "mellum2_12b.json")) as f:
        cfg = json.load(f)
    ops = {"/device:TPU:0": [
        (0 * MS, 10 * MS, "%pt.fused_attention.0_9 = bf16[64,4096,128]"),
        (10 * MS, 30 * MS, "%pt.fused_attention_grad.0_90.1 = (bf16[64"),
        (30 * MS, 40 * MS, "%pt.moe_expert_mlp.0_20 = bf16[65536,2304]"),
        (40 * MS, 70 * MS, "%custom-call.7 = bf16[65536,896]"),
        (70 * MS, 75 * MS, "%fusion.3 = s32[65536]"),
        (75 * MS, 100 * MS, "%fusion.4 = f32[2304,24576]"),
    ]}
    phases = {
        "pt.fused_attention.0_9": ("pt.fused_attention.0_9",
                                   "fused_attention", "forward"),
        "pt.fused_attention_grad.0_90.1": (
            "pt.fused_attention_grad.0_90", "fused_attention_grad",
            "backward"),
        "pt.moe_expert_mlp.0_20": ("pt.moe_expert_mlp.0_20",
                                   "moe_expert_mlp", "forward"),
        "custom-call.7": ("pt.moe_expert_mlp_grad.0_80",
                          "moe_expert_mlp_grad", "backward"),
        "fusion.3": ("pt.moe_dispatch.0_19", "moe_dispatch", "forward"),
        "fusion.4": ("pt.mul_grad.0_99", "mul_grad", "backward"),
    }
    return {"cfg": cfg, "rows": 2, "device_kind": "TPU v5 lite",
            "trace": {"ops": ops, "steps": 1, "busy_s": 0.1,
                      "window_s": 0.1},
            "instruction_phases": phases}


def test_seconds_by_op_type_count_the_grad_with_its_op():
    facts = _facts()
    assert _op_types.seconds_of(facts, ("moe_expert_mlp",)) == \
        pytest.approx(0.040)
    assert _op_types.seconds_of(facts, ("moe_dispatch", "moe_combine")) == \
        pytest.approx(0.005)
    assert _op_types.seconds_of(facts, ("conv2d",)) is None


def test_the_four_readers_on_the_made_up_trace():
    facts = _facts()
    m = facts["cfg"]["model"]
    peak = work.peaks("TPU v5 lite")
    share = load_module("layer_metrics", "moe_share_pct").compute(facts)
    assert share == pytest.approx(45.0)
    gmm = load_module("layer_metrics", "moe_gmm_roofline_pct").compute(facts)
    least = work_moe.grouped_matmul_least_seconds(16384, 2304, 896, 16, peak)
    assert gmm == pytest.approx(100 * 4 * least / 0.040)
    flash = load_module("layer_metrics",
                        "flash_window_roofline_pct").compute(facts)
    window = work_moe.masked_attention_least_seconds(2, 32, 4, 4096, 128,
                                                     1024, peak)
    full = work_moe.masked_attention_least_seconds(2, 32, 4, 4096, 128,
                                                   None, peak)
    assert m["layer_types"] == ["sliding_attention"] * 3 + ["full_attention"]
    assert flash == pytest.approx(100 * (3 * window + full) / 0.030)
    assert load_module("layer_metrics", "flash_window_share_pct").compute(
        facts) == pytest.approx(30.0)


@pytest.mark.parametrize("reader", ["moe_share_pct", "moe_gmm_roofline_pct",
                                    "flash_window_roofline_pct",
                                    "flash_window_share_pct"])
def test_nothing_to_read_gives_none(reader):
    compute = load_module("layer_metrics", reader).compute
    facts = _facts()
    assert compute(dict(facts, trace=None)) is None
    # a program without these ops (the parent commit): no event of theirs
    bare = dict(facts, instruction_phases={}, trace=dict(
        facts["trace"], ops={"/device:TPU:0": [
            (0, MS, "%fusion.1 = f32[8]")]}))
    assert compute(bare) is None
