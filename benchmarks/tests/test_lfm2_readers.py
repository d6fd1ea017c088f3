"""The four readers of the LFM2-24B-A2B cell on a made-up trace (events of
known length, a map as ``opprof.instruction_phases`` gives it, the counts
of ``work_conv`` and ``work_moe`` at this cell's shapes), nothing to read
where the program has no such op (the parent commit), no map or no device
plane, ``work_conv`` against counts by hand, and the configuration's count
of a whole step against a count by hand."""

import json
import os

import pytest

from benchmarks import work, work_conv, work_moe
from benchmarks.reference import lfm2_moe
from benchmarks.run import load_module

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
MS = 1_000_000  # ns
READERS = ("short_conv_ms_per_step", "short_conv_roofline_pct",
           "flash_gqa64_roofline_pct", "moe_gmm_w1536_roofline_pct")


def _cfg():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "lfm2_24b_a2b.json")) as f:
        return json.load(f)


def _facts():
    ops = {"/device:TPU:0": [
        (0 * MS, 10 * MS, "%pt.fused_attention.0_30 = bf16[64,8192,64]"),
        (10 * MS, 38 * MS, "%pt.fused_attention_grad.0_140.1 = (bf16[64"),
        (38 * MS, 42 * MS, "%custom-call.3 = bf16[65536,1536]"),
        (42 * MS, 50 * MS, "%custom-call.7 = bf16[65536,2048]"),
        (50 * MS, 53 * MS, "%fusion.3 = bf16[2,8192,2048]"),
        (53 * MS, 62 * MS, "%fusion.5 = bf16[2,8192,6144]"),
        (62 * MS, 80 * MS, "%fusion.8 = bf16[16384,6144]"),
        (80 * MS, 100 * MS, "%fusion.4 = f32[2048,8192]"),
    ]}

    def tagged(op_type, index, phase):
        return ("pt.%s.0_%d" % (op_type, index), op_type, phase)

    phases = {
        "pt.fused_attention.0_30": tagged("fused_attention", 30, "forward"),
        "pt.fused_attention_grad.0_140.1": tagged("fused_attention_grad",
                                                  140, "backward"),
        "custom-call.3": tagged("moe_expert_mlp", 45, "forward"),
        "custom-call.7": tagged("moe_expert_mlp_grad", 120, "backward"),
        "fusion.3": tagged("gated_short_conv", 3, "forward"),
        "fusion.5": tagged("gated_short_conv_grad", 170, "backward"),
        "fusion.8": tagged("mul", 2, "forward"),       # the input projection
        "fusion.4": tagged("matmul_grad", 99, "backward"),
    }
    return {"cfg": _cfg(), "rows": 2, "device_kind": "TPU v5 lite",
            "trace": {"ops": ops, "steps": 2, "busy_s": 0.1,
                      "window_s": 0.1},
            "instruction_phases": phases}


def _shape(facts):
    m = facts["cfg"]["model"]
    return m, facts["rows"] * m["seq_len"]


def test_the_four_readers_on_the_made_up_trace():
    facts = _facts()
    m, tokens = _shape(facts)
    peak = work.peaks("TPU v5 lite")
    read = {name: load_module("layer_metrics", name).compute(facts)
            for name in READERS}
    # the op 3 ms + its gradient 9 ms over 2 steps; the projections are not
    # in it
    assert read["short_conv_ms_per_step"] == pytest.approx(12.0 / 2)
    assert m["layer_types"].count("conv") == 4 and m["conv_L_cache"] == 3
    conv = work_conv.short_conv_least_seconds(tokens, 2048, 3, peak)
    assert read["short_conv_roofline_pct"] == pytest.approx(
        100 * 4 * 2 * conv / 0.012)
    # one layer of five has attention: 32 / 8 heads of 64, causal, no window
    full = work_moe.masked_attention_least_seconds(
        2, 32, 8, m["seq_len"], 64, None, peak)
    assert read["flash_gqa64_roofline_pct"] == pytest.approx(
        100 * 2 * full / 0.038)
    assert m["mlp_layer_types"].count("sparse") == 4
    pairs = tokens * 4 * 8 // 64
    gmm = work_moe.grouped_matmul_least_seconds(pairs, 2048, 1536, 8, peak)
    assert read["moe_gmm_w1536_roofline_pct"] == pytest.approx(
        100 * 4 * 2 * gmm / 0.012)


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


@pytest.mark.parametrize("reader", READERS[1:])
def test_a_share_cannot_pass_100_where_the_events_take_the_least_time(
        reader):
    """The work is the least time of exactly the events divided by: a trace
    whose events ran at the peak reads 100, and nothing runs faster (an
    expert layer that got fewer pairs than the expectation could: the
    reader's docstring says so; a part of the conv op that XLA books to a
    neighbouring projection would too: PERF.md section 5)."""
    facts = _facts()
    m, tokens = _shape(facts)
    peak = work.peaks("TPU v5 lite")
    if reader.startswith("moe"):
        least = 4 * work_moe.grouped_matmul_least_seconds(
            tokens * 4 * 8 // 64, 2048, 1536, 8, peak)
        name, op_type = "custom-call.3", "moe_expert_mlp"
    elif reader.startswith("short_conv"):
        least = 4 * work_conv.short_conv_least_seconds(tokens, 2048, 3, peak)
        name, op_type = "fusion.3", "gated_short_conv"
    else:
        least = work_moe.masked_attention_least_seconds(
            2, 32, 8, m["seq_len"], 64, None, peak)
        name, op_type = "pt.fused_attention.0_30", "fused_attention"
    ops = {"/device:TPU:0": [(0, int(least * 1e9), "%" + name + " = bf16[8]")]}
    facts["trace"] = dict(facts["trace"], ops=ops, steps=1)
    facts["instruction_phases"] = {name: (name, op_type, "forward")}
    assert load_module("layer_metrics", reader).compute(facts) == \
        pytest.approx(100.0, rel=1e-6)


def test_the_conv_ops_work_by_hand():
    # 4 tokens of 8 channels, 3 taps: forward (2 x 3 + 2) and backward
    # (4 x 3 + 4) operations a token and channel
    assert work_conv.short_conv_flops(4, 8, 3) == (8 * 32, 16 * 32)
    # in [4, 24] + out [4, 8] forward; in, cotangent [4, 8] and the
    # input's gradient [4, 24] backward: 11 x 32 elements of 2 bytes, and
    # the float32 filter [8, 3] read twice and its gradient written
    assert work_conv.short_conv_bytes(4, 8, 3) == 11 * 32 * 2 + 3 * 24 * 4
    assert work_conv.short_conv_bytes(4, 8, 3, 4) == 11 * 32 * 4 + 3 * 24 * 4
    peak = work.peaks("TPU v5 lite")
    # at the cell's widest, 2 x 8192 tokens of 2048 channels: 738 MB at
    # 819 GB/s = 0.90 ms a layer; the 0.8 GFLOP are nothing beside them
    least = work_conv.short_conv_least_seconds(16384, 2048, 3, peak)
    assert least == pytest.approx(
        (11 * 16384 * 2048 * 2 + 3 * 2048 * 3 * 4) / 819e9)
    assert least == pytest.approx(0.901e-3, rel=1e-3)
    assert sum(work_conv.short_conv_flops(16384, 2048, 3)) / 197e12 < least
    # a longer filter moves the same activations
    assert work_conv.short_conv_bytes(4, 8, 4) - \
        work_conv.short_conv_bytes(4, 8, 3) == 3 * 8 * 4


def test_the_other_work_at_this_cells_shapes_by_hand():
    m = _cfg()["model"]
    seq = m["seq_len"]
    assert work_moe.keys_seen(seq) == seq * (seq + 1) // 2
    assert work_moe.pairs_held(2 * seq, 4, 8, 64) == seq
    fwd, bwd = work_moe.grouped_matmul_flops(seq, 2048, 1536)
    assert fwd == 3 * 2 * seq * 2048 * 1536 and bwd == 2 * fwd
    # 32 query heads on 8 key/value heads of 64: Q, O, dO, dQ and K, V, dK,
    # dV once each
    assert work_moe.grouped_attention_bytes(2, 32, 8, seq, 64) == \
        4 * 2 * 40 * seq * 64 * 2


def test_a_step_of_the_cell_by_hand():
    cfg = _cfg()
    m = cfg["model"]
    seq = m["seq_len"]
    tokens, d = 2 * seq, 2048
    per_token = 4 * (2 * d * 3 * d + 2 * d * d + 8 * d)   # in, out, taps
    per_token += 2 * 2 * d * 2048 + 2 * 2 * d * 512       # q, o; k, v
    per_token += 3 * 2 * d * 11776                        # the dense layer
    per_token += 4 * 2 * d * 64                           # the routers
    per_token += 2 * d * 8192                             # the tied head
    assert per_token == pytest.approx(334.6e6, rel=1e-3)
    experts = 4 * 9 * 2 * (tokens * 4 * 8 // 64) * d * 1536
    attention = 12 * 2 * 32 * 64 * work_moe.keys_seen(seq)
    flops = lfm2_moe.step_flops(cfg, 2)
    assert flops == 3 * tokens * per_token + experts + attention
    # forward 406 MFLOP a token at 8192 positions (ISSUE 35's count)
    if seq == 8192:
        assert flops / (3 * tokens) == pytest.approx(406e6, rel=0.01)
        assert flops == pytest.approx(19.96e12, rel=0.01)
