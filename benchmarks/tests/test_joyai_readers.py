"""The readers of the JoyAI-LLM-Flash cell on a made-up trace (events of
known length, a map as ``opprof.instruction_phases`` gives it), nothing to
read where the program has no such op (the parent commit), no map or no
device plane, ``work_mla`` against counts by hand and against ``work_moe``
at equal widths, the held experts' capacity, and the configuration's count
of a whole step against a count by hand."""

import json
import os

import pytest

from benchmarks import work, work_mla, work_moe
from benchmarks.reference import joyai_llm_flash
from benchmarks.run import load_module

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
MS = 1_000_000  # ns
READERS = ("flash_mla_roofline_pct", "mla_ms_per_step")
LAYER_READERS = ("moe_w768_ms_per_step", "moe_gmm_w768_roofline_pct",
                 "dense_and_shared_mlp_ms_per_step")
PEAK = work.peaks("TPU v5 lite")


def _cfg():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "joyai_llm_flash.json")) as f:
        return json.load(f)


def _facts():
    ops = {"/device:TPU:0": [
        (0 * MS, 3 * MS, "%fusion.3 = bf16[1,32,4096,192]"),
        (3 * MS, 10 * MS, "%fusion.5 = f32[1536,6144]"),
        (10 * MS, 14 * MS, "%pt.fused_attention.0_40.1 = bf16[1,32,4096]"),
        (14 * MS, 26 * MS, "%pt.fused_attention_grad.0_90.2 = bf16[1,32]"),
        (26 * MS, 40 * MS, "%fusion.8 = bf16[4096,1536]"),
        (40 * MS, 42 * MS, "%fusion.9 = bf16[4096,2048]"),
    ]}

    def tagged(op_type, index, phase):
        return ("pt.%s.0_%d" % (op_type, index), op_type, phase)

    phases = {
        "fusion.3": tagged("latent_qkv", 3, "forward"),
        "fusion.5": tagged("latent_qkv_grad", 170, "backward"),
        "pt.fused_attention.0_40.1": tagged("fused_attention", 40,
                                            "forward"),
        "pt.fused_attention_grad.0_90.2": tagged("fused_attention_grad", 90,
                                                 "backward"),
        "fusion.8": tagged("mul", 2, "forward"),      # the down-projection
        "fusion.9": tagged("rms_norm", 4, "forward"),
    }
    return {"cfg": _cfg(), "rows": 1, "device_kind": "TPU v5 lite",
            "trace": {"ops": ops, "steps": 2, "busy_s": 0.042,
                      "window_s": 0.042},
            "instruction_phases": phases}


def test_the_two_readers_on_the_made_up_trace():
    facts = _facts()
    m = facts["cfg"]["model"]
    read = {name: load_module("layer_metrics", name).compute(facts)
            for name in READERS}
    # latent_qkv 3 + 7, attention 4 + 12 ms over 2 steps; the projections
    # and the norms are not in it
    assert read["mla_ms_per_step"] == pytest.approx(26.0 / 2)
    # 5 layers and the MTP module, each one attention of 32 heads, causal,
    # q.k 192 and values 128, as many K/V heads as Q heads
    one = work_mla.mla_attention_least_seconds(1, 32, m["seq_len"], 192, 128,
                                               PEAK)
    assert read["flash_mla_roofline_pct"] == pytest.approx(
        100 * 6 * 2 * one / 0.016)


@pytest.mark.parametrize("reader", READERS)
def test_nothing_to_read_gives_none(reader):
    compute = load_module("layer_metrics", reader).compute
    facts = _facts()
    assert compute(dict(facts, trace=None)) is None
    assert compute(dict(facts, trace=dict(facts["trace"], steps=0))) is None
    # a program without these ops (the parent commit): no event of theirs
    bare = dict(facts, instruction_phases={}, trace=dict(
        facts["trace"], ops={"/device:TPU:0": [
            (0, MS, "%fusion.1 = f32[8]")]}))
    assert compute(bare) is None
    # a program without the map of instructions to ops (the flash reader
    # finds its events by the name the trace gives them, not by the map)
    if reader != "flash_mla_roofline_pct":
        assert compute(dict(facts, instruction_phases=None)) is None


def test_the_attention_time_alone_reads_nothing_of_latent_attention():
    """A cell whose attention is not latent (no ``latent_qkv`` events)
    reads no ``mla_ms_per_step``, though it has flash events."""
    facts = _facts()
    phases = {k: v for k, v in facts["instruction_phases"].items()
              if not v[1].startswith("latent_qkv")}
    assert load_module("layer_metrics", "mla_ms_per_step").compute(
        dict(facts, instruction_phases=phases)) is None


def test_the_share_reads_100_where_the_events_take_the_least_time():
    """A trace whose attention events ran at the roofline reads 100."""
    facts = _facts()
    m = facts["cfg"]["model"]
    least = 6 * work_mla.mla_attention_least_seconds(
        1, 32, m["seq_len"], 192, 128, PEAK)
    facts["trace"] = dict(facts["trace"], steps=1, ops={"/device:TPU:0": [
        (0, int(round(least * 1e9)), "%pt.fused_attention.0_4.1 = bf16[8]")]})
    assert load_module("layer_metrics", "flash_mla_roofline_pct").compute(
        facts) == pytest.approx(100.0, rel=1e-6)


def test_the_attention_work_by_hand():
    # 1 row of 8 positions, 2 heads: 36 (query, key) pairs a head seen
    # causally; forward 2 x 72 x (6 + 4), backward 2 x 72 x (12 + 8)
    fwd, bwd = work_mla.mla_attention_flops(1, 2, 8, 6, 4)
    assert (fwd, bwd) == (2 * 72 * 10, 2 * 72 * 20)
    # q, k, dq, dk at 6 and v, o, do, dv at 4, 2 heads of 8 positions
    assert work_mla.mla_attention_bytes(1, 2, 8, 6, 4) == 16 * (24 + 16) * 2
    # at the cell's 1 x 4096 positions: 41.9 MFLOP a token forward, 0.515
    # TFLOP forward and backward, 2.62 ms at 197 TFLOP/s against 0.41 ms
    # for the bytes: the operations bound
    fwd, bwd = work_mla.mla_attention_flops(1, 32, 4096, 192, 128)
    assert fwd / 4096 == pytest.approx(41.95e6, rel=1e-3)
    assert fwd + bwd == pytest.approx(0.5155e12, rel=1e-3)
    assert work_mla.mla_attention_bytes(1, 32, 4096, 192, 128) / 819e9 == \
        pytest.approx(0.41e-3, rel=0.01)
    assert work_mla.mla_attention_least_seconds(
        1, 32, 4096, 192, 128, PEAK) == pytest.approx((fwd + bwd) / 197e12)


@pytest.mark.parametrize("rows, heads, seq, dim", [
    (1, 32, 4096, 128), (2, 12, 2048, 64), (8, 12, 2048, 64),
    (1, 4, 96, 8)])
def test_equal_widths_are_the_grouped_count_with_every_head(rows, heads, seq,
                                                            dim):
    """At q.k and v of one width, with as many K/V heads as Q heads, the
    least time is ``work_moe.masked_attention_least_seconds``'s."""
    assert work_mla.mla_attention_least_seconds(
        rows, heads, seq, dim, dim, PEAK) == pytest.approx(
            work_moe.masked_attention_least_seconds(
                rows, heads, heads, seq, dim, None, PEAK), rel=1e-12)
    assert work_mla.mla_attention_flops(rows, heads, seq, dim, dim) == \
        work_moe.masked_attention_flops(rows, heads, seq, dim)
    assert work_mla.mla_attention_bytes(rows, heads, seq, dim, dim) == \
        work_moe.grouped_attention_bytes(rows, heads, heads, seq, dim)


def test_a_step_of_the_cell_by_hand():
    cfg = _cfg()
    m = cfg["model"]
    seq, d, v = m["seq_len"], 2048, 16160
    tokens = seq
    mla = 2 * (d * 1536 + 1536 * 32 * 192 + d * 576 + 512 * 32 * 256
               + 32 * 128 * d)
    assert mla == pytest.approx(52.7e6, rel=1e-3)
    per_token = 6 * mla                              # 5 layers and the MTP
    per_token += 6 * d * 7168                        # the dense layer
    per_token += 5 * (2 * d * 256 + 6 * d * 768)     # routers, shared
    per_token += 2 * 2 * d * d                       # eh_proj
    experts = 5 * 9 * 2 * (tokens * 8 * 16 // 256) * d * 768
    attention = 6 * sum(work_mla.mla_attention_flops(1, 32, seq, 192, 128))
    head = 3 * (tokens + seq - 1) * 2 * d * v
    flops = joyai_llm_flash.step_flops(cfg, 1)
    assert flops == 3 * tokens * per_token + experts + attention + head
    if seq == 4096:
        # 2.64 GFLOP a token trained (2.77 if the scores that the backward
        # makes again counted, which work.py's convention leaves out),
        # latent attention (its projections and the attention) two thirds
        # of the step
        assert flops / tokens == pytest.approx(2.64e9, rel=0.01)
        latent = 3 * tokens * 6 * mla + attention
        assert latent / flops == pytest.approx(0.66, abs=0.03)


def _layer_facts():
    """Two steps of the expert layers' and the gated MLPs' events, beside a
    projection that is neither's."""
    ops, phases, at = [], {}, 0
    for i, (op_type, ms) in enumerate((
            ("moe_router", 1), ("moe_dispatch", 1), ("moe_expert_mlp", 4),
            ("moe_combine", 1), ("gated_mlp", 2), ("mul", 5),
            ("moe_expert_mlp_grad", 6), ("moe_dispatch_grad", 1),
            ("moe_router_grad", 1), ("gated_mlp_grad", 3),
            ("moe_bias_update", 1))):
        ops.append((at, at + ms * MS, "%%fusion.%d = bf16[8]" % i))
        phases["fusion.%d" % i] = ("pt.%s.0_%d" % (op_type, i), op_type,
                                   "backward" if "grad" in op_type
                                   else "forward")
        at += ms * MS
    return {"cfg": _cfg(), "rows": 1, "device_kind": "TPU v5 lite",
            "trace": {"ops": {"/device:TPU:0": ops}, "steps": 2,
                      "busy_s": at / 1e9, "window_s": at / 1e9},
            "instruction_phases": phases}


def test_the_expert_layer_and_mlp_readers_on_the_made_up_trace():
    facts = _layer_facts()
    m = facts["cfg"]["model"]
    read = {name: load_module("layer_metrics", name).compute(facts)
            for name in LAYER_READERS}
    # router 1 + 1, dispatch 1 + 1, MLP 4 + 6, combine 1, bias update 1
    assert read["moe_w768_ms_per_step"] == pytest.approx(16.0 / 2)
    assert read["dense_and_shared_mlp_ms_per_step"] == pytest.approx(5.0 / 2)
    # four expert layers and the MTP module's, 2,048 pairs each, over the
    # MLP's 10 ms
    one = work_moe.grouped_matmul_least_seconds(
        2048, 2048, 768, 16, PEAK) if m["seq_len"] == 4096 else None
    if one is not None:
        assert read["moe_gmm_w768_roofline_pct"] == pytest.approx(
            100 * 5 * 2 * one / 0.010)


@pytest.mark.parametrize("reader", LAYER_READERS)
def test_nothing_of_the_expert_layer_to_read_gives_none(reader):
    compute = load_module("layer_metrics", reader).compute
    facts = _layer_facts()
    assert compute(facts) is not None
    assert compute(dict(facts, trace=None)) is None
    assert compute(dict(facts, trace=dict(facts["trace"], steps=0))) is None
    assert compute(dict(facts, instruction_phases=None)) is None
    assert compute(dict(facts, instruction_phases={})) is None


def test_the_held_experts_capacity_is_their_expected_share():
    """At 1 x 4096 positions, 8 of 256 a token, 16 held: 128 pairs a held
    expert in expectation and at most, 2,048 a layer; a smaller factor
    caps the layer below the expectation, none leaves it."""
    m = _cfg()["model"]
    assert m["expert_capacity_factor"] == 1.0
    assert joyai_llm_flash.capacity(m, 4096) == 128
    assert joyai_llm_flash.held_pairs(m, 4096) == 2048 == \
        work_moe.pairs_held(4096, 8, 16, 256)
    assert joyai_llm_flash.held_pairs(
        dict(m, expert_capacity_factor=0.5), 4096) == 16 * 64
    assert joyai_llm_flash.capacity(
        dict(m, expert_capacity_factor=None), 4096) is None
    # the rehearsal's 2 x 64 positions, 8 of 32 a token: 32 a held expert
    assert joyai_llm_flash.capacity(
        dict(m, router_experts=32), 128) == 32
