"""The readers of the Nemotron-3-Super cell on a made-up trace (events of
known length, a map as ``opprof.instruction_phases`` gives it), nothing to
read where the program has no such op (the parent commit), no map or no
device plane, the held experts' capacity, and the configuration's count
of a whole step against a count by hand."""

import json
import os

import pytest

from benchmarks import work, work_moe, work_ssd
from benchmarks.reference import nemotron3_super
from benchmarks.run import load_module

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
MS = 1_000_000  # ns
READERS = ("latent_moe_ms_per_step", "moe_gmm_latent_roofline_pct")
PEAK = work.peaks("TPU v5 lite")


def _cfg():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "nemotron3_super_120b_a12b.json")) as f:
        return json.load(f)


def _facts():
    kinds = [("moe_router", 1), ("moe_router_grad", 1), ("moe_latent", 2),
             ("moe_latent_grad", 3), ("moe_dispatch", 1),
             ("moe_expert_mlp", 4), ("moe_expert_mlp_grad", 6),
             ("moe_combine", 1), ("moe_bias_update", 1), ("mul", 5),
             ("ssd_scan", 2)]
    ops, phases, at = [], {}, 0
    for i, (op_type, ms) in enumerate(kinds):
        name = "fusion.%d" % i
        ops.append((at, at + ms * MS, "%%%s = bf16[8]" % name))
        phases[name] = ("pt.%s.0_%d" % (op_type, i), op_type,
                        "backward" if op_type.endswith("_grad")
                        else "forward")
        at += ms * MS
    return {"cfg": _cfg(), "rows": 1, "device_kind": "TPU v5 lite",
            "trace": {"ops": {"/device:TPU:0": ops}, "steps": 2,
                      "busy_s": at / 1e9, "window_s": at / 1e9},
            "instruction_phases": phases}


def test_the_two_readers_on_the_made_up_trace():
    facts = _facts()
    read = {name: load_module("layer_metrics", name).compute(facts)
            for name in READERS}
    # router 1 + 1, latent projections 2 + 3, dispatch 1, MLP 4 + 6,
    # combine 1, bias update 1 over 2 steps; the shared expert's ``mul``
    # and the scan are not in it
    assert read["latent_moe_ms_per_step"] == pytest.approx(20.0 / 2)
    # five expert layers and the MTP module's, 1,408 pairs each at the
    # latent's 1,024 and the experts' 2,688, over the MLP's 10 ms
    one = 2.0 / 3.0 * work_moe.grouped_matmul_least_seconds(
        1408, 1024, 2688, 8, PEAK)
    assert read["moe_gmm_latent_roofline_pct"] == pytest.approx(
        100 * 6 * 2 * one / 0.010)


@pytest.mark.parametrize("reader", READERS)
def test_nothing_to_read_gives_none(reader):
    compute = load_module("layer_metrics", reader).compute
    facts = _facts()
    assert compute(facts) is not None
    assert compute(dict(facts, trace=None)) is None
    assert compute(dict(facts, trace=dict(facts["trace"], steps=0))) is None
    assert compute(dict(facts, instruction_phases=None)) is None
    # a program without these ops (the parent commit): no event of theirs
    assert compute(dict(facts, instruction_phases={})) is None


def test_the_share_reads_100_where_the_events_take_the_least_time():
    """A trace whose expert MLP events ran at the roofline reads 100."""
    facts = _facts()
    least = 6 * 2.0 / 3.0 * work_moe.grouped_matmul_least_seconds(
        1408, 1024, 2688, 8, PEAK)
    facts["trace"] = dict(facts["trace"], steps=1, ops={"/device:TPU:0": [
        (0, int(round(least * 1e9)), "%fusion.5 = bf16[8]")]})
    assert load_module("layer_metrics", "moe_gmm_latent_roofline_pct"
                       ).compute(facts) == pytest.approx(100.0, rel=1e-6)


def test_the_held_experts_capacity_is_their_expected_share():
    """At 1 x 4096 positions, 22 of 512 a token, 8 held: 176 pairs a held
    expert in expectation and at most, 1,408 a layer, which the dispatch's
    buffer holds in whole tiles of 256 rows (1,536), not 4,096 x 22."""
    from paddle_tpu.ops import moe_ops

    m = _cfg()["model"]
    assert nemotron3_super.capacity(m, 4096) == 176
    assert nemotron3_super.held_pairs(m, 4096) == 1408 == \
        work_moe.pairs_held(4096, 22, 8, 512)
    share = m["expert_capacity_factor"] * 22 / 512
    assert moe_ops.buffer_rows(4096, 22, {
        "experts_held": 8, "capacity_per_token": share}) == 1536
    assert moe_ops.buffer_rows(4096, 22, {"experts_held": 8}) == 4096 * 8


def test_a_step_of_the_cell_by_hand():
    cfg = _cfg()
    m = cfg["model"]
    seq = m["seq_len"]
    tokens, d, latent = seq, 4096, 1024
    per_token = 5 * (2 * d * 2320 + 2 * 1024 * d + 2 * 4 * 1280)  # mixers
    # routers, latent projections and shared experts: 5 layers and MTP's
    per_token += 6 * (2 * d * 512 + 2 * 2 * d * latent + 2 * 2 * d * 5376)
    per_token += 2 * (2 * 2 * d * 512 + 2 * 2 * d * 128)  # q, o; k, v
    per_token += 2 * 2 * d * d                            # eh_proj
    heads = 2 * d * 16384 * (tokens + tokens - 1)         # main and MTP
    experts = 6 * 3 * 2 * 2 * 1408 * latent * 2688
    scan = 5 * sum(work_ssd.ssd_flops(tokens, 16, 64, 1, 128, 128))
    attention = 2 * 12 * 4 * 128 * work_moe.keys_seen(seq)
    flops = nemotron3_super.step_flops(cfg, 1)
    assert flops == 3 * tokens * per_token + 3 * heads + experts + scan \
        + attention
    if seq == 4096:
        # 14.54 TFLOP a step; the six LatentMoE layers (router, projections,
        # shared expert, held experts) 57 % of it, the held experts' own
        # products 1.9 %
        assert flops == pytest.approx(14.537e12, rel=1e-3)
        moe = 6 * (3 * tokens * (2 * d * 512 + 4 * d * latent + 4 * d * 5376)
                   + 3 * 4 * 1408 * latent * 2688)
        assert moe / flops == pytest.approx(0.572, abs=0.005)
        assert experts / flops == pytest.approx(0.019, abs=0.001)
