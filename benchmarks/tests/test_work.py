"""The arithmetic that ``step_mfu_pct`` and ``flash_roofline_pct`` divide,
on the examples PERF.md quotes."""

import json
import os

import pytest

from benchmarks import work
from benchmarks.reference import bert, resnet

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _model(name):  # a configuration file, whole
    with open(os.path.join(HERE, "configs", name + ".json")) as f:
        return json.load(f)


def test_attention_of_the_2048_cell_is_3_7_tflop_a_step():
    fwd, bwd = work.attention_flops(8, 12, 2048, 64)
    assert fwd == 4 * 8 * 12 * 2048 * 2048 * 64
    assert bwd == 2 * fwd
    assert 12 * (fwd + bwd) == pytest.approx(3.71e12, rel=5e-3)


def test_attention_at_2048_is_bound_by_compute():
    peak = work.peaks("TPU v5 lite")
    least, bound = work.attention_least_seconds(8, 12, 2048, 64, peak)
    assert bound == "compute"
    assert least == pytest.approx(309.2e9 / 197e12, rel=1e-3)
    # 8 tensors of [8, 12, 2048, 64] bf16, once each
    assert work.attention_bytes(8, 12, 2048, 64) == 8 * 8 * 12 * 2048 * 64 * 2


def test_bert_base_sample_at_2048_is_1_8_tflop():
    cfg = _model("bert_base_s2048")
    per_sample = bert.step_flops(cfg, 1)
    # 6 x 85 M encoder parameters x 2048 tokens, 0.46 TFLOP of attention,
    # 0.29 TFLOP of the vocabulary projection, 7 GFLOP of the MLM dense
    encoder = 3 * 2048 * 12 * (8 * 768 * 768 + 4 * 768 * 3072)
    assert encoder == pytest.approx(6 * 84.9e6 * 2048, rel=1e-2)
    attention = 12 * 12 * 12 * 2048 * 2048 * 64
    vocab = 3 * 2048 * 2 * 768 * 30522
    dense = 3 * 2048 * 2 * 768 * 768
    assert per_sample == encoder + attention + vocab + dense
    assert per_sample == pytest.approx(1.80e12, rel=5e-3)
    assert bert.step_flops(cfg, 8) == 8 * per_sample


def test_128_positions_have_a_sixteenth_of_the_attention():
    long = _model("bert_base_s2048")
    short = dict(long, model=dict(long["model"], seq_len=128))
    # the same 16,384 tokens a step
    gap = bert.step_flops(long, 8) - bert.step_flops(short, 128)
    assert gap == pytest.approx(3.71e12 * (1 - 1 / 16), rel=5e-3)


def test_resnet50_is_4_1_gmac_forward_and_24_gflop_a_trained_image():
    cfg = _model("resnet50")
    per_image = resnet.step_flops(cfg, 1)
    # forward 4.1 G multiply-adds (He et al. quote 3.8 G for their variant
    # with the stride on the first 1x1); three products a convolution but
    # for the stem, which has no input gradient
    assert per_image / 6 == pytest.approx(4.1e9, rel=3e-2)
    assert per_image == pytest.approx(24.3e9, rel=1e-2)
    assert resnet.step_flops(cfg, 256) == 256 * per_image


def test_step_mfu_asks_the_cells_reference_for_its_count():
    from benchmarks.run import load_module

    reader = load_module("layer_metrics", "step_mfu_pct")
    cfg = _model("resnet50")
    facts = {"cfg": cfg, "rows": 256, "device_kind": "TPU v5 lite",
             "trace": {"steps": 20, "window_s": 2.0}}
    assert reader.compute(facts) == pytest.approx(
        100 * 20 * resnet.step_flops(cfg, 256) / (2.0 * 197e12))
    assert reader.compute(dict(facts, trace=None)) is None


def test_a_device_that_is_not_in_the_table_is_an_error():
    with pytest.raises(KeyError):
        work.peaks("TPU v9 imaginary")
