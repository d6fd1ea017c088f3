"""Checked examples of ``work_moe.py``: the keys a query sees, the
attention's and the grouped matmuls' operations and bytes, and the new
configuration's count of a whole step."""

import json
import os

import pytest

from benchmarks import work, work_moe
from benchmarks.reference import mellum2

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def test_keys_seen_counts_by_hand():
    # 4 queries, no window: 1 + 2 + 3 + 4; window 2: 1 + 2 + 2 + 2
    assert work_moe.keys_seen(4) == 10
    assert work_moe.keys_seen(4, 2) == 7
    assert work_moe.keys_seen(4, 4) == work_moe.keys_seen(4, 9) == 10
    # the cell's window layers: 896 keys a query on average, its full
    # layer 2048.5
    assert work_moe.keys_seen(4096, 1024) == 896 * 4096 + 512
    assert work_moe.keys_seen(4096) == 4096 * 4097 // 2


def test_masked_attention_is_a_share_of_the_full_square():
    fwd, bwd = work_moe.masked_attention_flops(2, 32, 4096, 128, 1024)
    assert fwd == 4 * 2 * 32 * work_moe.keys_seen(4096, 1024) * 128
    assert bwd == 2 * fwd
    full, _ = work.attention_flops(2, 32, 4096, 128)
    assert fwd / full == pytest.approx(0.21877, rel=1e-3)
    assert work_moe.grouped_attention_bytes(2, 32, 4, 4096, 128) == \
        4 * 2 * 36 * 4096 * 128 * 2


def test_grouped_matmuls_of_the_cell():
    pairs = work_moe.pairs_held(8192, 8, 16, 64)
    assert pairs == 16384
    fwd, bwd = work_moe.grouped_matmul_flops(pairs, 2304, 896)
    assert fwd == 3 * 2 * 16384 * 2304 * 896 and bwd == 2 * fwd
    # per token and layer, forward: 2 held pairs x 3 matmuls x 2 d w
    assert fwd / 8192 == pytest.approx(24.77e6, rel=1e-3)
    assert work_moe.grouped_matmul_bytes(pairs, 2304, 896, 16) == \
        9 * (16384 * 3200 + 16 * 2304 * 896) * 2
    peak = work.peaks("TPU v5 lite")
    least = work_moe.grouped_matmul_least_seconds(pairs, 2304, 896, 16, peak)
    assert least == pytest.approx((fwd + bwd) / 197e12)   # compute binds


def test_a_step_of_the_new_cell_is_11_tflop():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "mellum2_12b.json")) as f:
        cfg = json.load(f)
    flops = mellum2.step_flops(cfg, 2)
    per_token_forward = flops / 3 / 8192
    # projections 170 + router 1 + experts 99 + head 113 + attention 78 M
    assert per_token_forward == pytest.approx(461e6, rel=0.01)
    assert flops == pytest.approx(11.3e12, rel=0.01)
