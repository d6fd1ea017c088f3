"""The control of every cell, at a size a test run can hold: the plain
reference put in the program's place and computed in the nearest precision
below the configuration's (operands, results and returning gradients
rounded to fp8 e4m3 for a bf16 configuration) has to come out as not
correct, on three seeds; the same reference in the configuration's own
precision (bf16) has to pass. The limits are those of the workload's
``rehearsal`` block (set by the cell's rule from readings at this size).
The readings at the cells' own sizes, on the chip, are in PERF.md: there
``tools/prove.py`` puts the control and the half batch through the same
``compare.judge`` under the cells' own limits.

At 8 images of 96 x 96 the ResNet's matmuls are short and its batch
statistics few, and operand precision shows in the worst leaf's first
gradient alone (``grad_gap``: the control reads twice the program's
largest, on 15 seeds each)."""

import importlib
import json
import os

import pytest

from benchmarks import compare
from benchmarks.drivers import train
from benchmarks.reference import common
from benchmarks.run import load_json

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    CELLS = [w["name"] for w in json.load(f)["workloads"]]
SEEDS = (5, 6, 3000000007)


def _sized(cell):
    workload = load_json("workloads", cell + ".json")
    config = load_json("configs", workload["config"] + ".json")
    cfg, rows = train.sized(config, workload, rehearse=True)
    model = importlib.import_module(
        "benchmarks.reference." + cfg["reference"])
    return workload, cfg, rows, model


@pytest.mark.parametrize("cell", CELLS)
def test_the_control_comes_out_not_correct(cell):
    workload, cfg, rows, model = _sized(cell)
    for seed in SEEDS:
        reference = common.follow(model, cfg, rows, seed)
        control = common.follow(model, cfg, rows, seed, precision="fp8")
        correct, compared = compare.judge(
            control, reference, train.limits(workload, rehearse=True))
        assert not correct, (seed, compared)


@pytest.mark.parametrize("cell", CELLS)
def test_the_configurations_own_precision_passes(cell):
    workload, cfg, rows, model = _sized(cell)
    reference = common.follow(model, cfg, rows, SEEDS[0])
    witness = common.follow(model, cfg, rows, SEEDS[0], precision="bf16")
    correct, compared = compare.judge(
        witness, reference, train.limits(workload, rehearse=True))
    assert correct, compared
