"""Everything of a run but the look for a chip, with the timed path broken
underneath: ``correct`` has to come out false, once for each fault a
one-chip training cell can have (a step that returns its state unchanged;
half of the batch left out, the mean taken over the rest), and true with
no fault planted. Each run is a process of its own (``faulty_run.py``),
since a process builds a Program's parameter names once."""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    CELLS = [w["name"] for w in json.load(f)["workloads"]]


def _run(fault, cell):
    done = subprocess.run(
        [sys.executable, os.path.join(HERE, "faulty_run.py"), fault,
         "--workload", cell, "--seed", "5", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, cwd=ROOT, timeout=900)
    assert done.returncode == 0, done.stderr[-2000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", ["unchanged", "half"])
def test_a_planted_fault_comes_out_not_correct(fault, cell):
    result = _run(fault, cell)
    assert result["device"]["platform"] == "cpu"
    assert result["correct"] is False, result["compared"]
    failed = [n for n, c in result["compared"].items()
              if c["limit"] is not None and not c["value"] <= c["limit"]]
    assert failed, result["compared"]


@pytest.mark.parametrize("cell", CELLS)
def test_no_fault_comes_out_correct(cell):
    result = _run("none", cell)
    assert result["correct"] is True, result["compared"]
