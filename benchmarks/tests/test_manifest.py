"""``BENCHMARK.json`` against the files it names: every cell and
configuration has its file, every per-layer metric its reader with the same
declaration, and every name keeps to the permitted characters."""

import json
import os
import re

import pytest

from benchmarks.run import load_json, load_module

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_every_cell_has_its_files_and_agrees_with_them():
    configs = {c["name"]: c for c in MANIFEST["configs"]}
    used = set()
    for cell in MANIFEST["workloads"]:
        assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
        assert cell["name"] == "%s.%s" % (cell["config"], cell["traffic"])
        workload = load_json("workloads", cell["name"] + ".json")
        assert workload["config"] == cell["config"]
        assert workload["chips"] == cell["chips"] in (1, 4)
        assert len(cell["why"]) <= 200 and "\n" not in cell["why"]
        assert all(v is None or v > 0 for v in workload["limits"].values())
        load_module("drivers", workload["driver"])
        used.add(cell["config"])
    assert used == set(configs)
    for config in configs.values():
        assert config["file"].startswith(MANIFEST["paths"][0] + "/")
        with open(os.path.join(ROOT, config["file"])) as f:
            held = json.load(f)
        assert held["name"] == config["name"]
        assert held["reduced"] == config["reduced"]
        for key in ("source", "assumed", "rehearsal", "model", "optimizer"):
            assert key in held, key


def test_every_per_layer_metric_has_a_reader_that_declares_the_same():
    ends = {m["name"] for m in MANIFEST["end_to_end"]}
    cells = {w["name"] for w in MANIFEST["workloads"]}
    assert "setup_s" in ends
    for metric in MANIFEST["per_layer"] + MANIFEST["end_to_end"]:
        assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
        assert metric["better"] in ("lower", "higher")
    for metric in MANIFEST["per_layer"]:
        decl = load_module("layer_metrics", metric["name"]).DECLARATION
        for key in ("name", "unit", "better", "source", "layer", "moves"):
            assert decl[key] == metric[key], (metric["name"], key)
        assert metric["moves"] in ends
        assert set(metric.get("workloads", ())) <= cells
        assert decl.get("drivers") or decl.get("workloads")


def test_the_run_fits_the_check():
    cells, seconds = 24, MANIFEST["run_seconds"]
    assert (2 + 14 * cells) * (seconds + 60) + cells * 2 * 90 + 1200 <= 43200


@pytest.mark.parametrize("metric", ["ttft", "bert", "resnet", "samples"])
def test_the_harness_names_no_model_cell_or_metric(metric):
    with open(os.path.join(ROOT, "benchmarks", "run.py")) as f:
        code = f.read().split('"""', 2)[2]
    assert metric not in code.lower()
