"""The whole step's share of the chip's bf16 peak: model operations of the
traced steps (``step_flops(cfg, rows)`` of the configuration's plain
reference: matmuls and convolutions of forward and backward, nothing
recomputed) over the traced slice's span times the peak."""

import importlib

from benchmarks import work

DECLARATION = {
    "name": "step_mfu_pct", "unit": "%", "better": "higher",
    "source": "device_trace", "layer": "the whole step on the device",
    "moves": "train_samples_per_s", "drivers": ["train"],
}


def compute(facts):
    trace = facts.get("trace")
    if not trace or not trace["steps"] or not trace["window_s"]:
        return None
    peak = work.peaks(facts["device_kind"])["bf16_flops_per_s"]
    model = importlib.import_module(
        "benchmarks.reference." + facts["cfg"]["reference"])
    flops = model.step_flops(facts["cfg"], facts["rows"]) * trace["steps"]
    return 100.0 * flops / (trace["window_s"] * peak)
