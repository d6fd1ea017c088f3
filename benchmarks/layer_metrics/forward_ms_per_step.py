"""Device milliseconds a step on instructions made by the forward ops
(op_role Forward, the loss included), by the join of ``_phases.py``."""

from benchmarks.layer_metrics import _phases

DECLARATION = {
    "name": "forward_ms_per_step", "unit": "ms", "better": "lower",
    "source": "device_trace",
    "layer": "forward lowerings (ops/, engine/lowering.py)",
    "moves": "train_samples_per_s", "drivers": ["train"],
}


def compute(facts):
    return _phases.ms_per_step(facts, "forward")
