"""Device milliseconds a step on instructions made by the backward ops
(op_role Backward: the ``*_grad`` lowerings, the flash backward kernels
among them), by the join of ``_phases.py``."""

from benchmarks.layer_metrics import _phases

DECLARATION = {
    "name": "backward_ms_per_step", "unit": "ms", "better": "lower",
    "source": "device_trace",
    "layer": "backward (backward.py, the *_grad lowerings)",
    "moves": "train_samples_per_s", "drivers": ["train"],
}


def compute(facts):
    return _phases.ms_per_step(facts, "backward")
