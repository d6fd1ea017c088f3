"""Device milliseconds a step on instructions made by the optimizer's ops
(op_role Optimize or LRSched: the update ops and what the epilogue adds):
the trace's ``XLA Ops`` events joined with the program's map from
instruction to phase (``_phases.py``), over the traced steps. It names the
work by the Fluid op that made it, not by the family XLA's fusion pass
prints, so it stays readable when the fusions are regrouped."""

from benchmarks.layer_metrics import _phases

DECLARATION = {
    "name": "optimizer_ms_per_step", "unit": "ms", "better": "lower",
    "source": "device_trace",
    "layer": "optimizer epilogue (optimizer.py, ops/optimizer_ops.py, the epilogue in engine/lowering.py)",
    "moves": "train_samples_per_s", "drivers": ["train"],
}


def compute(facts):
    return _phases.ms_per_step(facts, "optimizer")
