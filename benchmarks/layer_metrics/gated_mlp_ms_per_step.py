"""Device milliseconds a step of the dense gated MLPs: the events that the
``gated_mlp`` ops and their gradients made (a dense layer's MLP and every
sparse layer's shared experts), by op type (``_op_types.py``), over the
traced steps."""

from benchmarks.layer_metrics import _op_types

DECLARATION = {
    "name": "gated_mlp_ms_per_step", "unit": "ms", "better": "lower",
    "source": "device_trace", "layer": "forward lowerings (ops/, engine/lowering.py)",
    "moves": "train_samples_per_s",
    "workloads": ["trinity_mini.pretrain_b2"],
}


def compute(facts):
    trace = facts.get("trace")
    if not trace or not trace["steps"]:
        return None
    seconds = _op_types.seconds_of(facts, ("gated_mlp",))
    return 1000.0 * seconds / trace["steps"] if seconds else None
