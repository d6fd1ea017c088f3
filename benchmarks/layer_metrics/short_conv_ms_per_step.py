"""Device milliseconds a step of the gated short convolutions: the events
that the ``gated_short_conv`` ops and their gradients made (every conv
layer's token mixer between its two projections, which are ``mul`` ops and
not in it), by op type (``_op_types.py``), over the traced steps."""

from benchmarks.layer_metrics import _op_types

DECLARATION = {
    "name": "short_conv_ms_per_step", "unit": "ms", "better": "lower",
    "source": "device_trace", "layer": "forward lowerings (ops/, engine/lowering.py)",
    "moves": "train_samples_per_s",
    "workloads": ["lfm2_24b_a2b.pretrain_b2"],
}


def compute(facts):
    trace = facts.get("trace")
    if not trace or not trace["steps"]:
        return None
    seconds = _op_types.seconds_of(facts, ("gated_short_conv",))
    return 1000.0 * seconds / trace["steps"] if seconds else None
