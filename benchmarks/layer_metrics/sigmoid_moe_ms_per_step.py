"""Device milliseconds a step of a sigmoid-routed expert layer with a
balancing bias: the events of the router, the bias update, the dispatch,
the expert MLP's grouped matmuls and the combine, forward and backward, by
the type of the Fluid op that made them (``_op_types.py``), over the traced
steps. The shared experts are not in it: they are ``gated_mlp`` ops
(``gated_mlp_ms_per_step``)."""

from benchmarks.layer_metrics import _op_types

OP_TYPES = ("moe_router", "moe_bias_update", "moe_dispatch",
            "moe_expert_mlp", "moe_combine")

DECLARATION = {
    "name": "sigmoid_moe_ms_per_step", "unit": "ms", "better": "lower",
    "source": "device_trace", "layer": "expert layer (ops/moe_ops.py, kernels/grouped_matmul.py)",
    "moves": "train_samples_per_s",
    "workloads": ["trinity_mini.pretrain_b2"],
}


def compute(facts):
    trace = facts.get("trace")
    if not trace or not trace["steps"]:
        return None
    seconds = _op_types.seconds_of(facts, OP_TYPES)
    return 1000.0 * seconds / trace["steps"] if seconds else None
