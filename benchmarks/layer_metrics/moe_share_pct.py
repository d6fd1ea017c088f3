"""Share of the device's busy time spent in the expert layer: the events
of the router, the dispatch, the expert MLP's grouped matmuls and the
combine, forward and backward, by the type of the Fluid op that made them
(``_op_types.py``). Less the grouped matmuls' own time (``moe_expert_mlp``),
what is left is the routing's: top-k, sort, gathers."""

from benchmarks.layer_metrics import _op_types

OP_TYPES = ("moe_router", "moe_dispatch", "moe_expert_mlp", "moe_combine")

DECLARATION = {
    "name": "moe_share_pct", "unit": "%", "better": "lower",
    "source": "device_trace", "layer": "expert layer (ops/moe_ops.py, kernels/grouped_matmul.py)",
    "moves": "train_samples_per_s",
    "workloads": ["mellum2_12b.pretrain_s4096_b2"],
}


def compute(facts):
    trace = facts.get("trace")
    if not trace or not trace["busy_s"]:
        return None
    seconds = _op_types.seconds_of(facts, OP_TYPES)
    return 100.0 * seconds / trace["busy_s"] if seconds else None
