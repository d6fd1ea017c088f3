"""Device milliseconds a step of the LatentMoE expert layers: the events of
the router, the bias update, the dispatch, the expert MLP's grouped
matmuls, the combine and the two projections into and out of the experts'
latent (``moe_latent``), forward and backward, by the type of the Fluid
op that made them (``_op_types.py``), over the traced steps; read in the
Nemotron-3-Super cell, whose five expert layers and the MTP module's are
all LatentMoE. The relu² shared expert is ``mul``, ``relu`` and ``square``
ops and is not in it."""

from benchmarks.layer_metrics import _op_types
from benchmarks.run import load_module

DECLARATION = {
    "name": "latent_moe_ms_per_step", "unit": "ms", "better": "lower",
    "source": "device_trace", "layer": "expert layer (ops/moe_ops.py, kernels/grouped_matmul.py)",
    "moves": "train_samples_per_s",
    "workloads": ["nemotron3_super_120b_a12b.pretrain_b1"],
}


def compute(facts):
    trace = facts.get("trace")
    if not trace or not trace["steps"]:
        return None
    seconds = _op_types.seconds_of(facts, load_module(
        "layer_metrics", "sigmoid_moe_ms_per_step").OP_TYPES
        + ("moe_latent",))
    return 1000.0 * seconds / trace["steps"] if seconds else None
