"""Device milliseconds a step of the expert layers of the JoyAI-LLM-Flash
cell (16 of 256 SwiGLU experts 768 wide at d 2048, 8 a token, a sigmoid
router with a balancing bias; four layers and the MTP module's):
``sigmoid_moe_ms_per_step``'s op types (the router, the bias update, the
dispatch, the expert MLP's grouped matmuls and the combine, forward and
backward), by op type (``_op_types.py``), over the traced steps. The
shared experts are ``gated_mlp`` ops and not in it
(``dense_and_shared_mlp_ms_per_step``)."""

from benchmarks.layer_metrics import _op_types
from benchmarks.run import load_module

DECLARATION = {
    "name": "moe_w768_ms_per_step", "unit": "ms", "better": "lower",
    "source": "device_trace", "layer": "expert layer (ops/moe_ops.py, kernels/grouped_matmul.py)",
    "moves": "train_samples_per_s",
    "workloads": ["joyai_llm_flash.pretrain_b1"],
}


def compute(facts):
    trace = facts.get("trace")
    if not trace or not trace["steps"]:
        return None
    seconds = _op_types.seconds_of(facts, load_module(
        "layer_metrics", "sigmoid_moe_ms_per_step").OP_TYPES)
    return 1000.0 * seconds / trace["steps"] if seconds else None
