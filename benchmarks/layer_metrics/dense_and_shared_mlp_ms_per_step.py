"""Device milliseconds a step of the gated MLPs of the JoyAI-LLM-Flash
cell: the events that the ``gated_mlp`` ops and their gradients made, the
dense first layer's (7168 wide) and the five shared experts' (768 wide, one
an expert layer and one in the MTP module), by op type (``_op_types.py``),
over the traced steps."""

from benchmarks.layer_metrics import _op_types

DECLARATION = {
    "name": "dense_and_shared_mlp_ms_per_step", "unit": "ms", "better": "lower",
    "source": "device_trace", "layer": "forward lowerings (ops/, engine/lowering.py)",
    "moves": "train_samples_per_s",
    "workloads": ["joyai_llm_flash.pretrain_b1"],
}


def compute(facts):
    trace = facts.get("trace")
    if not trace or not trace["steps"]:
        return None
    seconds = _op_types.seconds_of(facts, ("gated_mlp",))
    return 1000.0 * seconds / trace["steps"] if seconds else None
