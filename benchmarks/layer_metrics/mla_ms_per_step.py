"""Device milliseconds a step of latent attention's own ops: the events
that the ``latent_qkv`` op (the up-projections, the rotations and the
shared key's broadcast) and the ``fused_attention`` op and their gradients
made, by op type (``_op_types.py``), over the traced steps. The
down-projections, the two norms inside the mixer and the output projection
are ``mul`` and ``rms_norm`` ops and not in it. Where the program has no
``latent_qkv`` op there is nothing to read."""

from benchmarks.layer_metrics import _op_types

DECLARATION = {
    "name": "mla_ms_per_step", "unit": "ms", "better": "lower",
    "source": "device_trace", "layer": "forward lowerings (ops/, engine/lowering.py)",
    "moves": "train_samples_per_s",
    "workloads": ["joyai_llm_flash.pretrain_b1"],
}

OP_TYPES = ("latent_qkv", "fused_attention")


def compute(facts):
    trace = facts.get("trace")
    if not trace or not trace["steps"]:
        return None
    if not _op_types.seconds_of(facts, ("latent_qkv",)):
        return None
    seconds = _op_types.seconds_of(facts, OP_TYPES)
    return 1000.0 * seconds / trace["steps"] if seconds else None
