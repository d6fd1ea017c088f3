"""The grouped matmuls' share of their roofline at d 2048, width 768, 16
experts held of 256, 8 a token: the least time the chip could take for the
expert layers' MLPs of the traced steps (``work_moe``: gate, up and down
forward and each one's two products backward, nine grouped products a
layer; the larger of operations over the bf16 peak and bytes over the
memory's), four expert layers and the MTP module's, over the device time
of the events that the ``moe_expert_mlp`` op and its gradient made. The
backward's recomputed products do not count as work; their time does.

The work is counted over the pairs on held experts in expectation, no
more than the held experts' capacity allows (the reference's
``held_pairs``: 2,048 a layer at 1 x 4096 positions, 128 a held expert,
which is both). A step holds no more than that; where the routing leaves
a held expert under its capacity, the share reads high by that ratio."""

from benchmarks import work, work_moe
from benchmarks.layer_metrics import _op_types
from benchmarks.reference import joyai_llm_flash

DECLARATION = {
    "name": "moe_gmm_w768_roofline_pct", "unit": "%", "better": "higher",
    "source": "device_trace", "layer": "expert layer (ops/moe_ops.py, kernels/grouped_matmul.py)",
    "moves": "train_samples_per_s",
    "workloads": ["joyai_llm_flash.pretrain_b1"],
}


def compute(facts):
    trace = facts.get("trace")
    if not trace or not trace["steps"]:
        return None
    seconds = _op_types.seconds_of(facts, ("moe_expert_mlp",))
    if not seconds:
        return None
    m = facts["cfg"]["model"]
    kinds = list((m.get("mlp_layer_types") or ())[:m["num_hidden_layers"]])
    layers = m["num_hidden_layers"] - kinds.count("dense") + (
        1 if m.get("mtp") else 0)
    pairs = joyai_llm_flash.held_pairs(m, facts["rows"] * m["seq_len"])
    least = work_moe.grouped_matmul_least_seconds(
        pairs, m["hidden_size"], m["moe_intermediate_size"],
        m["experts_held"], work.peaks(facts["device_kind"]))
    return 100.0 * least * layers * trace["steps"] / seconds
