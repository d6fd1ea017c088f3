"""The grouped matmuls' share of their roofline in a LatentMoE layer of
non-gated relu² experts, at the latent's width d 1024, width 2688, 8
experts held of 512, 22 a token: the least time the chip could take for
the expert MLPs of the traced steps (up and down forward and each one's
two products backward, six grouped products a layer, two thirds of
``work_moe.grouped_matmul_least_seconds`` in operations and in bytes
alike, as ``moe_gmm_relu2_roofline_pct`` reckons them), over the pattern's
expert layers and the MTP module's, divided by the device time of the
events that the ``moe_expert_mlp`` op and its gradient made. The latent
projections and the shared expert are other ops and are not in it; the
``w · relu(·)²`` between the products is XLA's and counts as time, not
work.

The work is counted over the pairs on held experts in expectation, no
more than the held experts' capacity allows (the reference's
``held_pairs``: 1,408 a layer at 1 x 4096 positions, 176 a held expert,
which is both). A step holds no more than that; where the routing leaves
a held expert under its capacity, the share reads high by that ratio."""

from benchmarks import work, work_moe
from benchmarks.layer_metrics import _op_types
from benchmarks.reference import nemotron3_super

DECLARATION = {
    "name": "moe_gmm_latent_roofline_pct", "unit": "%", "better": "higher",
    "source": "device_trace", "layer": "expert layer (ops/moe_ops.py, kernels/grouped_matmul.py)",
    "moves": "train_samples_per_s",
    "workloads": ["nemotron3_super_120b_a12b.pretrain_b1"],
}


def compute(facts):
    trace = facts.get("trace")
    if not trace or not trace["steps"]:
        return None
    seconds = _op_types.seconds_of(facts, ("moe_expert_mlp",))
    if not seconds:
        return None
    m = facts["cfg"]["model"]
    layers = (m["hybrid_override_pattern"][:m["num_hidden_layers"]]
              + (m.get("mtp_hybrid_override_pattern") or "")).count("E")
    pairs = nemotron3_super.held_pairs(m, facts["rows"] * m["seq_len"])
    least = 2.0 / 3.0 * work_moe.grouped_matmul_least_seconds(
        pairs, m["moe_latent_size"], m["moe_intermediate_size"],
        m["experts_held"], work.peaks(facts["device_kind"]))
    return 100.0 * least * layers * trace["steps"] / seconds
