"""The grouped matmuls' share of their roofline at d 2048, width 1024, 16
experts held of 128: the least time the chip could take for the sparse
layers' expert MLPs of the traced steps (``work_moe``: gate, up and down
forward and each one's two products backward, nine grouped products a
layer; the larger of operations over the bf16 peak and bytes over the
memory's) over the device time of the events that the ``moe_expert_mlp``
op and its gradient made. Dense layers hold no such op and are not
counted. The backward's recomputed products do not count as work; their
time does.

The work is counted over the pairs that fall on held experts IN
EXPECTATION (tokens x k x held / experts), as ``moe_gmm_roofline_pct``
counts it: where a run's routing puts more pairs on the held experts the
share reads low by that ratio, and high where it puts fewer. The balancing
bias pulls every router output's load to the mean, so the expectation is
what the traced steps hold, more nearly than under a softmax router."""

from benchmarks import work, work_moe
from benchmarks.layer_metrics import _op_types

DECLARATION = {
    "name": "moe_gmm_w1024_roofline_pct", "unit": "%", "better": "higher",
    "source": "device_trace", "layer": "expert layer (ops/moe_ops.py, kernels/grouped_matmul.py)",
    "moves": "train_samples_per_s",
    "workloads": ["trinity_mini.pretrain_b2"],
}


def compute(facts):
    trace = facts.get("trace")
    if not trace or not trace["steps"]:
        return None
    seconds = _op_types.seconds_of(facts, ("moe_expert_mlp",))
    if not seconds:
        return None
    m = facts["cfg"]["model"]
    kinds = m.get("mlp_layer_types") or ()
    sparse = m["num_hidden_layers"] - list(
        kinds[:m["num_hidden_layers"]]).count("dense")
    pairs = work_moe.pairs_held(
        facts["rows"] * m["seq_len"], m["num_experts_per_tok"],
        m["experts_held"], m["router_experts"])
    least = work_moe.grouped_matmul_least_seconds(
        pairs, m["hidden_size"], m["moe_intermediate_size"],
        m["experts_held"], work.peaks(facts["device_kind"]))
    return 100.0 * least * sparse * trace["steps"] / seconds
