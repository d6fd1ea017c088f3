"""The grouped matmuls' share of their roofline: the least time the chip
could take for the expert MLPs of the traced steps (``work_moe``: gate, up
and down forward and each one's two products backward; the larger of
operations over the bf16 peak and bytes over the memory's) over the device
time of the events that the ``moe_expert_mlp`` op and its gradient made.
The backward's recomputed gate and up products do not count as work; their
time does.

The work is counted over the pairs that fall on held experts IN
EXPECTATION (tokens x k x held / experts), as ISSUE 29 defines it: where
the router has moved towards the held experts the traced steps hold more
pairs and the share reads low by that ratio (PERF.md section 5 gives the
pairs read on the chip). The count of a run is the dispatch op's ``Counts``
output; the ``train`` driver fetches the loss alone and hands no reader the
scope, so taking it from the run needs an edit there (PERF.md, Open
questions)."""

from benchmarks import work, work_moe
from benchmarks.layer_metrics import _op_types

DECLARATION = {
    "name": "moe_gmm_roofline_pct", "unit": "%", "better": "higher",
    "source": "device_trace", "layer": "expert layer (ops/moe_ops.py, kernels/grouped_matmul.py)",
    "moves": "train_samples_per_s",
    "workloads": ["mellum2_12b.pretrain_s4096_b2"],
}


def compute(facts):
    trace = facts.get("trace")
    if not trace or not trace["steps"]:
        return None
    seconds = _op_types.seconds_of(facts, ("moe_expert_mlp",))
    if not seconds:
        return None
    m = facts["cfg"]["model"]
    pairs = work_moe.pairs_held(
        facts["rows"] * m["seq_len"], m["num_experts_per_tok"],
        m["experts_held"], m["router_experts"])
    least = work_moe.grouped_matmul_least_seconds(
        pairs, m["hidden_size"], m["moe_intermediate_size"],
        m["experts_held"], work.peaks(facts["device_kind"]))
    return (100.0 * least * m["num_hidden_layers"] * trace["steps"]
            / seconds)
