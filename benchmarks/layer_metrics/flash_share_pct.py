"""Share of the device's busy time spent in the attention kernels: the
Pallas custom calls of the ``fused_attention`` and ``fused_attention_grad``
ops (forward, dQ, dK/dV), which the trace names by the framework op's scope
(``%pt.fused_attention...``). Where attention runs as the XLA composition
there is no such event and the reader finds nothing to read."""

from benchmarks import trace_reduce

NEEDLES = ("pt.fused_attention",)

DECLARATION = {
    "name": "flash_share_pct", "unit": "%", "better": "lower",
    "source": "device_trace", "layer": "kernels (kernels/flash_attention.py)",
    "moves": "train_samples_per_s",
    "workloads": ["bert_base_s2048.pretrain_b8"],
}


def compute(facts):
    trace = facts.get("trace")
    if not trace or not trace["busy_s"]:
        return None
    seconds = trace_reduce.seconds_matching(trace["ops"], NEEDLES)
    return 100.0 * seconds / trace["busy_s"] if seconds else None
