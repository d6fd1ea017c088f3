"""Share of the device's busy time spent in the attention kernels of a
decoder with window and full grouped-query layers: the Pallas custom calls
of ``fused_attention`` and ``fused_attention_grad``, which the trace names
by the framework op's scope (``pt.fused_attention``), as ``flash_share_pct``
finds them in the cell it lists. The denominator of
``flash_window_roofline_pct`` over the busy time."""

from benchmarks import trace_reduce

NEEDLES = ("pt.fused_attention",)

DECLARATION = {
    "name": "flash_window_share_pct", "unit": "%", "better": "lower",
    "source": "device_trace", "layer": "kernels (kernels/flash_attention.py)",
    "moves": "train_samples_per_s",
    "workloads": ["mellum2_12b.pretrain_s4096_b2"],
}


def compute(facts):
    trace = facts.get("trace")
    if not trace or not trace["busy_s"]:
        return None
    seconds = trace_reduce.seconds_matching(trace["ops"], NEEDLES)
    return 100.0 * seconds / trace["busy_s"] if seconds else None
