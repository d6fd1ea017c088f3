"""The attention kernels' share of their roofline in a decoder whose
queries see only some keys: the least time the chip could take for the
traced steps' attention (``work_moe.masked_attention_least_seconds``: the
keys a query really sees, causal and under each layer's window, 4 forward
and 8 backward per key and head dimension; Q, O, dO, dQ at the query heads
and K, V, dK, dV at the key/value heads moved once) over the device time
of the kernels' events, which the trace names by the framework op's scope
(``pt.fused_attention``), as ``flash_roofline_pct`` finds them."""

from benchmarks import trace_reduce, work, work_moe

NEEDLES = ("pt.fused_attention",)

DECLARATION = {
    "name": "flash_window_roofline_pct", "unit": "%", "better": "higher",
    "source": "device_trace", "layer": "kernels (kernels/flash_attention.py)",
    "moves": "train_samples_per_s",
    "workloads": ["mellum2_12b.pretrain_s4096_b2"],
}


def compute(facts):
    trace = facts.get("trace")
    if not trace or not trace["steps"]:
        return None
    seconds = trace_reduce.seconds_matching(trace["ops"], NEEDLES)
    if not seconds:
        return None
    m = facts["cfg"]["model"]
    peak = work.peaks(facts["device_kind"])
    least = sum(
        work_moe.masked_attention_least_seconds(
            facts["rows"], m["num_attention_heads"],
            m["num_key_value_heads"], m["seq_len"], m["head_dim"],
            m["sliding_window"] if kind == "sliding_attention" else None,
            peak)
        for kind in m["layer_types"][:m["num_hidden_layers"]])
    return 100.0 * least * trace["steps"] / seconds
