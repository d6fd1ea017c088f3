"""The attention kernels' share of their roofline: the least time the chip
could take for the attention mathematics of the traced steps
(``work.attention_least_seconds``: 4*B*H*T^2*d forward, 8*B*H*T^2*d
backward, Q K V O dO dQ dK dV moved once; the recomputed scores do not
count) over the device time of the kernels' events. It groups by the
framework op's scope, not by a kernel's name, so it reads the same work
whatever implements it. At 2048 positions and head size 64 the compute
bound binds (``work.attention_least_seconds`` says which)."""

from benchmarks import trace_reduce, work

NEEDLES = ("pt.fused_attention",)

DECLARATION = {
    "name": "flash_roofline_pct", "unit": "%", "better": "higher",
    "source": "device_trace", "layer": "kernels (kernels/flash_attention.py)",
    "moves": "train_samples_per_s",
    "workloads": ["bert_base_s2048.pretrain_b8"],
}


def compute(facts):
    trace = facts.get("trace")
    if not trace or not trace["steps"]:
        return None
    seconds = trace_reduce.seconds_matching(trace["ops"], NEEDLES)
    if not seconds:
        return None
    model = facts["cfg"]["model"]
    least, _ = work.attention_least_seconds(
        facts["rows"], model["n_heads"], model["seq_len"],
        model["d_model"] // model["n_heads"],
        work.peaks(facts["device_kind"]))
    return 100.0 * least * model["n_layers"] * trace["steps"] / seconds
