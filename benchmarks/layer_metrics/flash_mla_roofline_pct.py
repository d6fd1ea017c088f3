"""The flash kernels' share of their roofline in latent attention: the
least time the chip could take for the traced steps' attention
(``work_mla.mla_attention_least_seconds``: causal, q.k over the
non-rotary and rotary parts of a head together, the values at their own
width, as many K/V heads as Q heads), one call for every decoder layer and
one for the multi-token-prediction module, over the device time of the
kernels' events, which the trace names by the framework op's scope
(``pt.fused_attention``). Where the program has no such events there is
nothing to read."""

from benchmarks import trace_reduce, work, work_mla

NEEDLES = ("pt.fused_attention",)

DECLARATION = {
    "name": "flash_mla_roofline_pct", "unit": "%", "better": "higher",
    "source": "device_trace", "layer": "kernels (kernels/flash_attention.py)",
    "moves": "train_samples_per_s",
    "workloads": ["joyai_llm_flash.pretrain_b1"],
}


def compute(facts):
    trace = facts.get("trace")
    if not trace or not trace["steps"]:
        return None
    seconds = trace_reduce.seconds_matching(trace["ops"], NEEDLES)
    if not seconds:
        return None
    m = facts["cfg"]["model"]
    a = m["mla"]
    calls = m["num_hidden_layers"] + (1 if m.get("mtp") else 0)
    least = calls * work_mla.mla_attention_least_seconds(
        facts["rows"], m["num_attention_heads"], m["seq_len"],
        a["qk_nope_head_dim"] + a["qk_rope_head_dim"], a["v_head_dim"],
        work.peaks(facts["device_kind"]))
    return 100.0 * least * trace["steps"] / seconds
