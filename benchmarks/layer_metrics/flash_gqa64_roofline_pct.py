"""The flash kernels' share of their roofline at heads of 64, 32 query
heads on 8 key/value heads, causal, no window, at the cell's positions:
the least time the chip could take for the traced steps' attention
(``work_moe.masked_attention_least_seconds`` over the keys a causal query
sees, one call for every layer that has attention: a ``conv`` layer has
none) over the device time of the kernels' events, which the trace names
by the framework op's scope (``pt.fused_attention``)."""

from benchmarks.run import load_module

DECLARATION = {
    "name": "flash_gqa64_roofline_pct", "unit": "%", "better": "higher",
    "source": "device_trace", "layer": "kernels (kernels/flash_attention.py)",
    "moves": "train_samples_per_s",
    "workloads": ["lfm2_24b_a2b.pretrain_b2"],
}


def compute(facts):
    """``flash_window_roofline_pct``'s arithmetic, which reads every size
    from the configuration and takes every layer kind for an attention:
    so it is shown the layers that have one."""
    m = facts["cfg"]["model"]
    kinds = [kind for kind in m["layer_types"][:m["num_hidden_layers"]]
             if kind != "conv"]
    cfg = dict(facts["cfg"], model=dict(m, layer_types=kinds,
                                        num_hidden_layers=len(kinds)))
    return load_module("layer_metrics", "flash_window_roofline_pct").compute(
        dict(facts, cfg=cfg))
