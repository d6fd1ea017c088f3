"""The flash kernels' share of their roofline under a window of 2048 on
four layers and none on the fifth, 32 query heads on 4 key/value heads of
128: the least time the chip could take for the traced steps' attention
(``work_moe.masked_attention_least_seconds`` over each layer's keys seen)
over the device time of the kernels' events (``pt.fused_attention``). The
arithmetic is ``flash_window_roofline_pct``'s, which reads every size from
the configuration; this is its reading at this cell's shape, under a name
of its own because that one lists its cells."""

from benchmarks.run import load_module

DECLARATION = {
    "name": "flash_w2048_roofline_pct", "unit": "%", "better": "higher",
    "source": "device_trace", "layer": "kernels (kernels/flash_attention.py)",
    "moves": "train_samples_per_s",
    "workloads": ["trinity_mini.pretrain_b2"],
}


def compute(facts):
    return load_module("layer_metrics",
                       "flash_window_roofline_pct").compute(facts)
