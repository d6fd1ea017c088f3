"""The grouped matmuls' share of their roofline at d 2048, width 1536, 8
experts held of 64, 4 a token: ``moe_gmm_w1024_roofline_pct``'s arithmetic
(the least time of the sparse layers' nine grouped products a layer over
the device time of the ``moe_expert_mlp`` op's and its gradient's events),
which reads every size from the configuration; this is its reading at this
cell's shape, under a name of its own because that one lists its cells.

The work is counted over the pairs that fall on held experts IN
EXPECTATION (tokens x 4 x 8 / 64 a sparse layer, 8,192 at 2 x 8192
positions): where a run's routing puts more pairs on the held experts the
share reads low by that ratio, and high where it puts fewer."""

from benchmarks.run import load_module

DECLARATION = {
    "name": "moe_gmm_w1536_roofline_pct", "unit": "%", "better": "higher",
    "source": "device_trace", "layer": "expert layer (ops/moe_ops.py, kernels/grouped_matmul.py)",
    "moves": "train_samples_per_s",
    "workloads": ["lfm2_24b_a2b.pretrain_b2"],
}


def compute(facts):
    return load_module("layer_metrics",
                       "moe_gmm_w1024_roofline_pct").compute(facts)
