"""The gated short convolution's share of its roofline: the least time the
chip could take for the op's own traffic in the traced steps
(``work_conv.short_conv_least_seconds``: forward reads the input [T, 3d]
and writes [T, d], backward reads the input and the cotangent and writes
[T, 3d], 11 T d elements a conv layer at the program's activation width,
the filter's bytes beside them, at the memory's peak) over the device time
of the events that the ``gated_short_conv`` ops and their gradients made
(``short_conv_ms_per_step``'s events). The count is of the op's arguments
and results, whatever implements it. Where XLA books a part of the op to a
neighbour (a fusion is booked whole to the op XLA names it after) the
share reads high by that part: PERF.md section 5 says what the op table
shows of it."""

from benchmarks import work, work_conv
from benchmarks.layer_metrics import _op_types

DECLARATION = {
    "name": "short_conv_roofline_pct", "unit": "%", "better": "higher",
    "source": "device_trace", "layer": "forward lowerings (ops/, engine/lowering.py)",
    "moves": "train_samples_per_s",
    "workloads": ["lfm2_24b_a2b.pretrain_b2"],
}


def compute(facts):
    trace = facts.get("trace")
    if not trace or not trace["steps"]:
        return None
    seconds = _op_types.seconds_of(facts, ("gated_short_conv",))
    if not seconds:
        return None
    m = facts["cfg"]["model"]
    layers = list(m["layer_types"][:m["num_hidden_layers"]]).count("conv")
    itemsize = 2 if facts["cfg"]["program"].get("amp") == "bf16" else 4
    least = work_conv.short_conv_least_seconds(
        facts["rows"] * m["seq_len"], m["hidden_size"],
        m.get("conv_L_cache", 3), work.peaks(facts["device_kind"]), itemsize)
    return 100.0 * least * layers * trace["steps"] / seconds
