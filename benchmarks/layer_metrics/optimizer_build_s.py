"""Seconds of set-up in ``Optimizer.minimize`` while the Program is built,
``append_backward`` taken out: the self time of the program's ``minimize``
seam spans (``_setup_spans.py``)."""

from benchmarks.layer_metrics import _setup_spans

DECLARATION = {
    "name": "optimizer_build_s", "unit": "s", "better": "lower",
    "source": "program_span",
    "layer": "optimizer epilogue (optimizer.py, ops/optimizer_ops.py, the epilogue in engine/lowering.py)",
    "moves": "setup_s", "drivers": ["train"],
}


def compute(facts):
    return _setup_spans.minimize_self_seconds(_setup_spans.recorded())
