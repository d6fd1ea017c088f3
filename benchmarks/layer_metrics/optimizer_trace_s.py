"""Seconds of set-up in the optimizer ops' lowerings while JAX traces the
executables' first calls: the self time of the program's ``op:<type>``
spans with ``role`` optimizer (``_setup_spans.py``)."""

from benchmarks.layer_metrics import _setup_spans

DECLARATION = {
    "name": "optimizer_trace_s", "unit": "s", "better": "lower",
    "source": "program_span",
    "layer": "optimizer epilogue (optimizer.py, ops/optimizer_ops.py, the epilogue in engine/lowering.py)",
    "moves": "setup_s", "drivers": ["train"],
}


def compute(facts):
    return _setup_spans.role_seconds(
        "optimizer", _setup_spans.recorded())
