"""Seconds of set-up in the Python of the forward ops' lowerings while JAX
traces the executables' first calls: the self time of the program's
``op:<type>`` spans with ``role`` forward (``_setup_spans.py``), the ops
``forward_ms_per_step`` books on the device."""

from benchmarks.layer_metrics import _setup_spans

DECLARATION = {
    "name": "forward_trace_s", "unit": "s", "better": "lower",
    "source": "program_span",
    "layer": "forward lowerings (ops/, engine/lowering.py)",
    "moves": "setup_s", "drivers": ["train"],
}


def compute(facts):
    return _setup_spans.role_seconds("forward", _setup_spans.recorded())
