"""Seconds of set-up in the grad ops' lowerings while JAX traces the
executables' first calls, each with its ``jax.vjp`` replay of the forward
lowering and its backward's tracing: the self time of the program's
``op:<type>`` spans with ``role`` backward (``_setup_spans.py``)."""

from benchmarks.layer_metrics import _setup_spans

DECLARATION = {
    "name": "backward_trace_s", "unit": "s", "better": "lower",
    "source": "program_span",
    "layer": "backward (backward.py, the *_grad lowerings)",
    "moves": "setup_s", "drivers": ["train"],
}


def compute(facts):
    return _setup_spans.role_seconds("backward", _setup_spans.recorded())
