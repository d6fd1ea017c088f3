"""Seconds of the executables' first calls that JAX names nowhere: the
first-call spans' durations less their tracing, lowering and backend
seconds (``_setup_spans.py``): the arguments' transfer, the first
execution's dispatch, whatever else."""

from benchmarks.layer_metrics import _setup_spans

DECLARATION = {
    "name": "first_call_rest_s", "unit": "s", "better": "lower",
    "source": "program_span",
    "layer": "transforms and lowering (analysis/transforms.py, engine/lowering.py, get_compiled)",
    "moves": "setup_s", "drivers": ["train"],
}


def compute(facts):
    return _setup_spans.first_call_rest_seconds(_setup_spans.recorded())
