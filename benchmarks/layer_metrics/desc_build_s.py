"""Seconds of set-up in the program's cache-miss build of its executables,
before JAX sees anything: the ``trace`` seam span (desc transforms,
verify, ``lower_block``, the plans), summed over the executables set-up
makes (startup and the step). Always recorded (``_spans.py``)."""

from benchmarks.layer_metrics import _spans

DECLARATION = {
    "name": "desc_build_s", "unit": "s", "better": "lower",
    "source": "program_span",
    "layer": "transforms and lowering (analysis/transforms.py, engine/lowering.py, get_compiled)",
    "moves": "setup_s", "drivers": ["train"],
}


def compute(facts):
    return _spans.seam_seconds("trace")
