"""Seconds of set-up in ``append_backward`` while the Program is built: the
program's seam spans of that name (``_setup_spans.py``)."""

from benchmarks.layer_metrics import _spans

DECLARATION = {
    "name": "append_backward_s", "unit": "s", "better": "lower",
    "source": "program_span",
    "layer": "backward (backward.py, the *_grad lowerings)",
    "moves": "setup_s", "drivers": ["train"],
}


def compute(facts):
    return _spans.seam_seconds("append_backward")
