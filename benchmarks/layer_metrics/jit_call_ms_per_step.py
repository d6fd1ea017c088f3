"""Host milliseconds a step inside JAX's call of the jitted step (the
program's ``run`` span: argument handling, dispatch, and any wait JAX
imposes), mean over the traced slice's steps (``_spans.py``)."""

from benchmarks.layer_metrics import _spans

DECLARATION = {
    "name": "jit_call_ms_per_step", "unit": "ms", "better": "lower",
    "source": "program_span",
    "layer": "entry points and engine (executor.py, engine/executor.py)",
    "moves": "train_samples_per_s", "drivers": ["train"],
}


def compute(facts):
    split = _spans.step_split_of_run()
    return split[1] if split else None
