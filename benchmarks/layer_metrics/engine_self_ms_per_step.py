"""Host milliseconds a step in the program's own Python: the program's
``executor.run`` span less its jitted-call child (``run``), that is the self
time of ``executor.run``, ``step``, ``feed``, ``lookup``, ``gather``,
``writeback`` and ``fetch``, mean over the traced slice's steps
(``_spans.py``). With ``jit_call_ms_per_step`` it splits what
``dispatch_ms_per_step.train`` times from outside."""

from benchmarks.layer_metrics import _spans

DECLARATION = {
    "name": "engine_self_ms_per_step", "unit": "ms", "better": "lower",
    "source": "program_span",
    "layer": "entry points and engine (executor.py, engine/executor.py)",
    "moves": "train_samples_per_s", "drivers": ["train"],
}


def compute(facts):
    split = _spans.step_split_of_run()
    return split[0] if split else None
