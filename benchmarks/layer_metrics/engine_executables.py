"""The executables the engine made in the run: its first-call seam spans
(``_setup_spans.py``), two a training cell (startup and the step). A third
is a recompile, and its span's ``step`` argument says at which run."""

from benchmarks.layer_metrics import _setup_spans

DECLARATION = {
    "name": "engine_executables", "unit": "count", "better": "lower",
    "source": "program_counter",
    "layer": "entry points and engine (executor.py, engine/executor.py)",
    "moves": "setup_s", "drivers": ["train"],
}


def compute(facts):
    return _setup_spans.executables(_setup_spans.recorded())
