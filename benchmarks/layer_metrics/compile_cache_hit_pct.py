"""The share of set-up's executables that JAX's persistent compilation cache
served: ``cache_hits`` ÷ (``cache_hits`` + ``cache_misses``) over the
first-call spans (``_setup_spans.py``). A cold set-up reads 0, a warm one
100: it says which of the two a ``setup_s`` was."""

from benchmarks.layer_metrics import _setup_spans

DECLARATION = {
    "name": "compile_cache_hit_pct", "unit": "%", "better": "higher",
    "source": "program_counter",
    "layer": "transforms and lowering (analysis/transforms.py, engine/lowering.py, get_compiled)",
    "moves": "setup_s", "drivers": ["train"],
}


def compute(facts):
    return _setup_spans.cache_hit_pct(_setup_spans.recorded())
