"""Seconds JAX spent tracing the engine's jitted functions to a jaxpr and
lowering them to an MLIR module, on their first calls in set-up: JAX's
``jaxpr_trace_duration`` and ``jaxpr_to_mlir_module_duration`` events for
those functions alone, which the program charges to its first-call spans
(``_spans.py``). The benchmark's own jits are not in it, which
``backend_compile_s`` cannot tell apart."""

from benchmarks.layer_metrics import _spans

DECLARATION = {
    "name": "jax_trace_s", "unit": "s", "better": "lower",
    "source": "program_counter",
    "layer": "transforms and lowering (analysis/transforms.py, engine/lowering.py, get_compiled)",
    "moves": "setup_s", "drivers": ["train"],
}


def compute(facts):
    return _spans.seam_seconds("compile", ("jax_trace_s", "jax_lower_s"))
