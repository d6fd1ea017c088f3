"""Seconds JAX spent lowering the engine's traced functions to MLIR modules
on their first calls in set-up (the Pallas call sites are lowered for
Mosaic here): the ``jax_lower_s`` argument of the first-call spans alone,
which ``jax_trace_s`` adds to the jaxpr tracing."""

from benchmarks.layer_metrics import _spans

DECLARATION = {
    "name": "mlir_lower_s", "unit": "s", "better": "lower",
    "source": "program_counter",
    "layer": "transforms and lowering (analysis/transforms.py, engine/lowering.py, get_compiled)",
    "moves": "setup_s", "drivers": ["train"],
}


def compute(facts):
    return _spans.seam_seconds("compile", ("jax_lower_s",))
