"""Seconds JAX spent in XLA backend compiles (or the persistent-cache reads
that replace them) during set-up: the sum of its
``/jax/core/compile/backend_compile_duration`` events before the window."""

DECLARATION = {
    "name": "backend_compile_s", "unit": "s", "better": "lower",
    "source": "program_counter",
    "layer": "transforms and lowering (analysis/transforms.py, engine/lowering.py, get_compiled)",
    "moves": "setup_s", "drivers": ["*"],
}


def compute(facts):
    return facts.get("setup_compile_s")
