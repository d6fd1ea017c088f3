"""Host seconds of the first call of the system under test, drained: for a
training cell the first ``exe.run(main)`` (desc transforms, tracing,
lowering, XLA compile or cache read, one step)."""

DECLARATION = {
    "name": "first_step_s", "unit": "s", "better": "lower",
    "source": "host_clock",
    "layer": "transforms and lowering (analysis/transforms.py, engine/lowering.py, get_compiled)",
    "moves": "setup_s", "drivers": ["*"],
}


def compute(facts):
    return facts.get("first_step_s")
