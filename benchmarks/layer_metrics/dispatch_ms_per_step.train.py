"""Host milliseconds in one ``exe.run(main)`` call: the driver's own span
around each call of the traced slice, mean over its steps. It is what the
entry points and the engine cost the host a step (feed coercion, cache
look-up, state gather, the jitted call's dispatch, write-back)."""

DECLARATION = {
    "name": "dispatch_ms_per_step.train", "unit": "ms", "better": "lower",
    "source": "program_span",
    "layer": "entry points and engine (executor.py, engine/executor.py)",
    "moves": "train_samples_per_s", "drivers": ["train"],
}


def compute(facts):
    spans = facts.get("dispatch_spans_s")
    if not spans:
        return None
    return 1000.0 * sum(spans) / len(spans)
