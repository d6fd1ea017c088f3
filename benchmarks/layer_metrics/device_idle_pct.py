"""Share of the traced slice in which no operation ran on the device:
1 - union of the device's operation intervals / the slice."""

DECLARATION = {
    "name": "device_idle_pct", "unit": "%", "better": "lower",
    "source": "device_trace", "layer": "device",
    "moves": "train_samples_per_s", "drivers": ["train"],
}


def compute(facts):
    trace = facts.get("trace")
    if not trace or not trace["window_s"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
