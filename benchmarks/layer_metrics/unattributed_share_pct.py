"""Share of the device's busy time on instructions that carry no
provenance tag: what the compiler adds with no metadata of a Fluid op
(layout copies, async ``copy-done`` / ``slice-done``, parameter plumbing),
or what a transform or a lowering emitted outside an op's scope. The phase
split cannot see this time; ``forward`` + ``backward`` + ``optimizer`` +
this is all of the device's operation time."""

from benchmarks.layer_metrics import _phases

DECLARATION = {
    "name": "unattributed_share_pct", "unit": "%", "better": "lower",
    "source": "device_trace",
    "layer": "transforms and lowering (analysis/transforms.py, engine/lowering.py, get_compiled)",
    "moves": "train_samples_per_s", "drivers": ["train"],
}


def compute(facts):
    seconds = _phases.seconds_by_phase(facts)
    if seconds is None or not facts["trace"]["busy_s"]:
        return None
    return 100.0 * seconds[_phases.UNATTRIBUTED] / facts["trace"]["busy_s"]
