"""Device seconds of the traced slice by the type of the Fluid op that made
each instruction: the same join as ``_phases.py`` (the trace's ``XLA Ops``
events against ``opprof.instruction_phases()``), read by op type instead of
by phase. An op's ``_grad`` counts with it. Where the program has no such
map, no op of the types asked for, or the run has no device plane, there is
nothing to read."""

from benchmarks.layer_metrics import _phases


def _map(facts):
    """``{instruction: (tag, op type, phase)}``, made once a run."""
    if "instruction_phases" not in facts:
        try:
            from paddle_tpu.observability import opprof

            facts["instruction_phases"] = opprof.instruction_phases()
        except (ImportError, AttributeError):
            facts["instruction_phases"] = None
    return facts["instruction_phases"]


def seconds_of(facts, op_types, phases=None):
    """Device seconds, averaged over the device planes, of the events whose
    instruction a Fluid op of ``op_types`` (or its ``_grad``) made; None
    where there is none. ``phases`` stands in for the program's map."""
    trace = facts.get("trace")
    if not trace or not trace.get("ops"):
        return None
    phases = phases if phases is not None else _map(facts)
    if not phases:
        return None
    total = 0
    for rows in trace["ops"].values():
        for start, end, name in rows:
            op_type = phases.get(_phases.instruction_name(name),
                                 (None, None, None))[1]
            if op_type and (op_type in op_types
                            or op_type[:-len("_grad")] in op_types):
                total += end - start
    return total / max(len(trace["ops"]), 1) / 1e9 or None
