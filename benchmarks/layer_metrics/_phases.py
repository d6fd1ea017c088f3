"""Device seconds of the traced slice by the phase of the Fluid op that
made each instruction: the join of the trace's ``XLA Ops`` events with the
program's own map from HLO instruction to (provenance tag, op type, phase),
``paddle_tpu.observability.opprof.instruction_phases()``. The program
leaves a note of the executable on its first traced step and makes the map
only here, after the window. A fusion is booked whole to its root's op.

Shared by the four device readers; the loader skips files that start with
an underscore. Where the program has no such map (a parent commit before
PR 26), or the run has no device plane, there is nothing to read.
"""

PHASES = ("forward", "backward", "optimizer")
UNATTRIBUTED = "unattributed"


def instruction_name(event_name):
    """The text between ``%`` and `` = `` of an event's name: the trace
    names an event by its HLO instruction's whole text."""
    return event_name.split(" = ", 1)[0].split(" ", 1)[0].lstrip("%")


def join(ops, phases):
    """``trace_reduce``'s ``ops`` ({plane: [(start_ns, end_ns, event
    name)]}) against ``phases`` ({instruction: (tag, op type, phase)}) ->
    {phase or ``unattributed``: device seconds, averaged over the device
    planes}; None where no event meets a phase (the map is of another
    executable, or empty)."""
    out = dict.fromkeys(PHASES + (UNATTRIBUTED,), 0.0)
    nothing = (None, None, None)
    for rows in ops.values():
        for start, end, name in rows:
            phase = phases.get(instruction_name(name), nothing)[2]
            out[phase or UNATTRIBUTED] += end - start
    if not any(out[p] for p in PHASES):
        return None
    planes = max(len(ops), 1)
    return {k: v / planes / 1e9 for k, v in out.items()}


def seconds_by_phase(facts):
    """``join`` of the run's trace with the program's map, made once a
    run and kept in ``facts``; None where either is missing."""
    trace = facts.get("trace")
    if not trace or not trace.get("ops"):
        return None
    if "seconds_by_phase" not in facts:
        try:
            from paddle_tpu.observability import opprof

            phases = opprof.instruction_phases()
        except (ImportError, AttributeError):
            phases = None
        facts["seconds_by_phase"] = (join(trace["ops"], phases)
                                     if phases else None)
    return facts["seconds_by_phase"]


def ms_per_step(facts, phase):
    seconds = seconds_by_phase(facts)
    steps = (facts.get("trace") or {}).get("steps")
    if seconds is None or not steps:
        return None
    return 1000.0 * seconds[phase] / steps
