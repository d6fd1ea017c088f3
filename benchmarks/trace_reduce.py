"""From a profiler trace to numbers: the one reduction every PR shares.

``jax.profiler`` writes ``<dir>/plugins/profile/<time>/*.xplane.pb``;
``jax.profiler.ProfileData`` reads it with nothing but JAX. What the trace
of a v5e holds, as looked at by hand (PERF.md section 3):

- a plane ``/device:TPU:<n>`` for each chip; its line ``XLA Ops`` holds one
  event for each HLO operation that ran, named by the whole text of its HLO
  instruction, with start and duration in nanoseconds and no scope
  statistic; its lines ``XLA Modules`` and ``Steps`` hold one event for
  each run of an executable. A Pallas kernel is a ``custom-call`` whose
  instruction name carries the ``pt.<op type>.<block>_<index>`` scope of
  the framework op that made it (``%pt.fused_attention_grad.0_476.3``);
  fusions carry no scope (``%fusion.1206``, ``%divide_subtract_fusion``);
- a plane ``/host:CPU`` whose lines are host threads; the driver's
  ``StepTraceAnnotation`` and ``TraceAnnotation`` spans are events there,
  on the same clock.

The reduction takes the ``XLA Ops`` events of every device plane:

    busy_s      union of the intervals in which an operation runs, averaged
                over the device planes
    window_s    first driver span's start to the last device event's end
    by_family_s device seconds by family of the name the trace prints
    ops         the events themselves, for a per-layer reader to group by
                name (``seconds_matching``)
    idle_gaps   the longest gaps between device events, each with the host
                span of the driver that covers its middle
"""

import glob
import gzip
import os
import re

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
DRIVER_SPANS = ("bench_step", "bench_host_read")
TOP = 10


def find_xplane(trace_dir):
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError("no .xplane.pb under %s" % trace_dir)
    return found[-1]


def load(path):
    from jax.profiler import ProfileData

    if path.endswith(".gz"):  # the recorded trace under testdata/
        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(path)


def _events(line):
    for ev in line.events:
        yield ev.start_ns, ev.start_ns + ev.duration_ns, ev


def device_ops(profile):
    """{plane name: [(start_ns, end_ns, name)]}, time-ordered."""
    out = {}
    for plane in profile.planes:
        if not plane.name.startswith(DEVICE_PLANE):
            continue
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            out[plane.name] = sorted(
                (start, end, ev.name) for start, end, ev in _events(line))
    return out


def driver_spans(profile):
    """[(start_ns, end_ns, name)] of the spans the driver wrote."""
    rows = []
    for plane in profile.planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            for start, end, ev in _events(line):
                if ev.name.startswith(DRIVER_SPANS):
                    rows.append((start, end, ev.name))
    return sorted(rows)


def union_ns(intervals):
    """Total length of the union of (start, end) intervals."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps_ns(intervals, lo, hi):
    """[(start, end)] of the stretches of [lo, hi] no interval covers."""
    out, at = [], lo
    for s, e in sorted(intervals):
        if s > at:
            out.append((at, min(s, hi)))
        at = max(at, e)
        if at >= hi:
            break
    if at < hi:
        out.append((at, hi))
    return [(s, e) for s, e in out if e > s]


def _covering(spans, at):
    for s, e, name in spans:
        if s <= at <= e:
            return name
    return "between_driver_spans"


def family(name):
    """The short name a breakdown prints for an event. The trace names an
    event by its whole HLO instruction (``%fusion.12 = f32[...] fusion(...``);
    the family is the instruction's name without its counters, so that the
    twelve layers' copies of one fusion add up: ``%fusion.12`` -> ``fusion``,
    ``%pt.fused_attention_grad.0_476.3`` -> ``pt.fused_attention_grad``."""
    head = name.split(" = ", 1)[0].split(" ", 1)[0].lstrip("%")
    return re.sub(r"[._-]\d+", "", head) or head


def seconds_matching(ops, needles):
    """Device seconds, averaged over the planes, of the events whose name
    holds one of ``needles``."""
    total = 0
    for rows in ops.values():
        for s, e, name in rows:
            if any(n in name for n in needles):
                total += e - s
    return total / max(len(ops), 1) / 1e9


def reduce(profile):
    ops = device_ops(profile)
    spans = driver_spans(profile)
    if not ops or not any(ops.values()):
        return None  # no device plane (a CPU rehearsal): nothing to read
    first = min(rows[0][0] for rows in ops.values() if rows)
    last = max(max(e for _, e, _ in rows) for rows in ops.values() if rows)
    lo = min([first] + [s for s, _, _ in spans[:1]])
    busy, by_name, gaps = [], {}, []
    for rows in ops.values():
        busy.append(union_ns([(s, e) for s, e, _ in rows]))
        for s, e, name in rows:
            by_name[name] = by_name.get(name, 0) + (e - s)
        gaps += gaps_ns([(s, e) for s, e, _ in rows], lo, last)
    n = len(busy)
    gap_by = {}
    for s, e in gaps:
        what = _covering(spans, (s + e) // 2)
        gap_by[what] = gap_by.get(what, 0) + (e - s)
    by_family = {}
    for name, ns in by_name.items():
        by_family[family(name)] = by_family.get(family(name), 0) + ns
    top = sorted(by_family.items(), key=lambda kv: -kv[1])[:TOP]
    return {
        "devices": n,
        "busy_s": sum(busy) / n / 1e9,
        "window_s": (last - lo) / 1e9,
        "ops": ops,
        "by_family_s": {k: v / n / 1e9 for k, v in by_family.items()},
        "top_ops": [[k, v / n / 1e9] for k, v in top],
        "idle_gaps": [[k, v / n / 1e9] for k, v in sorted(
            gap_by.items(), key=lambda kv: -kv[1])[:TOP]],
        "steps": sum(1 for _, _, name in spans
                     if name.startswith(DRIVER_SPANS[0])),
    }


def reduce_dir(trace_dir):
    return reduce(load(find_xplane(trace_dir)))
