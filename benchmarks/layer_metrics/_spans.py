"""The program's own spans, as its in-memory tracer holds them after the
run (``paddle_tpu.observability``): recorded while the traced slice's
profiler session is on (``executor.run`` -> ``step`` -> ``feed``,
``lookup``, ``gather``, ``run``, ``writeback``, ``fetch``) and, whatever is
switched on, at the cache-miss seam (``trace`` and its children; an
executable's first call, ``compile``, with the seconds JAX reports for
tracing, lowering and backend-compiling that one function).

Shared by the span readers; the loader skips files that start with an
underscore. A program that records none of this (a parent commit before
PR 26) has nothing to read.
"""

ROOT = "executor.run"
JITTED_CALL = ("run", "compile")


def recorded():
    try:
        from paddle_tpu import observability as obs
        from paddle_tpu.observability.tracing import self_time  # noqa: F401
    except ImportError:
        return None
    return obs.spans()


def step_split(spans):
    """(engine self ms, jitted call ms), each a mean over the recorded
    ``executor.run`` calls: the self time of every span inside one but
    the jitted call's, and the jitted call's."""
    from paddle_tpu.observability.tracing import self_time

    roots = [s for s in spans if s.name == ROOT and s.dur_us > 0]
    if not roots:
        return None
    inside = [s for s in spans if any(
        r.tid == s.tid and r.ts_us <= s.ts_us
        and s.ts_us + s.dur_us <= r.ts_us + r.dur_us + 1e-3
        for r in roots)]
    own = self_time(inside)
    call = sum(own.get(n, 0.0) for n in JITTED_CALL)
    engine = sum(us for n, us in own.items() if n not in JITTED_CALL)
    return engine / len(roots) / 1e3, call / len(roots) / 1e3


def step_split_of_run():
    spans = recorded()
    return step_split(spans) if spans else None


def seam_seconds(name, keys=None):
    """Seconds of the seam spans called ``name``, summed over the
    executables of the run: their durations, or with ``keys`` the sum of
    those arguments. None where no such span (or argument) is recorded."""
    spans = recorded()
    rows = [s for s in spans or () if s.name == name]
    if keys is not None:
        rows = [s for s in rows if s.args and any(k in s.args for k in keys)]
        return (sum(s.args.get(k, 0.0) for s in rows for k in keys)
                if rows else None)
    return sum(s.dur_us for s in rows) / 1e6 if rows else None
