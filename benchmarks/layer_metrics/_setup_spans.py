"""Set-up from the inside: what the program's tracer holds of the first
call of each executable and of the Program's construction, whatever is
switched on (PR 37).

- ``compile``, the first-call seam span of an executable (``_spans.py``),
  carries beside JAX's three durations what the compilation cache did
  meanwhile: ``cache_hits``, ``cache_misses`` (counts), ``cache_retrieval_s``
  and ``compile_saved_s``.
- Inside it lies one span ``op:<type>`` for every Fluid op whose lowering
  ran under JAX's trace, with ``idx`` (``<block>_<index>``) and ``role``
  (``forward``, ``backward``, ``optimizer``: the device join's rule). A grad
  op's replay of its forward is inside its own span. Only op spans inside a
  first call are read, by thread and interval as ``_spans.step_split``
  does, so a retrace after set-up cannot count twice.
- ``minimize`` and, nested in it, ``append_backward``: seam spans of the
  Program's construction with the ``ops`` they appended.

Shared by the nine set-up readers; the loader skips files that start with
an underscore. A program that records none of this (a parent commit) has
nothing to read, and the readers return None.
"""

from benchmarks.layer_metrics import _spans

FIRST_CALL = "compile"
JAX_SECONDS = ("jax_trace_s", "jax_lower_s", "backend_compile_s")
OP = "op:"

recorded = _spans.recorded


def first_calls(spans):
    """The first-call seam spans: one an executable."""
    return [s for s in spans or () if s.name == FIRST_CALL and s.dur_us > 0
            and s.args and "fun_name" in s.args]


def inside(spans, roots):
    """The spans that lie within one of ``roots``, on its thread."""
    return [s for s in spans if any(
        r is not s and r.tid == s.tid and r.ts_us <= s.ts_us
        and s.ts_us + s.dur_us <= r.ts_us + r.dur_us + 1e-3
        for r in roots)]


def op_spans(spans):
    """The ``op:<type>`` spans of set-up's first calls."""
    return [s for s in inside(spans or (), first_calls(spans))
            if s.name.startswith(OP)]


def self_seconds_by(ops, label):
    """{``label(span)``: self seconds} over ``ops``: the program's own
    ``self_time`` on copies renamed by ``label``, so that a control-flow
    op's time is less its sub-block's ops'."""
    from paddle_tpu.observability.tracing import SpanRecord, self_time

    renamed = [SpanRecord(label(s), s.ts_us, s.dur_us, s.tid, s.depth, None)
               for s in ops]
    return {k: us / 1e6 for k, us in self_time(renamed).items()}


def role_seconds(role, spans):
    """Self seconds of the op spans with ``role``; None where the program
    records no op span."""
    ops = op_spans(spans)
    if not ops:
        return None
    return self_seconds_by(ops, lambda s: s.args["role"]).get(role, 0.0)


def minimize_self_seconds(spans):
    """Self seconds of the ``minimize`` seam spans: the optimizer's own
    ops, ``append_backward`` taken out."""
    from paddle_tpu.observability.tracing import self_time

    roots = [s for s in spans or () if s.name == "minimize"]
    if not roots:
        return None
    return self_time(roots + inside(spans, roots))["minimize"] / 1e6


def rest_seconds(first):
    """A first call's duration less what JAX reports for tracing, lowering
    and backend-compiling its function: the first execution's dispatch,
    the arguments' transfer, whatever JAX does unnamed."""
    return first.dur_us / 1e6 - sum(
        first.args.get(k, 0.0) for k in JAX_SECONDS)


def first_call_rest_seconds(spans):
    rows = first_calls(spans)
    return sum(rest_seconds(s) for s in rows) if rows else None


def cache_hit_pct(spans):
    """Hits of the compilation cache ÷ (hits + misses) over the first
    calls, in percent; None where no first call carries either count (a
    parent commit, or no cache in use)."""
    rows = first_calls(spans)
    hits = sum(s.args.get("cache_hits", 0) for s in rows)
    misses = sum(s.args.get("cache_misses", 0) for s in rows)
    return 100.0 * hits / (hits + misses) if hits + misses else None


def executables(spans):
    return len(first_calls(spans)) or None
