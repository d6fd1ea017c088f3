"""Core of the marginal-cost timing protocol (used by
tools/flash_block_sweep.py, which fills the block table ``pick_block``
reads).

A single dispatch carries a fixed host overhead next to ms-scale
kernels (its size on the sealed chip machine: not measured); the protocol
times a jitted ``lax.fori_loop`` of data-dependency-chained steps at two
loop counts and reports (T_hi - T_lo)/Δn, cancelling the fixed overhead.

Also a CLI: the metrics-OFF seam-overhead budget check. The telemetry
layer's whole contract is that a disabled seam costs one cached-bool
check (README quotes ~0.3 µs); ``--budget-ns`` turns that promise into
an asserting gate CI can run::

    python tools/marginal_timing.py --budget-ns 5000

measures the marginal per-call cost of the instrumented no-op seam
(``obs.inc`` + ``obs.span`` + ``obs.time_block`` with the gate down,
empty-loop baseline subtracted) and exits 1 if the best-of-rounds
exceeds the budget — a regression in the off path fails the build
instead of quietly taxing every engine step.
"""


def run_marginal_protocol(variants, args, reps, warmup_rounds=1):
    """The shared two-loop-count timing driver.

    ``variants``: {key: (fn_lo, n_lo, fn_hi, n_hi)} — jitted chained
    loops for the same computation at two loop counts. Every window is
    compiled+warmed once, then all windows are timed INTERLEAVED for
    ``reps`` rounds (so overhead drift hits every variant equally).
    ``warmup_rounds`` untimed interleaved rounds run before timing; one
    is usually enough, but a process whose allocator state is still
    settling after the first interleaved dispatch needs a second.

    Returns {key: (marginal_seconds, per_rep_marginals)} where the
    headline marginal is diff-of-medians — median wall per loop count,
    then difference, so one outlier window cannot skew it — and
    ``per_rep_marginals`` are the paired per-round differences for error
    bars. Callers must treat non-positive values as overhead noise, not
    kernel signal."""
    import time

    import jax
    import numpy as np

    # Each window is tagged with a host span (no-ops unless
    # PADDLE_TPU_METRICS / a profiler session is up), so a protocol run
    # dumps straight to chrome-trace: per-variant lo/hi windows as
    # labeled slices, outlier reps visible at a glance.
    from paddle_tpu import observability as obs

    wall = {}
    for key, (fn_lo, _, fn_hi, _) in variants.items():
        with obs.span("marginal:compile", variant=key):
            jax.device_get(fn_lo(*args))    # compile + warm
            jax.device_get(fn_hi(*args))
        wall[key] = ([], [])
    # Untimed interleaved rounds before timing starts: the first
    # *interleaved* dispatch after the compile loop still eats stragglers
    # (host-side caching, allocator growth), which otherwise lands in
    # rep 0 of whichever variant runs first — observed as a 65.5ms
    # flash_attn_bwd_ms spread against a 3.4ms median.
    for wr in range(warmup_rounds):
        for key, (fn_lo, _, fn_hi, _) in variants.items():
            with obs.span("marginal:warmup", variant=key, round=wr):
                jax.device_get(fn_lo(*args))
                jax.device_get(fn_hi(*args))
    for rep in range(reps):
        for key, (fn_lo, _, fn_hi, _) in variants.items():
            for which, fn in ((0, fn_lo), (1, fn_hi)):
                with obs.span("marginal:rep", variant=key, rep=rep,
                              window="hi" if which else "lo"):
                    t0 = time.perf_counter()
                    jax.device_get(fn(*args))
                    dt = time.perf_counter() - t0
                wall[key][which].append(dt)
    out = {}
    for key, (_, n_lo, _, n_hi) in variants.items():
        lo, hi = wall[key]
        dn = n_hi - n_lo
        headline = (float(np.median(hi)) - float(np.median(lo))) / dn
        per_rep = [(h - l) / dn for l, h in zip(lo, hi)]
        out[key] = (headline, per_rep)
    return out


def measure_seam_overhead_ns(iters=200000, rounds=5):
    """Marginal per-call nanoseconds of one metrics-OFF seam: the
    engine's per-step pattern (counter inc + span ctx + time_block ctx)
    with the gate down, minus an empty-loop baseline, per iteration.
    Returns (best_ns, per_round_ns) — best-of-rounds is the asserting
    number (scheduler noise only ever inflates a round)."""
    import time

    from paddle_tpu import observability as obs

    was = obs.enabled()
    obs.set_enabled(False)
    try:
        def seam_loop(n):
            inc, span, time_block = obs.inc, obs.span, obs.time_block
            t0 = time.perf_counter_ns()
            for _ in range(n):
                inc("seam.counter")
                with span("seam"):
                    pass
                with time_block("seam.ms"):
                    pass
            return time.perf_counter_ns() - t0

        def empty_loop(n):
            t0 = time.perf_counter_ns()
            for _ in range(n):
                pass
            return time.perf_counter_ns() - t0

        seam_loop(1000)  # warm the code paths
        empty_loop(1000)
        per_round = []
        for _ in range(rounds):
            dt = seam_loop(iters) - empty_loop(iters)
            per_round.append(max(0.0, dt / iters))
    finally:
        obs.set_enabled(True if was else None)
    return min(per_round), per_round


def main(argv=None):
    import argparse
    import json
    import os
    import sys

    sys.path.insert(0, os.path.abspath(
        os.path.join(os.path.dirname(__file__), os.pardir)))
    p = argparse.ArgumentParser(
        description="metrics-off telemetry seam overhead check")
    p.add_argument("--iters", type=int, default=200000,
                   help="seam calls per timing round (default 200000)")
    p.add_argument("--rounds", type=int, default=5,
                   help="timing rounds; best-of is the headline")
    p.add_argument("--budget-ns", type=float, default=None,
                   help="fail (exit 1) if the best-of-rounds marginal "
                   "seam cost exceeds this many nanoseconds per call")
    args = p.parse_args(argv)
    best, per_round = measure_seam_overhead_ns(args.iters, args.rounds)
    out = {
        "seam_overhead_ns": round(best, 1),
        "per_round_ns": [round(r, 1) for r in per_round],
        "iters": args.iters,
    }
    if args.budget_ns is not None:
        out["budget_ns"] = args.budget_ns
        out["within_budget"] = best <= args.budget_ns
    print(json.dumps(out))
    if args.budget_ns is not None and best > args.budget_ns:
        print("FAIL: metrics-off seam overhead %.1f ns/call exceeds "
              "budget %.1f ns" % (best, args.budget_ns), file=sys.stderr)
        return 1
    return 0


def chained_grad_loop(grad_fn, n):
    """One jitted call running ``n`` fwd+bwd steps of ``grad_fn(q, k, v,
    *rest) -> (dq, dk, dv)`` chained by a data dependency: the 1e-30*dq
    term makes step i+1 depend on step i's output so XLA cannot collapse
    the loop, while perturbing q by less than one bf16 ulp. ``rest`` is
    passed through unchanged (a backward alone takes its saved forward
    there)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    @jax.jit
    def run(q, k, v, *rest):
        def body(_, carry):
            dq, dk, dv = grad_fn(
                q + (1e-30 * carry[0]).astype(q.dtype), k, v, *rest)
            return dq, dk, dv
        init = (jnp.zeros_like(q), jnp.zeros_like(k), jnp.zeros_like(v))
        return lax.fori_loop(0, n, body, init)
    return run


if __name__ == "__main__":
    import sys

    sys.exit(main())
