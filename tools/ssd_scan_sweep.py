#!/usr/bin/env python
"""Time the Mamba-2 chunked scan alone, on the chip: the plain form
(``ops/ssm_ops.py`` ``_ssd_scan``, what the CPU, float32 programs and odd
sizes run) against the two Pallas kernels of ``kernels/ssd_scan.py``, at
the ``nemotron_twotower_30b_a3b`` cell's shape (one layer: 2 rows of 4096
positions, 64 heads of 64 in 8 groups, states of 128, chunks of 128).
Runs no benchmark cell and is no part of the benchmark; its readings are
in PERF.md, Findings PR 41.

Each form is timed forward alone and forward with its backward (``jax.vjp``
and the pull-back of a fixed cotangent in one jitted call), dispatched
``--iters`` times back to back and drained once, the least of ``--reps``
such windows taken. The kernels' y and six gradients are held against the
plain form's in the same dtype and against the plain form's in float32:
the largest difference of each as a share of the reference's largest
magnitude. Prints one JSON line.
"""

import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.abspath(
    os.path.join(os.path.dirname(__file__), os.pardir)))

SHAPE = dict(rows=2, seq=4096, heads=64, head_dim=64, groups=8, state=128,
             chunk=128)
TINY = dict(rows=1, seq=300, heads=4, head_dim=64, groups=2, state=128,
            chunk=128)
NAMES = ("y", "x", "dt", "A_log", "B", "C", "D")


def inputs(rng, rows, seq, heads, head_dim, groups, state, dtype):
    """x, Δ, A_log, B, C, D and y's cotangent at the published
    initialisation's scales: Δ = softplus of a normal draw around
    dt_bias's -4.6 (``models/decoder_lm.py``), A_log = log of 1..16."""
    import jax.numpy as jnp

    def normal(*shape, scale=1.0):
        return jnp.asarray(scale * rng.randn(*shape), dtype)

    dt = np.log1p(np.exp(rng.randn(rows, seq, heads) - 4.6))
    return (normal(rows, seq, heads, head_dim),
            jnp.asarray(dt, jnp.float32),
            jnp.asarray(np.log(rng.uniform(1, 16, heads)), jnp.float32),
            normal(rows, seq, groups, state, scale=0.5),
            normal(rows, seq, groups, state, scale=0.5),
            jnp.asarray(rng.randn(heads), jnp.float32),
            normal(rows, seq, heads, head_dim))


def sweep(iters, reps, seed, rehearse=False):
    import jax
    import jax.numpy as jnp

    from paddle_tpu.kernels import ssd_scan as kernel
    from paddle_tpu.ops import ssm_ops
    from tools.moe_permute_sweep import timed

    assert rehearse or jax.default_backend() == "tpu", (
        "the sweep needs the chip (--rehearse interprets the kernels on the "
        "CPU: its times mean nothing)")
    shape = TINY if rehearse else SHAPE
    chunk = shape["chunk"]
    *args, gy = inputs(np.random.RandomState(seed), *(
        shape[k] for k in ("rows", "seq", "heads", "head_dim", "groups",
                           "state")), jnp.bfloat16)
    forms = {"plain": lambda *a: ssm_ops._ssd_scan(*a, chunk),
             "kernel": lambda *a: kernel._scan(*a, chunk, rehearse)}
    result, outs = dict(shape=shape), {}
    for name, fn in forms.items():
        def both(*a, fn=fn):
            y, back = jax.vjp(fn, *a[:-1])
            return y, back(a[-1])

        outs[name] = jax.tree_util.tree_leaves(jax.jit(both)(*args, gy))
        result[name + "_forward_ms"] = 1e3 * timed(fn, args, iters, reps)
        result[name + "_forward_backward_ms"] = 1e3 * timed(
            both, args + [gy], iters, reps)
    wide = [a.astype(jnp.float32) if a.dtype == jnp.bfloat16 else a
            for a in args + [gy]]
    with jax.default_matmul_precision("highest"):
        y, back = jax.vjp(forms["plain"], *wide[:-1])
        outs["float32"] = jax.tree_util.tree_leaves((y, back(wide[-1])))

    def gaps(got, want):
        return {name: float(np.abs(np.asarray(a, np.float32)
                                   - np.asarray(b, np.float32)).max()
                            / max(np.abs(np.asarray(b, np.float32)).max(),
                                  1e-30))
                for name, a, b in zip(NAMES, got, want)}

    result["kernel_vs_plain"] = gaps(outs["kernel"], outs["plain"])
    result["kernel_vs_float32"] = gaps(outs["kernel"], outs["float32"])
    result["plain_vs_float32"] = gaps(outs["plain"], outs["float32"])
    result["device"] = jax.devices()[0].device_kind
    print(json.dumps(result), flush=True)
    return result


if __name__ == "__main__":
    ap = argparse.ArgumentParser("ssd_scan_sweep")
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="off the chip, the kernels interpreted at a tiny "
                         "size: finds wrong arguments, measures nothing")
    a = ap.parse_args()
    sweep(a.iters, a.reps, a.seed, a.rehearse)
