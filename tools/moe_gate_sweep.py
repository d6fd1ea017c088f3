#!/usr/bin/env python
"""Time the gate of the expert MLP alone, on the chip: XLA's fusions over
all buffer rows (``silu_gate`` and ``jax.vjp`` of it, what the CPU and odd
sizes run) against the two Pallas kernels of
``kernels/grouped_matmul.py`` (``gated`` / ``gated_t``), whose grid is the
buffer's live prefix, at each share of live rows and each ``--tiles`` (rows
of a tile; the module's own, ``gate_tile_rows``, unless given). Runs no
benchmark cell and is no part of the benchmark; its table is in PERF.md,
Findings PR 36.

A form is timed as a chain of applications in one jitted call, each fed
by the one before it (forward: the result is the next ``gate``; backward:
``d_gate`` and ``d_up`` the next ``gate`` and ``up``) behind an
``optimization_barrier`` so that XLA fuses none with its neighbour; the
call is dispatched ``--iters`` times back to back and drained once, the
least of ``--reps`` such windows taken. A chain of ``--chain`` and one of
twice that are timed and their difference is the applications' own time:
the host's dispatch and the copies of the call's arguments (the transpose
writes ``d_gate`` and ``d_up`` in the places of ``gate`` and ``up``, which
a caller's arrays have to be copied for, once a call) fall out. Printed as
milliseconds an application and as GB/s of the live rows' bytes (three
arrays forward, five backward). The first application of every kernel form
is held against XLA's on the live rows.
"""

import argparse
import os
import sys

import numpy as np

sys.path.insert(0, os.path.abspath(
    os.path.join(os.path.dirname(__file__), os.pardir)))

# [tokens x k, width] of the three decoder cells' buffers
SHAPES = {"mellum2_12b": (65536, 896), "trinity_mini": (49152, 1024),
          "lfm2_24b_a2b": (65536, 1536)}


def sweep(names, shares, tiles, chain, iters, reps, seed, rehearse=False):
    import jax
    import jax.numpy as jnp

    from paddle_tpu.kernels import grouped_matmul as gm
    from tools.moe_permute_sweep import timed

    assert rehearse or jax.default_backend() == "tpu", (
        "the sweep needs the chip (--rehearse interprets the kernels on the "
        "CPU: its times mean nothing)")
    rng = np.random.RandomState(seed)

    def chained(once, transposed, length):
        def fn(gate, up, weight, d_hidden, sizes):
            for _ in range(length):
                if transposed:
                    gate, up, d_weight = once(gate, up, weight, d_hidden,
                                              sizes)
                    gate, up, d_weight = jax.lax.optimization_barrier(
                        (gate, up, d_weight))
                else:
                    gate = jax.lax.optimization_barrier(
                        once(gate, up, weight, sizes))
            return (gate, up, d_weight) if transposed else gate
        return jax.jit(fn)

    for name in names:
        rows, width = (256, 128) if rehearse else SHAPES[name]
        gate, up, d_hidden = (jnp.asarray(rng.randn(rows, width),
                                          jnp.bfloat16) for _ in range(3))
        weight = jnp.asarray(rng.rand(rows), jnp.float32)
        forms = {"xla": (
            lambda g, u, w, s: gm.silu_gate(g, u, w),
            lambda g, u, w, d, s: jax.vjp(gm.silu_gate, g, u, w)[1](d))}
        for tm in tiles or [gm.gate_tile_rows(rows, width)]:
            forms["kernel, tiles of %d rows" % tm] = (
                lambda g, u, w, s, tm=tm: gm._gate(g, u, w, None, s, tm,
                                                   rehearse),
                lambda g, u, w, d, s, tm=tm: gm._gate(g, u, w, d, s, tm,
                                                      rehearse))
        # the live rows are an argument: one executable a form serves
        # every share
        forms = {form: (jax.jit(fwd), jax.jit(bwd),
                        [chained(fwd, False, n) for n in (chain, 2 * chain)],
                        [chained(bwd, True, n) for n in (chain, 2 * chain)])
                 for form, (fwd, bwd) in forms.items()}
        for share in shares:
            live = int(rows * share)
            sizes = jnp.asarray([live - live // 2, live // 2], jnp.int32)
            print("%s: [%d, %d], %d rows live" % (name, rows, width, live),
                  flush=True)
            args = (gate, up, weight, d_hidden, sizes)
            wanted = None
            for form, (fwd, bwd, fwd_chains, bwd_chains) in forms.items():
                got = [np.asarray(a, np.float32)[:live] for a in (
                    fwd(gate, up, weight, sizes), *bwd(*args))]
                cells = []
                if wanted is None:
                    wanted = got
                else:
                    for what, a, b in zip(("hidden", "d_gate", "d_up",
                                           "d_weight"), got, wanted):
                        if live and not np.abs(a - b).max() <= 1e-2 * max(
                                np.abs(b).max(), 1e-6):
                            cells.append("%s DIFFERS by %g" % (
                                what, np.abs(a - b).max()))
                for what, (short, long), blocks in (
                        ("forward", fwd_chains, 3),
                        ("backward", bwd_chains, 5)):
                    s = (timed(long, args, iters, reps)
                         - timed(short, args, iters, reps)) / chain
                    cells.append("%s %.4f ms %.0f GB/s" % (
                        what, s * 1e3, blocks * live * width * 2 / s / 1e9))
                print("  %-28s %s" % (form, " | ".join(cells)), flush=True)


if __name__ == "__main__":
    ap = argparse.ArgumentParser("moe_gate_sweep")
    ap.add_argument("--shapes", nargs="+", default=list(SHAPES),
                    choices=list(SHAPES))
    ap.add_argument("--shares", type=float, nargs="+",
                    default=[0.125, 0.25, 0.5, 1.0])
    ap.add_argument("--tiles", type=int, nargs="+", default=[])
    ap.add_argument("--chain", type=int, default=8)
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="off the chip, the kernels interpreted at a tiny "
                         "size: finds wrong arguments, measures nothing")
    a = ap.parse_args()
    sweep(a.shapes, a.shares, a.tiles, a.chain, a.iters, a.reps, a.seed,
          a.rehearse)
