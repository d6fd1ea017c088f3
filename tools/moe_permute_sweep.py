#!/usr/bin/env python
"""Time the expert layer's four row movements alone, on the chip: XLA's
gathers (``ops/moe_ops.py`` ``_rows_of_tokens_xla`` / ``_sums_of_rows_xla``,
what the CPU and float32 programs run) against the Pallas kernel of
``kernels/row_permute.py`` at each (buffer tile, token chunk), the building
of its visit list included. Runs no benchmark cell and is no part of the
benchmark; the module's ``_TILE_ROWS`` / ``_CHUNK_ROWS`` are read off its
table (PERF.md, Findings PR 30).

The four movements of one layer, by the op that runs each:

    dispatch       x [N, d] bf16        -> rows [N k, d] bf16    (expand)
    combine        rows [N k, d] bf16   -> out [N, d] float32    (reduce)
    combine_grad   g [N, d] float32     -> rows [N k, d] bf16    (expand)
    dispatch_grad  rows [N k, d] bf16   -> dx [N, d] bf16        (reduce)

Routings are drawn so that about ``--live`` of the N k pairs fall on the
held experts (the held experts' odds are raised until they do). A movement
is one jitted call, dispatched ``--iters`` times back to back and drained
once; the least of ``--reps`` such windows is printed, in milliseconds and
as GB/s of the bytes the movement has to move (the live rows and the
tokens, once each). A call shorter than the host takes to dispatch it
(about 0.45 ms on the chip's machine) reads as that: what a movement costs
inside a step is the op table's to say (``benchmarks/tools/op_table.py``).
Every kernel result is held against XLA's on the rows in use.
"""

import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.abspath(
    os.path.join(os.path.dirname(__file__), os.pardir)))


def routing(rng, tokens, k, experts, held, live):
    """TopkIds [tokens, k] with about ``live`` pairs on experts < held."""
    gumbel = rng.gumbel(size=(tokens, experts))

    def ids_at(bias):
        score = gumbel + np.where(np.arange(experts) < held, bias, 0.0)
        return np.argsort(-score, axis=1)[:, :k]

    lo, hi = -8.0, 8.0
    for _ in range(30):
        mid = (lo + hi) / 2
        if (ids_at(mid) < held).sum() < live:
            lo = mid
        else:
            hi = mid
    return ids_at(hi).astype(np.int32)


def permutation(ids, held):
    """What ``moe_dispatch`` makes of TopkIds: (PairOfRow, Counts,
    RowOfPair, pair is held)."""
    tokens, k = ids.shape
    is_held = ids < held
    key = np.where(is_held, ids, held).reshape(-1)
    order = np.argsort(key, kind="stable").astype(np.int32)
    row_of_pair = np.argsort(order).astype(np.int32).reshape(tokens, k)
    counts = np.bincount(key, minlength=held + 1)[:held].astype(np.int32)
    return order, counts, row_of_pair, is_held


def timed(fn, args, iters, reps):
    import jax

    fn = jax.jit(fn)
    jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        out = None
        for _ in range(iters):
            out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / iters)
    return best


def sweep(tokens, k, d, experts, held, lives, tiles, chunks, iters, reps,
          seed, rehearse=False):
    import jax
    import jax.numpy as jnp

    from paddle_tpu.kernels import row_permute as rp
    from paddle_tpu.ops import moe_ops

    assert rehearse or jax.default_backend() == "tpu", (
        "the sweep needs the chip (--rehearse interprets the kernel on the "
        "CPU: its times mean nothing)")
    rng = np.random.RandomState(seed)
    rows = tokens * k
    x = jnp.asarray(rng.randn(tokens, d), jnp.bfloat16)
    g = jnp.asarray(rng.randn(tokens, d), jnp.float32)
    filled = jnp.asarray(rng.randn(rows, d), jnp.bfloat16)
    for live in lives:
        order, counts, row_of_pair, is_held = (
            jnp.asarray(a) for a in permutation(
                routing(rng, tokens, k, experts, held, live), held))
        n_live = int(counts.sum())
        # unused rows of a grouped matmul's result may hold anything
        buf = jnp.where(jnp.arange(rows)[:, None] < n_live, filled, jnp.nan)
        # what a movement has to move: the live rows and the tokens, once
        moved = {"dispatch": n_live * d * 2 + tokens * d * 2,
                 "combine": n_live * d * 2 + tokens * d * 4,
                 "combine_grad": n_live * d * 2 + tokens * d * 4,
                 "dispatch_grad": n_live * d * 2 + tokens * d * 2}
        xla = {
            "dispatch": (lambda x_, o, c: moe_ops._rows_of_tokens_xla(
                x_, o, c, k), (x, order, counts)),
            "combine": (lambda b, r, h: moe_ops._sums_of_rows_xla(
                b, r, h, jnp.float32), (buf, row_of_pair, is_held)),
            "combine_grad": (lambda g_, o, c: moe_ops._rows_of_tokens_xla(
                g_, o, c, k).astype(jnp.bfloat16), (g, order, counts)),
            "dispatch_grad": (lambda b, r, h: moe_ops._sums_of_rows_xla(
                b, r, h, jnp.bfloat16), (buf, row_of_pair, is_held))}

        def kernel(tile, chunk):
            at = dict(tile=tile, chunk=chunk, interpret=rehearse)
            return {
                "dispatch": (lambda x_, o, c: rp.expand(x_, o, c, k, **at),
                             (x, order, counts)),
                "combine": (lambda b, o, c: rp.reduce(
                    b, o, c, k, jnp.float32, **at), (buf, order, counts)),
                "combine_grad": (lambda g_, o, c: rp.expand(
                    g_.astype(jnp.bfloat16), o, c, k, **at),
                    (g, order, counts)),
                "dispatch_grad": (lambda b, o, c: rp.reduce(
                    b, o, c, k, jnp.bfloat16, **at), (buf, order, counts))}

        print("live %d of %d rows, %d tokens x %d, d %d, %d of %d experts "
              "held" % (n_live, rows, tokens, k, d, held, experts),
              flush=True)
        wanted = {}
        for tile, chunk in [(None, None)] + [(t, c) for t in tiles
                                             for c in chunks]:
            name = "xla" if tile is None else "kernel %d x %d" % (tile,
                                                                  chunk)
            cells, total = [], 0.0
            for what, (fn, args) in (xla if tile is None
                                     else kernel(tile, chunk)).items():
                try:
                    s = timed(fn, args, iters, reps)
                except Exception as e:  # noqa: BLE001 - a tiling may not fit
                    cells.append("%s failed: %s" % (what, str(e)[:120]))
                    continue
                # the result on the rows in use: XLA's kept, the kernel's
                # held against it
                got = np.asarray(jax.jit(fn)(*args).astype(jnp.float32))
                got = got[:n_live] if got.shape[0] == rows else got
                if tile is None:
                    wanted[what] = got
                else:
                    worst = float(np.abs(got - wanted[what]).max())
                    if not worst <= 1e-2 * float(np.abs(wanted[what]).max()):
                        cells.append("%s DIFFERS by %g" % (what, worst))
                if what in moved:
                    total += s
                    cells.append("%s %.3f ms %.0f GB/s" % (
                        what, s * 1e3, moved[what] / s / 1e9))
                else:
                    cells.append("%s %.3f ms" % (what, s * 1e3))
            if tile is not None:
                used = [int(rp.visits(order, counts, k, tokens, by, tile,
                                      chunk)[0][0]) for by in (False, True)]
                cells.append("visits %d / %d of %d" % (
                    used[0], used[1], rp.visit_bound(
                        rows // tile, tokens // chunk, held)))
            print("  %-18s four %.3f ms | %s" % (name, total * 1e3,
                                                 " | ".join(cells)),
                  flush=True)


if __name__ == "__main__":
    ap = argparse.ArgumentParser("moe_permute_sweep")
    ap.add_argument("--tokens", type=int, default=8192)
    ap.add_argument("--k", type=int, default=8)
    ap.add_argument("--d", type=int, default=2304)
    ap.add_argument("--experts", type=int, default=64)
    ap.add_argument("--held", type=int, default=16)
    ap.add_argument("--live", type=int, nargs="+", default=[16384, 32768])
    ap.add_argument("--tiles", type=int, nargs="+", default=[128, 256])
    ap.add_argument("--chunks", type=int, nargs="+", default=[128, 256, 512])
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="off the chip, the kernel interpreted: finds wrong "
                         "arguments, measures nothing")
    a = ap.parse_args()
    sweep(a.tokens, a.k, a.d, a.experts, a.held, a.live, a.tiles, a.chunks,
          a.iters, a.reps, a.seed, a.rehearse)
