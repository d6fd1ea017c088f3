#!/usr/bin/env python
"""Sweep flash-attention block sizes on the real chip and emit the
committed autotune table consumed by ``pick_block`` (VERDICT r3 Next #9:
replace the one-off hand tune with a table from a reproducible sweep;
the discipline of the reference's jit kernel benchmarks,
benchmark/paddle/fluid/operators/jit/README.en.md).

Protocol: the MARGINAL-cost measurement of ``tools/marginal_timing.py``
— a single drained window carries a fixed dispatch/readback
overhead next to the ms-scale kernels (its size on the sealed chip
machine: not measured), so each (dtype, seq, block) config runs as one jitted
``lax.fori_loop`` of chained fwd+bwd steps at TWO loop counts; per-step
device time = (T_hi - T_lo)/Δn (overhead subtracts out), diff-of-medians
over ``reps`` interleaved rounds. Δn is sized from a FLOP model so every
config's signal is ~3s. Configs that fail to compile (VMEM OOM at wide
blocks x long f32 seqs) are skipped; the table is dumped incrementally
after every (dtype, seq) row so a late failure cannot lose the sweep.

``--bwd`` sweeps the backward apart from the forward, for the rows whose
sequence takes the fused backward kernel (dQ, dK and dV in one pass; see
``_flash_backward``): every (block_q, block_k) of the candidates that
tiles and fits, timed on the backward alone from a saved (out, lse), the
two-kernel form at the row's forward block timed beside them for the
record. The forward, and the two-kernel backward of longer sequences,
keep the row's one block.

Writes paddle_tpu/kernels/flash_block_table.json:
    {"bfloat16": {"256": best_block,
                  "2048": {"fwd": best_block, "bwd": [block_q, block_k]},
                  ...}, "float32": {...}}
"""

import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.abspath(
    os.path.join(os.path.dirname(__file__), os.pardir)))

OUT = os.path.abspath(os.path.join(
    os.path.dirname(__file__), os.pardir, "paddle_tpu", "kernels",
    "flash_block_table.json"))


from tools.marginal_timing import (chained_grad_loop,  # noqa: E402
                                   run_marginal_protocol)


def _dump(table):
    with open(OUT, "w") as f:
        json.dump(table, f, indent=1, sort_keys=True)


DEFAULT_BLOCKS = (128, 256, 512, 1024)
# every row the committed table carries (tests/test_tpu_compile.py compiles
# the kernels at the largest)
DEFAULT_SEQS = (256, 512, 1024, 2048, 4096, 8192, 16384)


def sweep(seqs=DEFAULT_SEQS, blocks=DEFAULT_BLOCKS,
          dtypes=("bfloat16", "float32"), batch=4, heads=16, dim=64,
          reps=3, target_signal_s=3.0, fresh=False, kv_heads=None,
          window=None, write=True):
    """``kv_heads`` (grouped-query attention) and ``window`` sweep another
    attention than the table's rows were made on; with ``write`` off the
    readings are printed and the table is left as it is."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.kernels.flash_attention import flash_attention

    assert jax.default_backend() != "cpu", "sweep needs the TPU backend"
    # merge into the existing table so a partial re-sweep (one row, more
    # reps) refines rather than clobbers the committed winners;
    # fresh=True regenerates from scratch
    table = {}
    if not fresh:
        try:
            with open(OUT) as f:
                table = json.load(f)
        except (OSError, ValueError):
            pass
    for dtype in dtypes:
        table.setdefault(dtype, {})
        for seq in seqs:
            rng = np.random.RandomState(0)
            # long f32 runs blow HBM sooner; shrink batch at 4096
            b = batch if seq < 4096 else max(1, batch // 2)
            q, k, v = (jax.device_put(jnp.asarray(
                rng.randn(b, h, seq, dim), dtype))
                for h in (heads, kv_heads or heads, kv_heads or heads))
            # fwd+bwd ~ 3.5 x 4*B*H*T^2*D FLOPs; assume >=20 TFLOP/s so
            # Δn errs toward a LONGER (higher-signal) window
            est_s = 3.5 * 4 * b * heads * seq * seq * dim / 20e12
            dn = int(min(4096, max(64, target_signal_s / est_s)))
            n_lo, n_hi = 4, 4 + dn
            variants = {}
            any_tiled = False
            for blk in blocks:
                if seq % blk:
                    continue
                any_tiled = True
                g = jax.grad(
                    lambda a, c, d, _blk=blk: jnp.sum(flash_attention(
                        a, c, d, None, 0, True, None, 0.0, _blk, _blk,
                        False, window).astype(jnp.float32)),
                    argnums=(0, 1, 2))
                try:
                    # compile-check the SHORT window only: VMEM fit
                    # depends on the block config, not the trip count,
                    # and the protocol warms both windows itself
                    fn_lo = chained_grad_loop(g, n_lo)
                    jax.device_get(fn_lo(q, k, v))
                except Exception as e:              # noqa: BLE001
                    print("dtype=%s seq=%d block %d skipped: %s"
                          % (dtype, seq, blk, str(e)[:100]), flush=True)
                    continue
                variants[blk] = (fn_lo, n_lo,
                                 chained_grad_loop(g, n_hi), n_hi)
            if not variants:
                if not any_tiled:
                    # no candidate even tiles this seq (e.g. a narrow
                    # --blocks selection) — that's a no-measurement, not
                    # a failure; the committed row must survive
                    print("dtype=%s seq=%d: no candidate tiles, row "
                          "kept" % (dtype, seq), flush=True)
                    continue
                print("dtype=%s seq=%d: no block compiled, row dropped"
                      % (dtype, seq), flush=True)
                # a stale committed winner measured under an older
                # kernel must not survive a run where nothing compiles
                table[dtype].pop(str(seq), None)
                _dump(table)
                continue
            measured = run_marginal_protocol(variants, (q, k, v), reps)
            # a non-positive marginal is an overhead spike, not a kernel
            # time — it must never be crowned the winner
            med = {blk: m for blk, (m, _) in measured.items() if m > 0}
            if not med:
                print("dtype=%s seq=%d: all marginals drowned in "
                      "overhead noise, row dropped" % (dtype, seq),
                      flush=True)
                table[dtype].pop(str(seq), None)
                _dump(table)
                continue
            best = min(med, key=med.get)
            row = table[dtype].get(str(seq))
            if not write:
                pass
            elif isinstance(row, dict):     # keep the backward's own pair
                row["fwd"] = best
            else:
                table[dtype][str(seq)] = best
            print("dtype=%s seq=%d dn=%d -> block %d   %s" % (
                dtype, seq, dn, best,
                " ".join("%d:%.3fms" % (b_, m * 1e3)
                         for b_, m in sorted(med.items()))), flush=True)
            if write:
                _dump(table)                         # incremental dump
    return table


def sweep_bwd(seqs=(2048,), blocks=(256, 512, 1024), dtypes=("bfloat16",),
              batch=8, heads=12, dim=64, reps=3, target_signal_s=2.0):
    """Blocks of the fused backward, on the benchmark cell's attention by
    default ([8, 12, 2048, 64] bf16, key-padding mask, no dropout)."""
    import jax
    import jax.numpy as jnp

    import importlib

    # the module: the package exports the function under the same name
    fa = importlib.import_module("paddle_tpu.kernels.flash_attention")

    assert jax.default_backend() != "cpu", "sweep needs the TPU backend"
    with open(OUT) as f:
        table = json.load(f)
    fits = fa._bwd_fused_fits
    for dtype in dtypes:
        for seq in seqs:
            row = table.get(dtype, {}).get(str(seq))
            if row is None:
                print("dtype=%s seq=%d: the table has no such row (the "
                      "forward sweep makes it)" % (dtype, seq), flush=True)
                continue
            fwd = row["fwd"] if isinstance(row, dict) else row
            # candidates are passed as the caller's blocks: the lookup
            # must find no pair of an earlier sweep to put in their place
            fa._block_table()[dtype][str(seq)] = fwd
            rng = np.random.RandomState(0)
            q, k, v, g = (jax.device_put(jnp.asarray(
                rng.randn(batch, heads, seq, dim), dtype))
                for _ in range(4))
            lens = jnp.full((batch,), seq, jnp.int32)
            scale = dim ** -0.5
            out, lse = jax.jit(
                lambda a, b, c, le: fa.flash_attention_raw_lse(
                    a, b, c, le, 0, False, scale, 0.0, fwd, fwd,
                    False))(q, k, v, lens)
            saved = (out, lse.reshape(batch * heads, seq, -1), g, lens)

            def bwd(bq, bk, fused):
                def fn(q_, k_, v_, out_, lse_, g_, lens_):
                    # the form is read while the loop is traced
                    fa._bwd_fused_fits = fits if fused else (
                        lambda *a: False)
                    try:
                        return fa._flash_backward(
                            q_, k_, v_, out_, lse_, g_, None, lens_, None,
                            0, False, scale, 0.0, bq, bk, False)
                    finally:
                        fa._bwd_fused_fits = fits
                return fn

            # 7 and 5 half-filled tile matmuls of 2*B*H*T^2*D, at half of
            # ~200 TFLOP/s: errs toward a longer window
            est_s = 7 * 2 * batch * heads * seq * seq * dim / 100e12
            dn = int(min(4096, max(16, target_signal_s / est_s)))
            n_lo, n_hi = 2, 2 + dn
            cands = {"split": bwd(fwd, fwd, False)}
            for bq in blocks:
                for bk in blocks:
                    if (seq % bq == 0 and seq % bk == 0
                            and fits(seq, dim, dtype, bq, bk)):
                        cands[(bq, bk)] = bwd(bq, bk, True)
            variants = {}
            for key, fn in cands.items():
                try:
                    fn_lo = chained_grad_loop(fn, n_lo)
                    jax.device_get(fn_lo(q, k, v, *saved))
                except Exception as e:              # noqa: BLE001
                    print("dtype=%s seq=%d bwd %s skipped: %s"
                          % (dtype, seq, key, str(e)[:200]), flush=True)
                    continue
                variants[key] = (fn_lo, n_lo,
                                 chained_grad_loop(fn, n_hi), n_hi)
            measured = run_marginal_protocol(variants, (q, k, v) + saved,
                                             reps)
            med = {key: m for key, (m, _) in measured.items() if m > 0}
            print("dtype=%s seq=%d dn=%d backward   %s" % (
                dtype, seq, dn,
                " ".join("%s:%.3fms" % (key, m * 1e3)
                         for key, m in sorted(med.items(), key=str))),
                flush=True)
            fused = {key: m for key, m in med.items() if key != "split"}
            if fused:
                best = min(fused, key=fused.get)
                table[dtype][str(seq)] = {"fwd": fwd, "bwd": list(best)}
                _dump(table)
    return table


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser(
        "flash_block_sweep",
        description="Re-sweep all rows, or --seqs/--dtypes for one row "
                    "with more --reps; winners merge into the table.")
    ap.add_argument("--seqs", type=int, nargs="+",
                    default=list(DEFAULT_SEQS))
    ap.add_argument("--dtypes", nargs="+",
                    default=["bfloat16", "float32"])
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--fresh", action="store_true",
                    help="ignore the existing table, regenerate")
    ap.add_argument("--blocks", type=int, nargs="+",
                    default=list(DEFAULT_BLOCKS),
                    help="candidate block sizes (the streamed kernels "
                         "keep VMEM bounded by block size, so wide "
                         "candidates like 1024 are in the default set "
                         "— a default re-sweep must never clobber a "
                         "committed wide-block winner)")
    ap.add_argument("--bwd", action="store_true",
                    help="sweep the fused backward's (block_q, block_k) "
                         "of the rows in --seqs (batch 8, 12 heads: the "
                         "benchmark cell's attention) instead")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--heads", type=int, default=16)
    ap.add_argument("--kv-heads", type=int, default=None,
                    help="K/V heads under --heads Q heads")
    ap.add_argument("--dim", type=int, default=64)
    ap.add_argument("--window", type=int, default=None,
                    help="causal window of the forward+backward sweep")
    ap.add_argument("--print-only", action="store_true",
                    help="print the readings, leave the table as it is")
    a = ap.parse_args()
    if a.bwd:
        sweep_bwd(seqs=tuple(a.seqs), dtypes=tuple(a.dtypes), reps=a.reps,
                  blocks=tuple(a.blocks))
    else:
        sweep(seqs=tuple(a.seqs), dtypes=tuple(a.dtypes), reps=a.reps,
              blocks=tuple(a.blocks), fresh=a.fresh, batch=a.batch,
              heads=a.heads, dim=a.dim, kv_heads=a.kv_heads,
              window=a.window, write=not a.print_only)
    if not a.print_only:
        print("wrote", OUT)
