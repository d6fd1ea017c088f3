#!/usr/bin/env python
"""Multi-chip scaling probe: train the same model over a dp mesh at 1/2/4/8
(forced host) devices and report the weak-scaling efficiency curve.

Each device count runs in its OWN subprocess with ``JAX_PLATFORMS=cpu`` and
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` — XLA fixes the
device count at backend init, so a single process cannot sweep it. The
child trains through the real mesh path (``Executor.run(mesh=...)`` →
engine GSPMD jit, the exact seam production uses) with a
weak-scaling batch (``--batch-per-device × N``) and publishes its
throughput as ``probe.samples_per_sec``/``probe.devices`` gauges into a
per-run telemetry sink (observability JsonlSink); the parent assembles the
scaling table FROM THE SINKS — the same files a fleet run would ship — so
the probe doubles as an end-to-end test of the telemetry export path.

Efficiency here is CAPACITY-normalized: eff(N) = tput(N) / tput(1). The
N forced-host devices all share one physical CPU, so the real-hardware
definition tput(N)/(N×tput(1)) could never exceed ~1/N no matter how
good the graph is — whereas against flat capacity, healthy weak scaling
(same total FLOPs/sec, partitioning overhead only) sits near 1.0 and a
broken graph (state gathered to host every step, per-count recompiles,
unsharded fallbacks) craters well below it. A run on real devices
would use the per-device normalization (no benchmark cell spans chips
yet: PERF.md, Open questions row 2); this probe is the
shared-capacity stand-in. ``--efficiency-floor F`` exits non-zero when
the largest-N efficiency lands below F — the CI guard for "the psum
path stopped scaling".

``--predict`` additionally turns on the engine's SPMD prediction seam
(PADDLE_TPU_SPMD_PREDICT) in every child: the first run of each mesh
executable parses its own jitted HLO and emits a
``spmd.prediction_delta`` span into the sink; the parent prints the
predicted-vs-measured collective counts/bytes and per-device peak next
to the scaling table. ``--predict-tolerance F`` makes it a CI gate:
exit non-zero when any device count's psum count mismatches or its
collective bytes miss by more than the relative tolerance.

``--zero1`` flips every child onto the ZeRO-1 sharded weight update
(PADDLE_TPU_ZERO=1; optionally ``--bucket-mb N`` for bucketed gradient
reduction) so two invocations give the replicated-vs-sharded scaling
A/B.

Usage:
  python tools/multichip_probe.py --model mlp --devices 1,2,4,8
  python tools/multichip_probe.py --model bert --efficiency-floor 0.6
  python tools/multichip_probe.py --predict --predict-tolerance 0.1
  python tools/multichip_probe.py --model mlp --zero1 --bucket-mb 4
"""

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)

# tiny CPU-sized geometries: the probe measures the partitioner's scaling
# behavior, not the chip, so the models only need enough compute per step
# to dominate python dispatch
MODELS = ("mlp", "bert", "resnet50")


def _build(model, batch):
    """(main, startup, loss_var, feed_dict, param_rule_hints) on tiny
    CPU geometry. Import inside: the child must set platform env before
    jax loads."""
    import numpy as np

    from paddle_tpu import models

    rng = np.random.RandomState(0)
    if model == "mlp":
        main, startup, h = models.mnist.get_model(lr=0.01)
        feed = {"img": rng.randn(batch, 784).astype(np.float32),
                "label": rng.randint(0, 10, (batch, 1)).astype(np.int64)}
        return main, startup, h["loss"], feed
    if model == "bert":
        kw = dict(d_model=64, n_layers=2, n_heads=2, d_inner=128)
        main, startup, h = models.bert.get_model(
            batch_size=batch, seq_len=32, vocab_size=512, dropout=0.0,
            lr=1e-4, max_position=512, **kw)
        feed = models.bert.make_fake_batch(batch, 32, 512, kw["n_heads"])
        return main, startup, h["loss"], feed
    if model == "resnet50":
        # cifar resnet at depth 20: the real conv/BN/residual training
        # graph without imagenet-sized CPU step times
        main, startup, h = models.resnet.get_model(
            dataset="cifar10", depth=20, class_num=10, lr=0.1)
        feed = {"img": rng.randn(batch, 3, 32, 32).astype(np.float32),
                "label": rng.randint(0, 10, (batch, 1)).astype(np.int64)}
        return main, startup, h["loss"], feed
    raise ValueError("unknown model %r (want one of %s)" % (model, MODELS))


def _child(model, batch_per_device, steps, warmup):
    """Runs inside the forced-device-count subprocess: train over a dp
    mesh spanning every (virtual) device, publish throughput gauges to
    the attached sink, print one JSON line as a sink-less fallback."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    import paddle_tpu.fluid as fluid
    from paddle_tpu import observability as obs
    from paddle_tpu.parallel import ShardingRules, make_mesh

    n = len(jax.devices())
    batch = batch_per_device * n
    main, startup, loss, feed = _build(model, batch)
    mesh = make_mesh({"dp": n})
    exe = fluid.Executor()
    scope = fluid.Scope()
    feed = {k: jax.device_put(v) for k, v in feed.items()}
    with fluid.scope_guard(scope):
        exe.run(startup)
        run = lambda: exe.run(main, feed=feed, fetch_list=[loss],
                              mesh=mesh, shard_rules=ShardingRules(),
                              return_numpy=False)[0]
        out = None
        for _ in range(warmup):
            out = run()
        jax.device_get(out)  # drain compile + warmup before timing
        t0 = time.perf_counter()
        for _ in range(steps):
            out = run()
        val = jax.device_get(out)  # drain the dispatched pipeline
        elapsed = time.perf_counter() - t0
    assert np.isfinite(float(np.asarray(val).reshape(-1)[0]))
    tput = batch * steps / elapsed
    obs.set_gauge("probe.samples_per_sec", tput)
    obs.set_gauge("probe.devices", n)
    obs.set_gauge("probe.batch", batch)
    obs.detach_sink()  # final snapshot + flush (attach came from the flag)
    print(json.dumps({"devices": n, "samples_per_sec": tput,
                      "batch": batch}))


def _read_sink_gauges(path):
    """Last metrics snapshot's gauges from a JSONL sink file (the child's
    detach_sink() emits one on exit)."""
    gauges = None
    try:
        with open(path, encoding="utf-8") as f:
            for line in f:
                try:
                    ev = json.loads(line)
                except ValueError:
                    continue
                if ev.get("t") == "snap":
                    gauges = (ev.get("metrics") or {}).get("gauges") or gauges
    except OSError:
        return None
    return gauges


def _read_sink_span(path, name):
    """Last "span" event named ``name`` from a JSONL sink file; returns
    its args dict (or None). The prediction seam emits exactly one
    ``spmd.prediction_delta`` per compiled executable."""
    args = None
    try:
        with open(path, encoding="utf-8") as f:
            for line in f:
                try:
                    ev = json.loads(line)
                except ValueError:
                    continue
                if ev.get("t") == "span" and ev.get("name") == name:
                    args = ev.get("args") or args
    except OSError:
        return None
    return args


def probe_scaling(model="mlp", devices=(1, 2, 4, 8), batch_per_device=64,
                  steps=12, warmup=3, sink_dir=None, predict=False,
                  zero1=False, bucket_mb=0.0):
    """Run the sweep; returns {n: samples_per_sec} (plus
    {n: prediction_delta args} when ``predict``). Parent-side only.
    ``zero1``/``bucket_mb`` turn on the ZeRO-1 sharded weight update
    (PADDLE_TPU_ZERO) and bucketed gradient reduction
    (PADDLE_TPU_GRAD_BUCKET_MB) in every child — the A/B lever that
    prices the sharded update against the replicated one."""
    results = {}
    predictions = {}
    own_tmp = sink_dir is None
    if own_tmp:
        sink_dir = tempfile.mkdtemp(prefix="multichip_probe_")
    for n in devices:
        sink = os.path.join(sink_dir, "probe_dp%d%s.jsonl"
                            % (n, "_zero1" if zero1 else ""))
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                            + " --xla_force_host_platform_device_count=%d"
                            % n).strip()
        env["PADDLE_TPU_METRICS"] = "1"
        env["PADDLE_TPU_METRICS_SINK"] = sink
        if zero1:
            env["PADDLE_TPU_ZERO"] = "1"
            if bucket_mb:
                env["PADDLE_TPU_GRAD_BUCKET_MB"] = str(bucket_mb)
        if predict:
            env["PADDLE_TPU_SPMD_PREDICT"] = "1"
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        cmd = [sys.executable, os.path.abspath(__file__), "--child",
               "--model", model, "--batch-per-device",
               str(batch_per_device), "--steps", str(steps), "--warmup",
               str(warmup)]
        r = subprocess.run(cmd, env=env, cwd=REPO, capture_output=True,
                           text=True)
        if r.returncode != 0:
            sys.stderr.write(r.stderr[-2000:] + "\n")
            raise RuntimeError("probe child (dp=%d) failed rc=%d"
                               % (n, r.returncode))
        gauges = _read_sink_gauges(sink)
        if gauges and "probe.samples_per_sec" in gauges:
            results[n] = float(gauges["probe.samples_per_sec"])
        else:  # sink missing/rotated away — fall back to the stdout line
            last = [l for l in r.stdout.splitlines() if l.strip()][-1]
            results[n] = float(json.loads(last)["samples_per_sec"])
        if predict:
            delta = _read_sink_span(sink, "spmd.prediction_delta")
            if delta is not None:
                predictions[n] = delta
    if predict:
        return results, predictions
    return results


def efficiency_table(results):
    """[(n, tput, efficiency)] with efficiency = tput(n)/tput(1) — the
    shared-capacity normalization (see module docstring): the N virtual
    devices split one CPU, so flat throughput IS perfect weak scaling."""
    base = results.get(1)
    rows = []
    for n in sorted(results):
        t = results[n]
        eff = (t / base) if base else None
        rows.append((n, t, eff))
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--model", default="mlp", choices=MODELS)
    ap.add_argument("--devices", default="1,2,4,8",
                    help="comma-separated device counts to sweep")
    ap.add_argument("--batch-per-device", type=int, default=64)
    ap.add_argument("--steps", type=int, default=12)
    ap.add_argument("--warmup", type=int, default=3)
    ap.add_argument("--efficiency-floor", type=float, default=0.0,
                    help="exit 1 if the largest-N efficiency is below this")
    ap.add_argument("--predict", action="store_true",
                    help="enable the engine's SPMD prediction seam in "
                         "every child and print predicted-vs-measured "
                         "collective counts/bytes and per-device peak "
                         "next to the scaling table")
    ap.add_argument("--predict-tolerance", type=float, default=None,
                    metavar="F",
                    help="CI gate for --predict: exit 1 when any device "
                         "count's psum count mismatches or collective "
                         "bytes miss by more than this relative "
                         "tolerance (e.g. 0.1)")
    ap.add_argument("--sink-dir", default=None,
                    help="directory for the per-run telemetry sinks "
                         "(default: a fresh temp dir)")
    ap.add_argument("--zero1", action="store_true",
                    help="train with the ZeRO-1 sharded weight update "
                         "(PADDLE_TPU_ZERO=1 in every child) — combine "
                         "with a plain run for the replicated-vs-"
                         "sharded A/B")
    ap.add_argument("--bucket-mb", type=float, default=0.0, metavar="MB",
                    help="with --zero1: bucketed gradient reduction "
                         "size (PADDLE_TPU_GRAD_BUCKET_MB)")
    ap.add_argument("--child", action="store_true", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)

    if args.child:
        _child(args.model, args.batch_per_device, args.steps, args.warmup)
        return 0

    devices = tuple(int(d) for d in args.devices.split(","))
    predict = args.predict or args.predict_tolerance is not None
    predictions = {}
    if predict:
        results, predictions = probe_scaling(
            args.model, devices, args.batch_per_device, args.steps,
            args.warmup, args.sink_dir, predict=True,
            zero1=args.zero1, bucket_mb=args.bucket_mb)
    else:
        results = probe_scaling(args.model, devices,
                                args.batch_per_device, args.steps,
                                args.warmup, args.sink_dir,
                                zero1=args.zero1,
                                bucket_mb=args.bucket_mb)
    rows = efficiency_table(results)
    mode = ("zero1 bucket=%gMB" % args.bucket_mb if args.zero1
            and args.bucket_mb else
            "zero1" if args.zero1 else "replicated")
    print("update: %s" % mode)
    print("%-8s %-18s %s" % ("devices", "samples/sec", "efficiency"))
    for n, t, eff in rows:
        print("%-8d %-18.2f %s" % (n, t,
                                   "%.3f" % eff if eff is not None else "-"))
    summary = {"model": args.model, "update": mode,
               "throughput": {str(n): round(t, 2) for n, t, _ in rows},
               "efficiency": {str(n): round(eff, 4)
                              for n, _, eff in rows if eff is not None}}
    print(json.dumps(summary))
    rc = 0
    if predict:
        print("\n%-8s %-16s %-26s %-8s %s"
              % ("devices", "psums p/m", "coll bytes p/m", "ratio",
                 "peak bytes p/m"))
        for n in sorted(results):
            d = predictions.get(n)
            if d is None:  # dp=1: no collectives, no seam event
                print("%-8d %-16s %-26s %-8s %s" % (n, "-", "-", "-", "-"))
                continue
            bp, bm = d["bytes_predicted"], d["bytes_measured"]
            ratio = (bm / bp) if bp else float("nan")
            print("%-8d %-16s %-26s %-8s %s" % (
                n,
                "%d/%d" % (d["psums_predicted"], d["psums_measured"]),
                "%d/%d" % (bp, bm), "%.3f" % ratio,
                "%d/%d" % (d["peak_bytes_predicted"],
                           d["peak_bytes_measured"])))
            if args.predict_tolerance is not None:
                if d["psums_predicted"] != d["psums_measured"]:
                    sys.stderr.write(
                        "predict gate: psum count %d != measured %d at "
                        "%d devices\n" % (d["psums_predicted"],
                                          d["psums_measured"], n))
                    rc = 1
                if bp and abs(ratio - 1.0) > args.predict_tolerance:
                    sys.stderr.write(
                        "predict gate: collective bytes off by %.1f%% "
                        "(> %.1f%%) at %d devices\n"
                        % (abs(ratio - 1.0) * 100,
                           args.predict_tolerance * 100, n))
                    rc = 1
        if args.predict_tolerance is not None and not predictions:
            sys.stderr.write("predict gate: no spmd.prediction_delta "
                             "events found in any child sink\n")
            rc = 1
    if rows and rows[-1][2] is not None \
            and rows[-1][2] < args.efficiency_floor:
        sys.stderr.write(
            "scaling efficiency %.3f at %d devices below floor %.3f\n"
            % (rows[-1][2], rows[-1][0], args.efficiency_floor))
        rc = 1
    return rc


if __name__ == "__main__":
    sys.exit(main())
