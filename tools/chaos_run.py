"""Chaos acceptance run: training under the supervised launcher with a
seeded random fault schedule, asserting the job still completes with the
fault-free result.

Two modes in one file so the supervisor respawns exactly this script:

* default (supervisor): builds a reproducible fault spec
  (``faultinject.random_spec``) — by default one worker kill plus one
  NaN trip at random steps — exports it as ``PADDLE_TPU_FAULT_SPEC``,
  runs ``--nproc`` workers under ``distributed.launch.supervise`` with a
  restart budget, then verifies every rank finished all steps AND
  (``--check-parity``) that each rank's loss trajectory matches a
  fault-free in-process run bit-for-bit. Prints a one-line JSON verdict;
  exits non-zero on any miss.
* ``--worker``: one training process — a small MLP + SGD driven by
  ``resilience.ResilientDriver`` with a per-rank checkpoint root under
  ``PADDLE_TPU_RECOVERY_CKPT``, writing its per-step losses to
  ``<result-dir>/rank<i>.json`` on completion. Restart-safe: a respawned
  worker resumes from its latest complete checkpoint.

Usage::

    python tools/chaos_run.py --steps 30 --nproc 2 --seed 7
    python tools/chaos_run.py --spec 'step_nan@9' --nproc 1
    python tools/chaos_run.py --hang --nproc 2        # heartbeat watchdog
    python tools/chaos_run.py --dispatch-steps 8 --nproc 1 \
        --spec 'step_nan@12'   # fault lands mid async dispatch window
    python tools/chaos_run.py --shrink --nproc 2      # permanent loss:
        # the highest rank exits LOST mid-run, the supervisor shrinks
        # the gang (health.mesh_shrunk) and the SURVIVORS finish all
        # steps with fault-free parity
    python tools/chaos_run.py --sdc --nproc 2         # silent corruption:
        # a transient bitflip on rank 0 is detected at that step's
        # retire, replayed clean, and absorbed; a PERSISTENT bitflip on
        # the highest rank is blamed by the replay vote, the rank exits
        # LOST, the supervisor shrinks, and the survivors finish with
        # bit-exact fault-free parity
    python tools/chaos_run.py --preempt --nproc 2     # graceful SIGTERM:
        # rank 0 drains + checkpoints + exits rc 46; the supervisor
        # restarts WITHOUT spending restart budget and the job completes
    python tools/chaos_run.py --shrink --mesh --zero1 --nproc 2
        # ZeRO-1 sharded update on the dp mesh: the Momentum velocity
        # slots live partitioned, the mid-run rank loss shrinks the
        # mesh (sharded state reshards onto the survivors), and the
        # trajectory must keep fault-free parity

CPU-only by construction (workers force JAX_PLATFORMS=cpu); the point
is recovery-path coverage, not throughput.
"""

import argparse
import glob
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

CKPT_INTERVAL = 5


def _zero1_mode():
    """--zero1 gate: reads the engine's own PADDLE_TPU_ZERO flag env so
    the probe model switches to Momentum (slot state for the sharded
    update to partition) identically in workers AND the supervisor's
    in-process parity reference — where the flag itself is inert
    because the reference runs mesh-less."""
    return os.environ.get("PADDLE_TPU_ZERO", "").strip().lower() \
        not in ("", "0", "false")


def build(lr=0.1):
    import numpy as np

    import paddle_tpu.fluid as fluid
    from paddle_tpu.framework import Program, program_guard

    main, startup = Program(), Program()
    with program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[16], dtype="float32")
        h = fluid.layers.fc(input=x, size=16, act="relu",
                            param_attr=fluid.ParamAttr(name="cw1"),
                            bias_attr=False)
        y = fluid.layers.data(name="y", shape=[1], dtype="int64")
        pred = fluid.layers.fc(input=h, size=4,
                               param_attr=fluid.ParamAttr(name="cw2"),
                               bias_attr=False)
        loss = fluid.layers.mean(fluid.layers.softmax_with_cross_entropy(
            logits=pred, label=y))
        if _zero1_mode():
            fluid.optimizer.Momentum(learning_rate=lr,
                                     momentum=0.9).minimize(loss)
        else:
            fluid.optimizer.SGD(learning_rate=lr).minimize(loss)
    init = {
        "cw1": np.linspace(-0.4, 0.4, 16 * 16).astype(
            np.float32).reshape(16, 16),
        "cw2": np.linspace(0.3, -0.3, 16 * 4).astype(
            np.float32).reshape(16, 4),
    }
    return main, startup, loss, init


def batch_fn(step, batch=16, seed=0):
    """Deterministic in ``step`` — the rewind/replay contract the
    ResilientDriver requires for exact post-recovery parity."""
    import numpy as np

    W = np.random.RandomState(0).randn(16, 4).astype(np.float32)
    rng = np.random.RandomState(seed * 100003 + step)
    xv = rng.randn(batch, 16).astype(np.float32)
    yv = np.argmax(xv @ W, 1).astype(np.int64).reshape(-1, 1)
    return {"x": xv, "y": yv}


def train_losses(n_steps, ckpt_root, rank=0, max_rollbacks=8,
                 on_step=None, dispatch_steps=1, replica_roots=None):
    """Train the probe model under a ResilientDriver; returns the
    per-step scalar losses. Faults (if any are scheduled) fire through
    the engine's real seams; recovery is the driver's problem.
    ``dispatch_steps>1`` runs the loop through the engine's async
    dispatch window (engine/pipeline.py) — a fault then lands
    MID-WINDOW and the driver discards the in-flight steps before
    restoring."""
    import numpy as np

    import paddle_tpu.fluid as fluid
    from paddle_tpu import flags
    from paddle_tpu.checkpoint import CheckpointManager
    from paddle_tpu.resilience import ResilientDriver

    if dispatch_steps and dispatch_steps > 1:
        flags.set_flags({"dispatch_steps": int(dispatch_steps)})
    main, startup, loss, init = build()
    exe = fluid.Executor(fluid.TPUPlace())
    exe.run(startup)
    scope = fluid.global_scope()
    for k, v in init.items():
        scope.set(k, v)
    mgr = CheckpointManager(ckpt_root, max_to_keep=4,
                            replica_roots=replica_roots)
    # context manager: close() joins the async checkpoint writer and
    # SURFACES any error it recorded — without it a failed background
    # save of the final state is silently lost at process exit
    with ResilientDriver(exe, main, [loss], mgr, scope=scope,
                         ckpt_interval=CKPT_INTERVAL,
                         max_rollbacks=max_rollbacks) as drv:
        results = drv.train(lambda s: batch_fn(s, seed=rank), n_steps,
                            on_step=on_step)
    return [float(np.asarray(r[0]).reshape(-1)[0]) for r in results]


def reassemble_steps(steps_path, n_steps):
    """Per-step JSONL (possibly spanning incarnations and rollback
    replays) -> full loss trajectory, last write per step winning.
    Returns None when any step is missing."""
    got = {}
    try:
        with open(steps_path) as f:
            for line in f:
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue  # torn tail line from a kill mid-write
                got[rec["step"]] = rec["loss"]
    except OSError:
        return None
    if set(got) != set(range(n_steps)):
        return None
    return [got[s] for s in range(n_steps)]


def run_worker(args):
    # --mesh: 2 virtual devices so the dp-mesh GSPMD path (selected via
    # the inherited PADDLE_TPU_MESH flag) has something to shard over
    os.environ.setdefault(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=%d"
        % (2 if args.mesh else 1))
    import jax

    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    from paddle_tpu.resilience import SDCBlamed
    from paddle_tpu.resilience.faultinject import LOST_EXIT_CODE

    rank = int(os.environ.get("PADDLE_TRAINER_ID", "0"))
    nproc = int(os.environ.get("PADDLE_TRAINERS_NUM", "1"))
    root = os.environ.get("PADDLE_TPU_RECOVERY_CKPT") or os.path.join(
        args.result_dir, "ckpt")
    # elastic: a respawned worker inherits the supervisor's shrink count
    # and gives up on one virtual device per shrink — mesh_from_flag
    # then re-plans its dp=-1 axis over the survivors (the in-process
    # half of the capacity loss the gang shrink is the process half of)
    shrinks = int(os.environ.get("PADDLE_TPU_SHRINK_COUNT", "0"))
    if args.mesh and shrinks:
        from paddle_tpu.resilience import elastic

        for i in range(min(shrinks, 1)):     # 2 devices: 1 can go
            elastic.mark_device_lost(2 - 1 - i)
    # checkpoint quorum: with PADDLE_TPU_CKPT_REPLICAS > 0 each rank
    # mirrors its shards into its PEERS' roots, so a dead local disk
    # (disk_fail) restores from a surviving replica
    replica_roots = None
    if int(os.environ.get("PADDLE_TPU_CKPT_REPLICAS", "0") or 0) > 0:
        replica_roots = [os.path.join(root, "rank%d" % r)
                         for r in range(nproc) if r != rank]
    # stream every step's loss to an append-only per-rank JSONL: a
    # killed incarnation's in-memory results die with it, but this file
    # survives the respawn, so the full trajectory reassembles
    steps_path = os.path.join(args.result_dir, "rank%d.steps.jsonl" % rank)
    with open(steps_path, "a") as steps_f:
        # Resolution-aware streaming: forcing float(out[0]) on every
        # step would retire the dispatch window each time and serialize
        # it back to depth 1 — instead park placeholders and write them
        # once they resolve on their own (the window-overflow retire).
        # A killed incarnation loses at most the in-flight tail, which
        # the respawn replays from its checkpoint (last-write-wins in
        # reassemble_steps); rollback-discarded placeholders are
        # dropped, their replayed steps re-fire on_step.
        pending = []

        def _flush(force=False):
            while pending:
                s, v = pending[0]
                if getattr(v, "discarded", False):
                    pending.pop(0)
                    continue
                if not force and not getattr(v, "resolved", True):
                    break
                steps_f.write(json.dumps(
                    {"step": s,
                     "loss": float(np.asarray(v).reshape(-1)[0])}) + "\n")
                steps_f.flush()
                pending.pop(0)

        def on_step(step, out):
            pending.append((step, out[0]))
            _flush()

        try:
            train_losses(args.steps, os.path.join(root, "rank%d" % rank),
                         rank=rank, on_step=on_step,
                         dispatch_steps=args.dispatch_steps,
                         replica_roots=replica_roots)
        except SDCBlamed as e:
            # the sentinel's replay vote convicted OUR device of
            # persistent silent corruption and there is no in-process
            # spare to quarantine: flush what resolved (the discarded
            # in-flight tail drops itself), then exit LOST so the
            # supervisor shrinks the gang around this rank — the same
            # path a dead host takes, because that is what we now are
            _flush(force=True)
            from paddle_tpu import observability as obs

            # sentinel.blamed must be on disk for the verdict scan
            obs.flush_sink()
            print("chaos_run worker %d: %s; exiting LOST" % (rank, e),
                  file=sys.stderr)
            return LOST_EXIT_CODE
        _flush(force=True)   # train() drained the window; all resolved
    losses = reassemble_steps(steps_path, args.steps)
    if losses is None:
        print("chaos_run worker %d: incomplete step log" % rank,
              file=sys.stderr)
        return 1
    out = os.path.join(args.result_dir, "rank%d.json" % rank)
    tmp = out + ".tmp"
    with open(tmp, "w") as f:
        json.dump(losses, f)
    os.replace(tmp, out)
    return 0


def run_supervisor(args):
    from paddle_tpu import flags
    from paddle_tpu import observability as obs
    from paddle_tpu.distributed.launch import supervise
    from paddle_tpu.resilience.faultinject import random_spec

    flags.set_flags({"metrics": True})
    kinds = (("worker_hang", "step_nan") if args.hang
             else ("worker_kill", "step_nan"))
    # --sdc injects at ENGINE step numbers (the bitflip seam lives in
    # the executor): startup run is engine step 1, batch 0 is engine
    # step 2, so batch step b corrupts at engine step b + 2
    sdc_transient = max(2, args.steps // 3) + 2
    sdc_persist = max(4, args.steps // 2) + 2
    if args.spec is not None:
        spec = args.spec
    elif args.shrink:
        # permanent loss of the HIGHEST rank (survivor ranks then keep
        # their ids — and their checkpoint roots — across the shrink)
        spec = "worker_loss@rank%d:step%d" % (
            args.nproc - 1, max(2, args.steps // 2))
    elif args.sdc:
        # one TRANSIENT flip on rank 0 (fires once; the replay is clean
        # and the step is absorbed) plus a PERSISTENT flip on the
        # highest rank (x9: every replay corrupts again, so the vote
        # blames the device and the rank exits LOST)
        spec = ("bitflip@step%d:rank0;bitflip@step%d:rank%d:x9"
                % (sdc_transient, sdc_persist, args.nproc - 1))
    elif args.preempt:
        # SIGTERM-style eviction of rank 0 mid-run: the driver drains,
        # checkpoints, and exits PREEMPT_EXIT_CODE; the supervisor
        # restarts the gang without spending restart budget
        spec = "preempt@step%d:rank0" % max(2, args.steps // 2)
    else:
        spec = random_spec(args.seed, args.steps, nproc=args.nproc,
                           kinds=kinds)
    workdir = args.workdir or tempfile.mkdtemp(prefix="chaos_run_")
    result_dir = os.path.join(workdir, "results")
    ckpt_dir = os.path.join(workdir, "ckpt")
    os.makedirs(result_dir, exist_ok=True)
    sink = os.path.join(workdir, "metrics.jsonl")
    # the supervisor's own events (health.hang_detected, recovery.*)
    # land in the same sink family as the workers', host-tagged h99
    obs.attach_sink(sink, host=99)
    # kills AND watchdog-cleared hangs count against the restart budget;
    # everything else the workers absorb in-process
    max_restarts = args.max_restarts if args.max_restarts is not None \
        else max(2, spec.count("worker_kill")
                 + spec.count("worker_hang") + 1)
    max_shrinks = args.max_shrinks if args.max_shrinks is not None \
        else spec.count("worker_loss") + (1 if args.sdc else 0)
    env_extra = {
        "PADDLE_TPU_FAULT_SPEC": spec,
        "PADDLE_TPU_METRICS": "1",
        "PADDLE_TPU_METRICS_SINK": sink,
        # workers keep their own interval ledgers (goodput.* gauges in
        # the snap stream); the supervisor's JobLedger covers the
        # cross-incarnation gaps and lands in stats["goodput"]
        "PADDLE_TPU_GOODPUT": "1",
    }
    if args.sdc:
        # arm the sentinel in every worker: in-graph digests, replay
        # voting, and blame are all worker-side — the supervisor only
        # sees the resulting LOST exit
        env_extra["PADDLE_TPU_SDC"] = "1"
    if args.trace:
        # request tracing across the process boundary: supervise()
        # sees the flag in env_extra, opens one eager job trace, and
        # exports PADDLE_TPU_TRACE_ID to every incarnation — a
        # restarted worker's spans join the same trace (verdict below)
        env_extra["PADDLE_TPU_TRACE_SAMPLE"] = "1"
    if args.zero1:
        env_extra["PADDLE_TPU_ZERO"] = "1"
    if args.ckpt_replicas:
        env_extra["PADDLE_TPU_CKPT_REPLICAS"] = str(args.ckpt_replicas)
    worker_cmd = [os.path.abspath(__file__), "--worker",
                  "--steps", str(args.steps), "--result-dir", result_dir]
    if args.dispatch_steps > 1:
        # workers run the async dispatch window; the in-process parity
        # reference below stays synchronous (flag unset here), so
        # --check-parity proves faulted windowed == fault-free sync
        worker_cmd += ["--dispatch-steps", str(args.dispatch_steps)]
    if args.mesh:
        # every worker trains through the mesh-sharded executor path: a
        # dp mesh over 2 virtual devices, selected by the flag the
        # executor reads when no explicit mesh is passed. The override
        # (not setdefault) matters: the supervisor pinned its OWN
        # XLA_FLAGS to 1 device before initializing jax.
        env_extra["PADDLE_TPU_MESH"] = "dp=-1"
        env_extra["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
        worker_cmd.append("--mesh")
    stats = {}
    rc = supervise(worker_cmd, nproc=args.nproc, env_extra=env_extra,
                   max_restarts=max_restarts, recovery_dir=ckpt_dir,
                   started_port=args.started_port,
                   heartbeat_ms=args.heartbeat_ms,
                   hang_timeout_s=args.hang_timeout,
                   max_shrinks=max_shrinks, stats=stats)
    obs.detach_sink()

    final_nproc = stats.get("final_nproc", args.nproc)
    verdict = {"spec": spec, "rc": rc, "workdir": workdir,
               "restarts": obs.snapshot()["counters"].get(
                   "recovery.restart", 0),
               "shrinks": stats.get("shrinks", 0),
               "final_nproc": final_nproc}
    problems = []
    if rc != 0:
        problems.append("gang failed with rc %s" % rc)
    # after a shrink only the SURVIVING ranks owe a full trajectory —
    # the lost rank is permanently gone by design
    ranks = {}
    for r in range(final_nproc):
        path = os.path.join(result_dir, "rank%d.json" % r)
        try:
            with open(path) as f:
                ranks[r] = json.load(f)
        except (OSError, ValueError) as e:
            problems.append("rank %d wrote no result (%s)" % (r, e))
            continue
        if len(ranks[r]) != args.steps:
            problems.append("rank %d finished %d/%d steps"
                            % (r, len(ranks[r]), args.steps))
    # the workers' telemetry sinks ARE the incident log: recoveries
    # must have been recorded there, not just survived. Per-worker
    # sinks are host-tagged (metrics.jsonl -> metrics.h<rank>.jsonl).
    recoveries = []
    sentinel_events = []
    trace_events = []
    for path in glob.glob(os.path.splitext(sink)[0] + "*"):
        with open(path) as f:
            for line in f:
                try:
                    ev = json.loads(line)
                except ValueError:
                    continue
                name = str(ev.get("name", ""))
                if name.startswith(("recovery.", "faultinject",
                                    "health.", "ckpt.", "sentinel.")) \
                        and name != "ckpt.snapshot":
                    # ckpt.snapshot is routine save traffic, not an
                    # incident; the quorum/replica/poison events are
                    recoveries.append(name)
                if name.startswith("sentinel."):
                    sentinel_events.append(ev)
                if name.startswith("trace."):
                    trace_events.append(ev)
    verdict["recovery_events"] = sorted(set(recoveries))
    if spec and not recoveries and verdict["restarts"] == 0:
        problems.append("no recovery events recorded for spec %r" % spec)
    if "worker_hang" in spec and \
            "health.hang_detected" not in verdict["recovery_events"]:
        # the acceptance bar: the hang must be DETECTED from heartbeat
        # data, not merely survived by accident
        problems.append("spec injected worker_hang but the supervisor "
                        "never recorded health.hang_detected")
    if args.shrink or args.sdc:
        # the acceptance bar: the loss must have been ACTED on — the
        # supervisor recorded the shrink and the gang really is smaller
        if "health.mesh_shrunk" not in verdict["recovery_events"]:
            problems.append("the supervisor never recorded "
                            "health.mesh_shrunk")
        if final_nproc >= args.nproc:
            problems.append("the gang never shrank "
                            "(final nproc %d)" % final_nproc)
    if args.sdc:
        # the --sdc acceptance bar, end to end: the corruption must be
        # DETECTED at the injected step's retire (not later), the
        # replay vote must BLAME the injected rank, the transient must
        # have been absorbed, and the survivors' parity check below
        # proves the blamed rank's eviction cost zero trajectory drift
        by_name = {}
        for ev in sentinel_events:
            by_name.setdefault(ev["name"], []).append(
                ev.get("args") or {})
        suspects = by_name.get("sentinel.suspect", [])
        if not any(int(a.get("step", -1)) == sdc_persist
                   for a in suspects):
            problems.append(
                "no sentinel.suspect at injected engine step %d "
                "(suspects: %r)" % (sdc_persist, suspects))
        blamed = by_name.get("sentinel.blamed", [])
        if not any(int(a.get("step", -1)) == sdc_persist
                   and int(a.get("rank", -1)) == args.nproc - 1
                   for a in blamed):
            problems.append(
                "persistent bitflip on rank %d at engine step %d was "
                "never blamed (blamed: %r)"
                % (args.nproc - 1, sdc_persist, blamed))
        if not by_name.get("sentinel.transient"):
            problems.append("the transient bitflip on rank 0 was never "
                            "absorbed (no sentinel.transient event)")
        verdict["sentinel_events"] = sorted(by_name)
    if args.preempt:
        # the --preempt acceptance bar: the eviction was GRACEFUL (the
        # driver recorded recovery.preempted before exiting 46), the
        # supervisor took the no-budget restart path, and the restart
        # budget is untouched
        if "recovery.preempted" not in verdict["recovery_events"]:
            problems.append("rank 0 never recorded recovery.preempted")
        if "recovery.preempt_restart" not in verdict["recovery_events"]:
            problems.append("the supervisor never recorded "
                            "recovery.preempt_restart")
        verdict["preempts"] = stats.get("preempts", 0)
        if verdict["restarts"] != 0:
            problems.append(
                "preemption burned restart budget (recovery.restart "
                "= %d, expected 0)" % verdict["restarts"])
    if args.trace:
        # the --trace acceptance bar: ONE stitched trace spans the
        # whole chaosed job — the supervisor's trace ID was adopted by
        # every incarnation (spans from >= 2 distinct incarnations when
        # the gang restarted), with the supervisor's restart-gap span
        # between them. All reconstructed from the sinks alone.
        job_trace = stats.get("trace_id")
        verdict["trace_id"] = job_trace
        mine = [ev for ev in trace_events
                if (ev.get("args") or {}).get("trace") == job_trace]
        incs = sorted({(ev.get("args") or {}).get("incarnation")
                       for ev in mine
                       if (ev.get("args") or {}).get("incarnation")
                       is not None})
        names = sorted({str(ev.get("name", "")) for ev in mine})
        verdict["trace"] = {"spans": len(mine), "incarnations": incs,
                            "names": names}
        if not job_trace:
            problems.append("supervise() opened no job trace "
                            "(stats carries no trace_id)")
        elif not mine:
            problems.append("no trace.* spans for job trace %s in the "
                            "sinks" % job_trace)
        else:
            if verdict["restarts"] > 0 and len(incs) < 2:
                problems.append(
                    "gang restarted but the job trace has spans from "
                    "incarnation(s) %r only — the respawned worker "
                    "never joined the trace" % (incs,))
            if verdict["restarts"] > 0 \
                    and "trace.restart" not in names:
                problems.append("job trace has no supervisor "
                                "trace.restart span covering the gap")
            if "trace.train_start" not in names:
                problems.append("no worker ever adopted the job trace "
                                "(missing trace.train_start)")
    # goodput attribution gate: the supervisor's job ledger must (a)
    # conserve — categories sum to wall clock within 1% — and (b) have
    # charged the injected fault's cost to the RIGHT badput category,
    # not diffused it into idle
    job = stats.get("goodput") or {}
    cats = job.get("categories") or {}
    verdict["goodput"] = {
        "wall_ms": round(job.get("wall_ms", 0.0), 1),
        "goodput_frac": round(job.get("goodput_frac", 0.0), 4),
        "categories": {c: round(m, 1) for c, m in cats.items() if m > 0},
    }
    badput = {c: m for c, m in cats.items()
              if c not in ("compute", "input_wait", "host_sync")
              and m > 0}
    verdict["goodput_attr"] = (
        "%s:%.0fms" % max(badput.items(), key=lambda cm: cm[1])
        if badput else "clean")
    wall = job.get("wall_ms", 0.0)
    if not cats:
        problems.append("the supervisor recorded no job goodput ledger")
    elif wall > 0:
        err = abs(sum(cats.values()) - wall) / wall
        if err > 0.01:
            problems.append(
                "job ledger does not conserve: categories sum to "
                "%.1fms over %.1fms wall (err %.2f%%)"
                % (sum(cats.values()), wall, 100.0 * err))
    if verdict["restarts"] > 0 and not cats.get("restart_downtime"):
        problems.append("gang restarted %d time(s) but the job ledger "
                        "charged no restart_downtime"
                        % verdict["restarts"])
    if args.preempt and not cats.get("preempt_drain"):
        problems.append("preemption gate but the job ledger charged "
                        "no preempt_drain")
    if (args.shrink or args.sdc) and stats.get("shrinks", 0) > 0 \
            and not cats.get("shrink_rejit"):
        problems.append("the gang shrank but the job ledger charged "
                        "no shrink_rejit")
    if args.check_parity and not problems:
        import numpy as np

        for r, got in ranks.items():
            want = train_losses(args.steps,
                                os.path.join(workdir, "ref%d" % r), rank=r)
            # the supervisor's in-process reference runs single-device /
            # no-mesh: under --mesh the workers' psum reduction order
            # differs from the one-device sum, so parity is allclose
            # there and bit-exact otherwise
            ok = (np.allclose(got, want, rtol=1e-5, atol=1e-7)
                  if args.mesh else got == want)
            if not ok:
                diff = next(i for i, (a, b) in enumerate(zip(got, want))
                            if a != b)
                problems.append(
                    "rank %d diverged from the fault-free run at step %d"
                    % (r, diff))
    verdict["ok"] = not problems
    if problems:
        verdict["problems"] = problems
    print(json.dumps(verdict))
    return 0 if not problems else 1


def run_serve_retry(args):
    """Serving-fleet worker-kill-mid-flight gate (--serve-retry).

    Two in-process ``InferenceServer`` workers over ONE frozen program
    behind a ``FleetRouter`` with the full protection envelope (bounded
    retries, a hedge timer, per-worker circuit breakers) and request
    tracing at sample rate 1.0. The gate injects faults into worker 0's
    device-dispatch seam (``_run_padded``) and asserts the router's
    graceful-degradation story end to end:

    * hedge — worker 0 made a 0.5s straggler: the hedge timer re-issues
      on worker 1, the hedge wins, the client still gets the correct
      answer, and the cancelled straggler must NOT poison worker 0's
      batcher (the collect loop drops claimed-dead futures);
    * retry — worker 0 killed mid-flight: every routed request still
      resolves with the bit-correct result via worker 1; the failed and
      relaunched attempts share ONE trace id, and the stitched trace
      shows route spans on BOTH workers plus the ``trace.retry``
      hand-off span; two consecutive failures trip worker 0's breaker;
    * recover — fault cleared: after the breaker cooldown a half-open
      probe routes one real request to worker 0, its success closes the
      breaker, and worker 0 serves traffic again.

    Prints the machine verdict as the last stdout line.
    """
    import time

    import numpy as np

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import trace_query
    from serve_probe import build_server

    import paddle_tpu.fluid as fluid
    from paddle_tpu import flags
    from paddle_tpu import observability as obs
    from paddle_tpu.inference import InferenceServer
    from paddle_tpu.resilience.elastic import FleetRouter

    workdir = args.workdir or tempfile.mkdtemp(prefix="serve_retry_")
    os.makedirs(workdir, exist_ok=True)
    sink = os.path.join(workdir, "events.jsonl")
    problems = []
    obs.set_enabled(True)
    obs.reset()
    flags.set_flags({"metrics": True, "trace_sample": 1.0,
                     "trace_buffer": 16384})
    obs.attach_sink(sink)
    try:
        s0, one_row, _ = build_server(
            "mlp", int8=False, buckets="1,2", max_wait_ms=5.0,
            seed=args.seed)
        # the second worker wraps the SAME frozen program + scope: both
        # workers are bit-identical replicas, so "the survivor answered
        # correctly" is checkable against one executor reference
        s1 = InferenceServer(s0.program, s0.feed_names, s0.fetch_names,
                             scope=s0.scope, executor=s0._exe,
                             buckets=(1, 2), max_wait_ms=5.0,
                             name="probe-1")

        # fault seam on worker 0's device dispatch
        state = {"fail": False, "slow_s": 0.0, "served": 0, "fails": 0}
        orig_run = s0._run_padded

        def poisoned(feed, bucket):
            if state["slow_s"]:
                time.sleep(state["slow_s"])
            if state["fail"]:
                state["fails"] += 1
                raise RuntimeError("injected device loss (chaos)")
            out = orig_run(feed, bucket)
            state["served"] += 1
            return out

        s0._run_padded = poisoned

        rng = np.random.RandomState(args.seed)
        feeds = [{"img": rng.randn(1, 784).astype(np.float32)}
                 for _ in range(40)]
        with fluid.scope_guard(s0.scope):
            expected = [np.asarray(s0._exe.run(
                s0.program, feed=f,
                fetch_list=list(s0.fetch_names))[0]) for f in feeds]

        router = FleetRouter(lambda idx: (s0, s1)[idx], min_workers=2,
                             max_workers=2, cooldown_s=3600.0,
                             retries=2, hedge_after_ms=150.0,
                             breaker_failures=2, breaker_reset_s=1.0)
        router.start()
        try:
            for srv in (s0, s1):
                srv.warmup(feeds[0])

            def drain(lo, hi, phase):
                futs = [(i, router.submit(feeds[i]))
                        for i in range(lo, hi)]
                tids = []
                for i, f in futs:
                    try:
                        got = f.result(timeout=60)
                    except Exception as e:  # noqa: BLE001
                        problems.append("%s: request %d failed: %r"
                                        % (phase, i, e))
                        continue
                    tids.append(getattr(f, "trace_id", None))
                    if not np.allclose(np.asarray(got[0]), expected[i],
                                       rtol=1e-5, atol=1e-5):
                        problems.append("%s: request %d answered "
                                        "incorrectly" % (phase, i))
                return tids

            # -- phase 0: healthy fleet baseline
            drain(0, 6, "healthy")

            # -- phase 1: straggler -> hedge wins, answer still right
            state["slow_s"] = 0.5
            drain(6, 12, "hedge")
            state["slow_s"] = 0.0
            if router.hedge_wins < 1:
                problems.append("0.5s straggler never lost to a hedge "
                                "(hedges=%d wins=%d)"
                                % (router.hedges, router.hedge_wins))
            time.sleep(0.8)     # let worker 0 drain cancelled losers
            if not s0.alive():
                problems.append("worker 0's dispatch loop died on a "
                                "cancelled hedge loser")

            # -- phase 2: kill worker 0 mid-flight -> retries + breaker
            state["fail"] = True
            retries_before = router.retries
            kill_tids = drain(12, 26, "kill")
            stats = router.stats()
            if router.retries <= retries_before:
                problems.append("worker kill produced no retries")
            if stats["breaker_trips"] < 1:
                problems.append("repeated failures never tripped the "
                                "breaker: %s" % stats)
            if stats["breakers_open"] < 1:
                problems.append("breaker not open right after the kill "
                                "phase: %s" % stats)
            served_sick = state["served"]

            # -- phase 3: clear the fault -> half-open probe recovers
            state["fail"] = False
            time.sleep(1.2)     # past breaker_reset_s
            drain(26, 40, "recover")
            stats = router.stats()
            if stats["breakers_open"] != 0:
                problems.append("breaker still open after recovery: %s"
                                % stats)
            if state["served"] <= served_sick:
                problems.append("worker 0 never served again after the "
                                "fault cleared")
            fleet = {"retries": router.retries, "hedges": router.hedges,
                     "hedge_wins": router.hedge_wins,
                     "breaker_trips": stats["breaker_trips"],
                     "worker0_served": state["served"],
                     "worker0_fails": state["fails"]}
        finally:
            router.stop()
    finally:
        obs.detach_sink()
        for name in ("trace_sample", "trace_buffer", "metrics"):
            flags.reset_flag(name)
        obs.set_enabled(None)
        obs.reset()

    # -- stitched-trace audit: the retried request is ONE trace showing
    # the failed attempt, the hand-off, and the serving attempt
    traces, _, _ = trace_query.load([sink])
    retry_traces = {tid: evs for tid, evs in traces.items()
                    if any(ev["name"] == "trace.retry" for ev in evs)}
    stitched = 0
    for tid, evs in retry_traces.items():
        workers = {ev["args"].get("worker") for ev in evs
                   if ev["name"] == "trace.route"}
        errored = any(ev["name"] == "trace.request"
                      and ev["args"].get("error") for ev in evs)
        served = any(ev["name"] == "trace.request"
                     and not ev["args"].get("error") for ev in evs)
        if len(workers) >= 2 and errored and served:
            stitched += 1
    if not retry_traces:
        problems.append("no trace carries a trace.retry span")
    elif stitched == 0:
        problems.append("retry traces exist but none stitches both "
                        "attempts (route spans on 2 workers + errored "
                        "and served request spans) under one id")
    if kill_tids and not (set(retry_traces) & set(kill_tids)):
        problems.append("retry spans landed outside the kill-phase "
                        "trace ids")

    verdict = {
        "gate": "serve_retry",
        "fleet": fleet,
        "traces": {"total": len(traces), "retry": len(retry_traces),
                   "stitched": stitched},
        "sink": sink,
        "ok": not problems,
    }
    if problems:
        verdict["problems"] = problems
    print(json.dumps(verdict))
    return 0 if not problems else 1


def main():
    parser = argparse.ArgumentParser("chaos_run")
    parser.add_argument("--worker", action="store_true",
                        help="internal: run as one supervised worker")
    parser.add_argument("--steps", type=int, default=30)
    parser.add_argument("--nproc", type=int, default=2)
    parser.add_argument("--seed", type=int, default=0,
                        help="fault-schedule seed (same seed, same chaos)")
    parser.add_argument("--spec", default=None,
                        help="explicit fault spec; overrides --seed")
    parser.add_argument("--max-restarts", type=int, default=None,
                        help="default: worker kills/hangs in the spec + 1")
    parser.add_argument("--shrink", action="store_true",
                        help="inject a PERMANENT worker loss (rc 45) on "
                             "the highest rank mid-run: the supervisor "
                             "must shrink the gang and the survivors "
                             "must finish every step with fault-free "
                             "parity")
    parser.add_argument("--max-shrinks", type=int, default=None,
                        help="elastic shrink budget for the supervisor "
                             "(default: worker_loss entries in the spec, "
                             "+1 under --sdc)")
    parser.add_argument("--sdc", action="store_true",
                        help="silent-data-corruption gate: workers run "
                             "with PADDLE_TPU_SDC=1; a transient bitflip "
                             "on rank 0 must be replay-absorbed and a "
                             "persistent one on the highest rank must be "
                             "blamed, quarantined via gang shrink, and "
                             "the survivors must keep bit-exact "
                             "fault-free parity")
    parser.add_argument("--preempt", action="store_true",
                        help="graceful-preemption gate: rank 0 is "
                             "SIGTERM-evicted mid-run, must drain + "
                             "checkpoint + exit rc 46, and the "
                             "supervisor must restart without spending "
                             "restart budget")
    parser.add_argument("--ckpt-replicas", type=int, default=0,
                        help="mirror each rank's checkpoint shards into "
                             "this many PEER ranks' roots (quorum "
                             "restore coverage; pairs with a disk_fail "
                             "spec entry)")
    parser.add_argument("--trace", action="store_true",
                        help="cross-process tracing gate: the "
                             "supervisor opens one job trace, every "
                             "incarnation adopts it via "
                             "PADDLE_TPU_TRACE_ID, and the verdict "
                             "asserts one stitched trace spanning both "
                             "incarnations of a killed worker with the "
                             "supervisor's restart span between")
    parser.add_argument("--hang", action="store_true",
                        help="seeded spec injects worker_hang instead of "
                             "worker_kill — exercises the heartbeat "
                             "watchdog rather than the exit-code path")
    parser.add_argument("--heartbeat-ms", type=float, default=200.0,
                        help="worker heartbeat interval under supervise")
    parser.add_argument("--hang-timeout", type=float, default=15.0,
                        help="seconds of step-counter stall before the "
                             "supervisor declares a rank hung (must "
                             "comfortably exceed worker startup + first "
                             "XLA compile, which the stall clock ticks "
                             "through)")
    parser.add_argument("--workdir", default=None,
                        help="default: fresh temp dir, kept for forensics")
    parser.add_argument("--result-dir", default=None)
    parser.add_argument("--started_port", type=int, default=6280)
    parser.add_argument("--dispatch-steps", type=int, default=1,
                        help="workers enqueue this many steps into the "
                             "engine's async dispatch window "
                             "(engine/pipeline.py) — injected faults "
                             "land mid-window and must still restore "
                             "to bit-exact parity with the synchronous "
                             "fault-free reference")
    parser.add_argument("--mesh", action="store_true",
                        help="workers train through the dp-mesh GSPMD "
                             "path (2 virtual devices each) — proves the "
                             "mesh data-parallel path survives "
                             "worker_kill under the gang supervisor")
    parser.add_argument("--zero1", action="store_true",
                        help="run everything with PADDLE_TPU_ZERO=1 and "
                             "a Momentum probe optimizer: the workers' "
                             "dp-mesh update is ZeRO-1 sharded (velocity "
                             "slots partitioned, params all-gathered "
                             "after the shard update) and every "
                             "recovery path — restart, shrink, replay — "
                             "must keep fault-free parity with the "
                             "sharded state migrating across meshes")
    parser.add_argument("--serve-retry", action="store_true",
                        help="run the in-process serving-fleet gate "
                             "instead of the training gang: kill a "
                             "fleet worker mid-flight and assert hedged "
                             "retries answer correctly under one "
                             "stitched trace, the sick worker's breaker "
                             "trips, and a half-open probe recovers it")
    parser.add_argument("--check-parity", action="store_true",
                        default=True)
    parser.add_argument("--no-check-parity", dest="check_parity",
                        action="store_false")
    args = parser.parse_args()
    if args.zero1:
        # in os.environ (not just env_extra) so the supervisor's OWN
        # in-process parity reference builds the Momentum probe; the
        # zero flag itself is inert there (no mesh)
        os.environ["PADDLE_TPU_ZERO"] = "1"
    if args.worker:
        return run_worker(args)
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=1")
    import jax

    jax.config.update("jax_platforms", "cpu")
    if args.serve_retry:
        return run_serve_retry(args)
    return run_supervisor(args)


if __name__ == "__main__":
    sys.exit(main())
