"""Chaos matrix: every chaos acceptance gate in one command.

Runs each gate script (``tools/chaos_run.py`` for the training gang,
``tools/serve_probe.py`` for the serving fleet) as its own subprocess
(distinct rendezvous ports, distinct workdirs), parses the one-line
JSON verdict each gate prints, and renders a pass/fail table. Exit
code 0 iff every gate passed — this is the single entry point CI (or a
reviewer) runs to prove the whole failure-domain story at once:

    gate      injected fault                   proven recovery path
    -------   ------------------------------   -------------------------
    base      worker kill + step NaN           respawn + rollback/replay
    hang      wedged worker (no heartbeat)     watchdog detect + restart
    shrink    permanent rank loss mid-window   gang shrink, survivors
              (async dispatch depth 4)         finish with parity
    quorum    dead checkpoint disk + kill      restore from peer replica
    sdc       silent bitflips (transient +     digest detect, replay
              persistent)                      vote, blame, quarantine
    preempt   SIGTERM eviction                 drain + checkpoint + free
                                               restart (no budget spent)
    zero1     permanent rank loss with the     mesh shrink reshards the
              ZeRO-1 sharded Momentum update   partitioned velocity
              on the dp mesh (PADDLE_TPU_ZERO) slots; survivors keep
                                               fault-free parity
    overload  4x sustained serving overload    admission control sheds;
                                               queue stays bounded,
                                               every future resolves,
                                               admitted p99 holds SLO
    hedge     serving-fleet worker killed      hedged retry answers via
              mid-flight (+ a 0.5s straggler)  the survivor under ONE
                                               stitched trace; breaker
                                               trips, half-open recovers

Usage::

    python tools/chaos_matrix.py                  # all gates (~minutes)
    python tools/chaos_matrix.py --only sdc,hang  # a subset
    python tools/chaos_matrix.py --steps 20       # shorter runs

Every gate asserts bit-exact (or, under --mesh paths, allclose) loss
parity against a fault-free reference on top of its own recovery-path
assertions — see chaos_run.py for what each flag checks. Every gate
also asserts the supervisor's goodput job ledger conserves (categories
sum to wall within 1%) and charged the injected fault's wall cost to
the right badput category (kill -> restart_downtime, preempt ->
preempt_drain, shrink -> shrink_rejit); the table's ``badput=`` detail
shows the attribution.
"""

import argparse
import json
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
CHAOS_RUN = os.path.join(HERE, "chaos_run.py")
SERVE_PROBE = os.path.join(HERE, "serve_probe.py")

# (name, gate script, extra argv). Ports are assigned below, spaced so
# a lingering listener from one gate can never collide with the next;
# serve_probe gates are in-process (no rendezvous) and get only --seed.
GATES = [
    ("base", CHAOS_RUN, []),
    ("hang", CHAOS_RUN, ["--hang"]),
    # depth 4: the permanent loss lands MID async dispatch window, so
    # the in-flight deferred steps must retire/invalidate cleanly
    # before the survivors replay (the gang-level half of the live
    # shrink coverage; tests/test_elastic.py has the in-process half)
    ("shrink", CHAOS_RUN, ["--shrink", "--dispatch-steps", "4"]),
    ("quorum", CHAOS_RUN, ["--ckpt-replicas", "1", "--spec",
                           "disk_fail@rank0:step12;"
                           "worker_kill@rank0:step14"]),
    ("sdc", CHAOS_RUN, ["--sdc"]),
    ("preempt", CHAOS_RUN, ["--preempt"]),
    # the ZeRO-1 sharded weight update on the dp mesh: the permanent
    # rank loss shrinks the workers' mesh while the Momentum velocity
    # slots live dp-sharded — the reshard-on-shrink seam must migrate
    # the partitioned optimizer state and keep fault-free parity
    # (tests/test_elastic.py has the in-process half of this coverage)
    ("zero1", CHAOS_RUN, ["--shrink", "--mesh", "--zero1"]),
    # the serving-side failure domain (paddle_tpu/inference/admission):
    # sustained 4x overload against the armed admission stack — queue
    # bounded, served/rejected/expired conserve exactly, admitted p99
    # holds the SLO
    ("overload", SERVE_PROBE, ["--overload", "--duration", "2"]),
    # worker killed mid-flight behind the FleetRouter: hedged retries
    # answer correctly via the survivor under one stitched trace, the
    # sick worker's breaker trips, and a half-open probe recovers it
    ("hedge", CHAOS_RUN, ["--serve-retry"]),
]


def run_gate(name, script, extra, args, port):
    if script == SERVE_PROBE:
        cmd = [sys.executable, script, "--seed", str(args.seed)] + extra
    else:
        cmd = [sys.executable, script, "--steps", str(args.steps),
               "--nproc", str(args.nproc), "--seed", str(args.seed),
               "--started_port", str(port)] + extra
    t0 = time.monotonic()
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True,
                              timeout=args.timeout)
        rc, out = proc.returncode, proc.stdout
        tail = proc.stderr.strip().splitlines()[-1:] if rc else []
    except subprocess.TimeoutExpired:
        rc, out, tail = -1, "", ["timeout after %ds" % args.timeout]
    wall = time.monotonic() - t0
    # the verdict is the LAST stdout line that parses as a JSON object
    verdict = None
    for line in reversed(out.strip().splitlines()):
        try:
            cand = json.loads(line)
        except ValueError:
            continue
        if isinstance(cand, dict) and "ok" in cand:
            verdict = cand
            break
    ok = rc == 0 and verdict is not None and verdict.get("ok") is True
    return {"gate": name, "ok": ok, "rc": rc, "wall_s": round(wall, 1),
            "verdict": verdict, "note": "; ".join(tail)}


def main():
    parser = argparse.ArgumentParser("chaos_matrix")
    parser.add_argument("--only", default=None,
                        help="comma-separated gate names to run "
                             "(default: all of %s)"
                        % ",".join(n for n, _, _ in GATES))
    parser.add_argument("--steps", type=int, default=30)
    parser.add_argument("--nproc", type=int, default=2)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--timeout", type=int, default=600,
                        help="per-gate wall-clock budget in seconds")
    parser.add_argument("--started_port", type=int, default=6400,
                        help="first rendezvous port; each gate gets its "
                             "own +16 block")
    args = parser.parse_args()

    want = None
    if args.only:
        want = {n.strip() for n in args.only.split(",") if n.strip()}
        unknown = want - {n for n, _, _ in GATES}
        if unknown:
            parser.error("unknown gate(s): %s" % ", ".join(sorted(unknown)))

    rows = []
    for i, (name, script, extra) in enumerate(GATES):
        if want is not None and name not in want:
            continue
        port = args.started_port + 16 * i
        print("chaos_matrix: running %-8s ..." % name, flush=True)
        rows.append(run_gate(name, script, extra, args, port))
        row = rows[-1]
        print("chaos_matrix: %-8s %s in %.1fs"
              % (name, "PASS" if row["ok"] else "FAIL", row["wall_s"]),
              flush=True)

    width = max(len(r["gate"]) for r in rows) if rows else 4
    print()
    print("%-*s  %-4s  %6s  %s" % (width, "gate", "ok", "wall", "detail"))
    print("%s  %s  %s  %s" % ("-" * width, "-" * 4, "-" * 6, "-" * 40))
    for r in rows:
        v = r["verdict"] or {}
        if r["ok"]:
            if v.get("fleet"):          # the serving hedge/retry gate
                f = v["fleet"]
                detail = ("retries=%s hedge_wins=%s trips=%s stitched=%s"
                          % (f.get("retries"), f.get("hedge_wins"),
                             f.get("breaker_trips"),
                             (v.get("traces") or {}).get("stitched")))
            elif v.get("overload"):     # the serving overload gate
                o = v["overload"]
                turned = (sum((o.get("rejected") or {}).values())
                          + o.get("shed_evicted", 0)
                          + o.get("expired", 0))
                detail = ("served=%s turned_away=%s depth_max=%s "
                          "p99=%sms" % (o.get("served"), turned,
                                        o.get("depth_max"),
                                        o.get("served_p99_ms")))
            else:
                detail = ",".join(v.get("sentinel_events")
                                  or v.get("recovery_events") or [])[:60]
            if v.get("goodput_attr"):
                # where the injected fault's wall cost landed (asserted
                # per-gate in chaos_run.py — this column is the summary)
                detail += "  badput=%s" % v["goodput_attr"]
        else:
            detail = "; ".join(v.get("problems", [])) or r["note"] \
                or "rc %s, no verdict" % r["rc"]
        print("%-*s  %-4s  %5.1fs  %s"
              % (width, r["gate"], "PASS" if r["ok"] else "FAIL",
                 r["wall_s"], detail[:100]))
    n_fail = sum(1 for r in rows if not r["ok"])
    print("\nchaos_matrix: %d/%d gates passed"
          % (len(rows) - n_fail, len(rows)))
    return 1 if n_fail else 0


if __name__ == "__main__":
    sys.exit(main())
