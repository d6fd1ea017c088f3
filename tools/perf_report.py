#!/usr/bin/env python
"""Merge a host-span dump with xplane device aggregates into one
per-step perf report — or merge a directory of per-worker JSONL
telemetry dumps into one cross-host report.

Single-host mode: the host side comes from
``observability.dump_chrome_trace(path)`` (or the
``<profile_path>.trace.json`` stop_profiler writes): every engine step
is a "step" slice with its trace/transform/lower/compile/run children.
The device side comes from the jax profiler's xplane dump, aggregated
per op by ``observability.opprof.top_ops``. Together they answer the question
the throughput number alone cannot: where did each step's wall time go
— host build (trace/transform/lower), XLA compile, dispatch, or device
kernels.

Multi-host mode (``--merge DIR``): DIR holds the host-tagged JSONL
sinks each worker streamed (``PADDLE_TPU_METRICS_SINK`` +
distributed/launch.py's per-rank tagging — ``<base>.h<rank>.jsonl``
plus rotations). The merge joins them on step number into the table a
pod run is debugged from: per-step latency skew across workers,
slowest-worker attribution, per-worker heartbeat ages (which rank went
quiet or stalled first), and each worker's aggregate HBM watermarks.

Usage:
    python tools/perf_report.py HOST_TRACE.json [XPLANE_DIR] [--top N]
    python tools/perf_report.py --merge DUMP_DIR

With no XPLANE_DIR the report is host-only (the device planes are read
through ``jax.profiler.ProfileData``).
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.abspath(
    os.path.join(os.path.dirname(__file__), os.pardir)))

# The per-step breakdown columns, in pipeline order. "other" is the
# step-slice remainder not covered by any of them.
PHASES = ("trace", "transform", "lower", "compile", "run")


def load_host_events(path):
    with open(path) as f:
        trace = json.load(f)
    return [e for e in trace.get("traceEvents", [])
            if e.get("ph") == "X"]


def per_step_rows(events):
    """Group host slices into steps: each "step" slice owns every slice
    nested inside its [ts, ts+dur) window on the same pid/tid."""
    steps = sorted((e for e in events if e["name"] == "step"),
                   key=lambda e: e["ts"])
    rows = []
    for i, st in enumerate(steps):
        t0, t1 = st["ts"], st["ts"] + st.get("dur", 0.0)
        row = {"step": st.get("args", {}).get("step", i + 1),
               "total_ms": st.get("dur", 0.0) / 1e3}
        for ph in PHASES:
            row[ph] = 0.0
        for e in events:
            if e is st or e.get("pid") != st.get("pid") \
                    or e.get("tid") != st.get("tid"):
                continue
            if e["name"] in PHASES and t0 <= e["ts"] < t1:
                row[e["name"]] += e.get("dur", 0.0) / 1e3
        row["other"] = max(0.0, row["total_ms"] - sum(
            row[ph] for ph in PHASES))
        rows.append(row)
    return rows


def render_host(rows):
    lines = ["== host: per-step wall (ms) =="]
    hdr = ("step", "total") + PHASES + ("other",)
    lines.append("  ".join("%9s" % h for h in hdr))
    for r in rows:
        lines.append("  ".join(
            ["%9s" % r["step"], "%9.2f" % r["total_ms"]]
            + ["%9.2f" % r[ph] for ph in PHASES]
            + ["%9.2f" % r["other"]]))
    if not rows:
        lines.append("(no step spans in the host dump — was "
                     "PADDLE_TPU_METRICS up?)")
    return "\n".join(lines)


def render_device(xplane_dir, top_n):
    from paddle_tpu.observability.opprof import top_ops

    rows, total = top_ops(xplane_dir, top_n=top_n)
    lines = ["", "== device: XLA-op time (total %.2f ms) ==" % total]
    for name, ms in rows:
        pct = (ms / total * 100) if total else 0.0
        lines.append("%10.3f ms  %5.1f%%  %s" % (ms, pct, name[:80]))
    return "\n".join(lines)


def render_roofline(table, top_n):
    """The per-op roofline table from an attribution result: top-k by
    device time with %-of-step, arithmetic intensity (FLOPs/byte), the
    compute/memory/comm-bound verdict, and the source-op list fused ops
    expand to."""
    from paddle_tpu.observability import opprof

    lines = [
        "== roofline: device time by framework op "
        "(source %s, fusion policy %s) =="
        % (table["source"], table["fusion_policy"]),
        "%-36s %10s %6s %10s %-13s %s"
        % ("op", "ms", "%", "FLOP/B", "verdict", "src_ops")]
    shown = 0
    for tag, row in opprof.top_rows(table, top_n):
        if row["ms"] <= 0:
            continue
        shown += 1
        lines.append(
            "%-36s %10.3f %5.1f%% %10.2f %-13s %s"
            % (tag[:36], row["ms"], 100.0 * row["frac"],
               row["intensity"], row["verdict"],
               ",".join(row["src_ops"])[:40]))
    if not shown:
        lines.append("(no device time attributed to any provenance tag "
                     "— was the trace taken with PADDLE_TPU_OPPROF on?)")
    zero = [t for t, r in table["ops"].items() if r["ms"] <= 0]
    if zero:
        lines.append("(+%d op(s) at 0 ms: fused away or constant-folded "
                     "— e.g. %s)" % (len(zero), ", ".join(zero[:4])))
    lines.append(
        "attributed %.1f%% of %.3f ms device time "
        "(unattributed %.3f ms, comm lane %.3f ms, %d/%d collective "
        "instruction(s) vs registered schedule)"
        % (100.0 * table["attributed_frac"], table["total_ms"],
           table["unattributed_ms"], table["comm_ms"],
           table["collective_instances"],
           table["expected_collective_instances"]))
    if table["source"] != "tpu":
        lines.append("NOTE: CPU-plane attribution is coarse (durations "
                     "include host dispatch) — verdicts are "
                     "hardware-trustworthy on TPU traces only")
    return "\n".join(lines)


def roofline_report(xplane_dir, top_n=15, gate=False):
    """-> (text, rc). Attribute the trace dir's device time per
    provenance tag (using the opprof_provenance.json sidecar
    stop_profiler wrote next to the xplane dumps) and render the
    roofline table. With ``gate`` the rc is nonzero when the table is
    empty or the collective lane disagrees with the registered HLO
    schedule — wire into the bench flow the way multichip_probe
    --predict is."""
    from paddle_tpu.observability import opprof

    try:
        table = opprof.attribute(xplane_dir)
    except Exception as e:
        text = "roofline: attribution failed: %s" % e
        return text, (1 if gate else 0)
    text = render_roofline(table, top_n)
    rc = 0
    if gate:
        issues = opprof.gate_issues(table)
        for issue in issues:
            text += "\nGATE: %s" % issue
        rc = 1 if issues else 0
        if not issues:
            text += "\nroofline gate: PASS"
    return text, rc


# -- multi-host merge ------------------------------------------------------

# The HBM watermark gauges a "snap" event carries, in report order.
HBM_GAUGES = ("hbm.live_bytes_peak", "hbm.compile_peak_bytes",
              "hbm.device_peak_bytes_in_use")


def load_worker_dumps(dump_dir):
    """Parse every JSONL sink file under ``dump_dir`` (live + rotated),
    grouped by the host id each event carries:
    ``{host: {"steps": {step: dur_ms}, "hbm": {gauge: max_bytes},
    "hb": {count, last_ts, last_step, step_ts}, "files": [...],
    "events": n, "last_ts": newest_event_us}}``. The ``hb`` record
    tracks the newest ``health.heartbeat`` per worker so the merged
    report can show which rank went quiet (or stalled) first."""
    from paddle_tpu.observability.export import iter_events, sink_file_set
    from paddle_tpu.observability.health import HEARTBEAT_EVENT

    workers = {}

    def w(host):
        return workers.setdefault(
            host, {"steps": {}, "hbm": {}, "goodput": {}, "opprof": {},
                   "exemplars": {}, "job": None,
                   "hb": {"count": 0, "last_ts": None, "last_step": None,
                          "step_ts": None},
                   "files": set(), "events": 0, "last_ts": None})

    for path in sink_file_set(dump_dir):
        for ev in iter_events(path):
            host = ev.get("host", 0)
            rec = w(host)
            rec["files"].add(os.path.basename(path))
            rec["events"] += 1
            ts = ev.get("ts")
            if ts is not None:
                rec["last_ts"] = ts if rec["last_ts"] is None \
                    else max(rec["last_ts"], ts)
            kind = ev.get("t")
            if kind == "span" and ev.get("name") == "step":
                step = (ev.get("args") or {}).get("step")
                if step is not None:
                    # keep the LAST duration per step number (restarted
                    # counters: later wins, matching the file order)
                    rec["steps"][int(step)] = ev.get("dur", 0.0) / 1e3
            elif kind == "span" and ev.get("name") == HEARTBEAT_EVENT:
                hb = rec["hb"]
                hb["count"] += 1
                if ts is not None and (hb["last_ts"] is None
                                       or ts >= hb["last_ts"]):
                    hb["last_ts"] = ts
                    step = (ev.get("args") or {}).get("step")
                    if step is not None and step != hb["last_step"]:
                        hb["last_step"] = step
                        hb["step_ts"] = ts
            elif kind == "span" and ev.get("name") == "goodput.job":
                # the supervisor's job-ledger event (one per job exit);
                # later wins, matching file order
                rec["job"] = ev.get("args") or {}
            elif kind == "snap":
                gauges = (ev.get("metrics") or {}).get("gauges") or {}
                for g in HBM_GAUGES:
                    v = gauges.get(g)
                    if v is not None:
                        rec["hbm"][g] = max(rec["hbm"].get(g, 0), int(v))
                for g, v in gauges.items():
                    # goodput/mfu gauges are running totals, not
                    # watermarks: keep the NEWEST value per host
                    if g.startswith("goodput.") or g.startswith("mfu."):
                        rec["goodput"][g] = v
                    elif g.startswith("opprof."):
                        # per-op device-time gauges stop_profiler set —
                        # newest wins (they summarize the whole session)
                        rec["opprof"][g] = v
                ex = (ev.get("metrics") or {}).get("exemplars") or {}
                # exemplar slots pin the trace id of the worst request
                # behind each latency series — newest snapshot wins
                rec["exemplars"].update(ex)
    for rec in workers.values():
        rec["files"] = sorted(rec["files"])
    return workers


def _fmt_bytes(n):
    if n is None:
        return "-"
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if abs(n) < 1024 or unit == "TiB":
            return ("%.1f %s" % (n, unit)) if unit != "B" \
                else ("%d B" % n)
        n /= 1024.0
    return "%d" % n


def render_merge(workers):
    """The cross-host report: step-skew table, slowest-worker
    attribution, worker heartbeat health, aggregate HBM watermarks."""
    hosts = sorted(workers)
    lines = ["== cross-host: per-step wall (ms) across %d worker(s) =="
             % len(hosts)]
    if not hosts:
        lines.append("(no worker dumps found — were sinks attached via "
                     "PADDLE_TPU_METRICS_SINK?)")
        return "\n".join(lines)
    all_steps = sorted({s for h in hosts for s in workers[h]["steps"]})
    hdr = ["step"] + ["h%s" % h for h in hosts] + ["skew", "slowest"]
    lines.append("  ".join("%9s" % c for c in hdr))
    slowest_count = dict.fromkeys(hosts, 0)
    for step in all_steps:
        durs = {h: workers[h]["steps"].get(step) for h in hosts}
        present = {h: d for h, d in durs.items() if d is not None}
        row = ["%9d" % step]
        for h in hosts:
            row.append("%9.2f" % durs[h] if durs[h] is not None
                       else "%9s" % "-")
        if present:
            skew = max(present.values()) - min(present.values())
            slow = max(present, key=present.get)
            slowest_count[slow] += 1
            row += ["%9.2f" % skew, "%9s" % ("h%s" % slow)]
        else:
            row += ["%9s" % "-", "%9s" % "-"]
        lines.append("  ".join(row))
    if all_steps:
        joined = [s for s in all_steps
                  if all(s in workers[h]["steps"] for h in hosts)]
        if joined:
            skews = [max(workers[h]["steps"][s] for h in hosts)
                     - min(workers[h]["steps"][s] for h in hosts)
                     for s in joined]
            lines.append(
                "steps joined across all workers: %d  mean skew: %.2f ms"
                "  max skew: %.2f ms"
                % (len(joined), sum(skews) / len(skews), max(skews)))
        attribution = ", ".join(
            "h%s %d/%d" % (h, slowest_count[h], len(all_steps))
            for h in hosts if slowest_count[h])
        if attribution:
            lines.append("slowest-worker attribution: " + attribution)
    if any(workers[h]["hb"]["count"] for h in hosts):
        # heartbeat ages are measured against the FLEET's newest event:
        # in a post-mortem dump "now" is whenever the job died, and the
        # rank whose age stands out is the one that went quiet first
        fleet_end = max(workers[h]["last_ts"] for h in hosts
                        if workers[h]["last_ts"] is not None)
        lines.append("")
        lines.append("== worker health (heartbeat ages vs fleet end) ==")
        hdr = ("host", "beats", "last_step", "hb_age_s", "stalled_s")
        lines.append("  ".join("%10s" % c for c in hdr))
        for h in hosts:
            hb = workers[h]["hb"]
            age = (fleet_end - hb["last_ts"]) / 1e6 \
                if hb["last_ts"] is not None else None
            stalled = (hb["last_ts"] - hb["step_ts"]) / 1e6 \
                if hb["last_ts"] is not None and hb["step_ts"] is not None \
                else None
            lines.append("  ".join([
                "%10s" % ("h%s" % h),
                "%10d" % hb["count"],
                "%10s" % (hb["last_step"]
                          if hb["last_step"] is not None else "-"),
                "%10s" % ("%.1f" % age if age is not None else "-"),
                "%10s" % ("%.1f" % stalled
                          if stalled is not None else "-")]))
    lines.append("")
    lines.append("== aggregate HBM watermarks ==")
    short = {g: g[len("hbm."):] for g in HBM_GAUGES}
    hdr = ["host"] + [short[g] for g in HBM_GAUGES] + ["events", "files"]
    lines.append("  ".join("%24s" % c if i else "%6s" % c
                           for i, c in enumerate(hdr)))
    fleet = {}
    for h in hosts:
        rec = workers[h]
        row = ["%6s" % ("h%s" % h)]
        for g in HBM_GAUGES:
            v = rec["hbm"].get(g)
            if v is not None:
                fleet[g] = max(fleet.get(g, 0), v)
            row.append("%24s" % _fmt_bytes(v))
        row.append("%24d" % rec["events"])
        row.append("  " + ",".join(rec["files"]))
        lines.append("  ".join(row))
    if fleet:
        lines.append("fleet max: " + "  ".join(
            "%s=%s" % (short[g], _fmt_bytes(fleet[g]))
            for g in HBM_GAUGES if g in fleet))
    hot = render_fleet_hot_ops(workers)
    if hot:
        lines.append("")
        lines.append(hot)
    ex = render_exemplars(workers)
    if ex:
        lines.append("")
        lines.append(ex)
    return "\n".join(lines)


def render_exemplars(workers):
    """The metric→trace exemplar table: for each host that streamed
    exemplar slots in its metric snapshots, the offending request's
    trace id and the value it pinned — the lookup key for
    ``tools/trace_query.py --trace ID``. Returns "" when no worker
    carried exemplars."""
    hosts = [h for h in sorted(workers) if workers[h]["exemplars"]]
    if not hosts:
        return ""
    lines = ["== metric exemplars (worst request per series — "
             "tools/trace_query.py --trace ID) =="]
    hdr = ("host", "metric", "value", "trace")
    lines.append("  ".join(["%6s" % hdr[0], "%-28s" % hdr[1],
                            "%12s" % hdr[2], hdr[3]]))
    for h in hosts:
        for metric in sorted(workers[h]["exemplars"]):
            slot = workers[h]["exemplars"][metric] or {}
            val = slot.get("value")
            lines.append("  ".join([
                "%6s" % ("h%s" % h),
                "%-28s" % metric[:28],
                "%12s" % ("%.3f" % val if isinstance(val, (int, float))
                          else "-"),
                str(slot.get("trace_id", "-"))]))
    return "\n".join(lines)


def render_fleet_hot_ops(workers, top_n=10):
    """The fleet hot-ops table: per provenance tag, each rank's device
    ms (from the ``opprof.<tag>_ms`` gauges stop_profiler streams into
    the sink) plus the cross-rank spread — so a straggler is
    attributable to an OP, not just a rank. Returns "" when no worker
    carried opprof gauges."""
    hosts = sorted(workers)
    per_tag = {}  # tag -> {host: ms}
    for h in hosts:
        for g, v in workers[h]["opprof"].items():
            if not g.endswith("_ms") or not g.startswith("opprof.pt."):
                continue
            tag = g[len("opprof."):-len("_ms")]
            per_tag.setdefault(tag, {})[h] = float(v)
    if not per_tag:
        return ""
    lines = ["== fleet hot ops (device ms per rank, opprof tags) =="]
    hdr = ["op"] + ["h%s" % h for h in hosts] + ["spread"]
    lines.append("%-36s" % hdr[0] + "  ".join("%9s" % c
                                              for c in hdr[1:]))
    ranked = sorted(per_tag.items(),
                    key=lambda kv: -max(kv[1].values()))[:top_n]
    for tag, per_host in ranked:
        vals = [per_host.get(h) for h in hosts]
        present = [v for v in vals if v is not None]
        spread = (max(present) - min(present)) if len(present) > 1 \
            else 0.0
        lines.append("%-36s" % tag[:36] + "  ".join(
            ("%9.3f" % v) if v is not None else "%9s" % "-"
            for v in vals) + "  %9.3f" % spread)
    fracs = [workers[h]["opprof"].get("opprof.attributed_frac")
             for h in hosts]
    if any(f is not None for f in fracs):
        lines.append("attributed frac per rank: " + "  ".join(
            "h%s=%.1f%%" % (h, 100.0 * f) for h, f in zip(hosts, fracs)
            if f is not None))
    return "\n".join(lines)


def render_goodput(workers):
    """The fleet badput-attribution report: per-rank goodput %, MFU,
    and slowest badput category from each rank's ``goodput.*``/``mfu.*``
    gauges, the fleet-weighted goodput %, and the supervisor's
    cross-incarnation job ledger (the ``goodput.job`` event) — where
    restart backoff, shrink re-plans, and preemption drains live."""
    from paddle_tpu.observability.goodput import (CATEGORIES,
                                                  GOODPUT_CATEGORIES)

    hosts = sorted(workers)
    lines = ["== fleet goodput / badput attribution =="]
    rows = []
    for h in hosts:
        g = workers[h]["goodput"]
        if not g:
            continue
        cats = {c: float(g.get("goodput.%s_ms" % c, 0.0))
                for c in CATEGORIES}
        bad = sorted(((c, m) for c, m in cats.items()
                      if c not in GOODPUT_CATEGORIES and m > 0),
                     key=lambda cm: -cm[1])
        rows.append({
            "host": h,
            "wall": float(g.get("goodput.wall_ms", 0.0)),
            "frac": g.get("goodput.frac"),
            "mfu": g.get("mfu.mfu"),
            "flops_s": g.get("mfu.achieved_flops_per_s"),
            "top": ("%s %.0fms" % bad[0]) if bad else "-",
            "good": sum(cats[c] for c in GOODPUT_CATEGORIES),
        })
    if rows:
        hdr = ("host", "wall_s", "goodput%", "mfu%", "flops/s",
               "top badput")
        lines.append("  ".join("%10s" % c for c in hdr))
        for r in rows:
            lines.append("  ".join([
                "%10s" % ("h%s" % r["host"]),
                "%10.2f" % (r["wall"] / 1e3),
                "%10s" % ("%.2f" % (100.0 * r["frac"])
                          if r["frac"] is not None else "-"),
                "%10s" % ("%.1f" % (100.0 * r["mfu"])
                          if r["mfu"] else "-"),
                "%10s" % ("%.3g" % r["flops_s"]
                          if r["flops_s"] else "-"),
                "  " + r["top"]]))
        fleet_wall = sum(r["wall"] for r in rows)
        fleet_good = sum(r["good"] for r in rows)
        if fleet_wall > 0:
            lines.append("fleet goodput: %.2f%% over %.1f s of rank wall"
                         % (100.0 * fleet_good / fleet_wall,
                            fleet_wall / 1e3))
    else:
        lines.append("(no per-rank goodput gauges — was "
                     "PADDLE_TPU_GOODPUT=1 exported to the workers?)")
    for h in hosts:
        job = workers[h]["job"]
        if not job:
            continue
        cats = job.get("categories") or {}
        bad = sorted(((c, float(m)) for c, m in cats.items()
                      if c not in GOODPUT_CATEGORIES and float(m) > 0),
                     key=lambda cm: -cm[1])
        lines.append("")
        lines.append("== supervisor job ledger (host %s) ==" % h)
        lines.append("wall: %.1f s  goodput: %.2f%%  incarnations: %s"
                     % (float(job.get("wall_ms", 0.0)) / 1e3,
                        100.0 * float(job.get("goodput_frac", 0.0)),
                        1 + int(job.get("attempt", 0))))
        for c, m in bad:
            lines.append("  %-18s %10.1f ms" % (c, m))
        if not bad:
            lines.append("  (no cross-incarnation badput)")
    return "\n".join(lines)


def goodput_report(dump_dir):
    return render_goodput(load_worker_dumps(dump_dir))


def merge_report(dump_dir):
    return render_merge(load_worker_dumps(dump_dir))


def report(host_path, xplane_dir=None, top_n=15):
    events = load_host_events(host_path)
    rows = per_step_rows(events)
    out = [render_host(rows)]
    if rows:
        n = len(rows)
        tot = sum(r["total_ms"] for r in rows)
        comp = sum(r["compile"] + r["trace"] for r in rows)
        out.append("steps: %d  host wall: %.2f ms  build+compile: %.2f ms "
                   "(%.1f%%)" % (n, tot, comp, comp / tot * 100 if tot
                                 else 0.0))
    if xplane_dir:
        try:
            out.append(render_device(xplane_dir, top_n))
        except Exception as e:  # xplane protos absent / empty dir
            out.append("\n(device aggregates unavailable: %s)" % e)
    return "\n".join(out)


def main(argv=None):
    p = argparse.ArgumentParser(
        description="Merged host-span + device-op perf report")
    p.add_argument("host_trace", nargs="?", default=None,
                   help="chrome-trace JSON from "
                   "observability.dump_chrome_trace / stop_profiler")
    p.add_argument("xplane_dir", nargs="?", default=None,
                   help="jax profiler trace dir with .xplane.pb dumps")
    p.add_argument("--top", type=int, default=15,
                   help="device ops to list (default 15)")
    p.add_argument("--merge", metavar="DIR", default=None,
                   help="merge a directory of per-worker JSONL telemetry "
                   "dumps (PADDLE_TPU_METRICS_SINK files) into one "
                   "cross-host report: per-step latency skew, "
                   "slowest-worker attribution, aggregate HBM watermarks")
    p.add_argument("--goodput", metavar="DIR", default=None,
                   help="merge per-worker JSONL dumps into the fleet "
                   "goodput/badput-attribution table (per-rank goodput "
                   "%%, MFU, slowest badput category, fleet goodput %%, "
                   "and the supervisor's cross-incarnation job ledger)")
    p.add_argument("--roofline", metavar="XPLANE_DIR", default=None,
                   help="per-op roofline table from a profiled trace "
                   "dir: top-k ops by device time with %% of step, "
                   "arithmetic intensity, and compute/memory/comm-bound "
                   "verdict (joins the opprof_provenance.json sidecar "
                   "stop_profiler wrote against the xplane planes)")
    p.add_argument("--gate", action="store_true",
                   help="with --roofline: exit nonzero when the top-k "
                   "table is empty or the collective lane disagrees "
                   "with the registered HLO schedule (the bench-flow "
                   "gate, like multichip_probe --predict)")
    args = p.parse_args(argv)
    if args.roofline:
        text, rc = roofline_report(args.roofline, top_n=args.top,
                                   gate=args.gate)
        print(text)
        return rc
    if args.goodput:
        print(goodput_report(args.goodput))
        return 0
    if args.merge:
        print(merge_report(args.merge))
        return 0
    if not args.host_trace:
        p.error("either HOST_TRACE, --merge DIR, --goodput DIR, or "
                "--roofline DIR is required")
    print(report(args.host_trace, args.xplane_dir, args.top))
    return 0


if __name__ == "__main__":
    sys.exit(main())
