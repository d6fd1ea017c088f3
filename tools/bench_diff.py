#!/usr/bin/env python
"""Regression differ for two bench rounds (``BENCH_*.json``).

The bench trajectory has had no automated comparison since r05 — this
closes that: point it at any two rounds and it diffs every numeric leaf
(the flat throughput metrics AND the nested ``counters`` blocks bench.py
emits — compile walls, cache hit/miss, pipeline/serving/health/elastic/
sentinel/goodput sub-dicts), classifies each delta by the metric's
direction, and exits nonzero when a directional metric regressed past
the threshold:

    python tools/bench_diff.py old.json new.json
    python tools/bench_diff.py --threshold 0.10 old.json new.json
    python tools/bench_diff.py --all old.json new.json   # every delta

Direction is inferred from the key name: throughput-like suffixes
(``*_per_sec``, ``*speedup*``, ``*qps*``, ``*hit*``, ``*goodput*``,
``*frac``, ``*mfu*``) are higher-better; cost-like ones (``*_ms``,
``*_bytes``, ``*miss*``, ``*evict*``, ``*trips*``, ``*crashes*``,
``*_wall*``, ``*transpose*``) are lower-better; anything else is
informational (printed under --all, never a failure). Both file shapes are accepted: the raw
``bench.py`` stdout JSON and the archived ``{"cmd", "rc", "parsed"}``
wrapper the rounds are stored as.
"""
import argparse
import json
import sys

HIGHER = ("per_sec", "per_s", "speedup", "qps", "hit", "goodput",
          "frac", "mfu", "fill", "efficiency", "max_batch",
          "savings_bytes")
LOWER = ("_ms", "_bytes", "_ns", "miss", "evict", "trips", "crashes",
         "wall", "dropped", "failed", "skew", "spread", "overhead",
         "badput", "retries", "transpose", "unattributed", "rejected",
         "shed_", "expired")


def direction(key):
    """-> 'higher' | 'lower' | None (informational)."""
    k = key.lower()
    # the most specific (longest) matching cue wins, so e.g.
    # "cache_miss_ms" reads as lower-better via _ms AND miss — agreeing
    # — while "prefetch_hit" is higher-better despite no suffix match
    hi = max((len(c) for c in HIGHER if c in k), default=0)
    lo = max((len(c) for c in LOWER if c in k), default=0)
    if hi == lo:
        return None
    return "higher" if hi > lo else "lower"


def numeric_leaves(obj, prefix=""):
    """Flatten every numeric leaf: {'counters.goodput.frac': 0.99, ...}
    (bools excluded — rc/ok flags are not metrics)."""
    out = {}
    if isinstance(obj, dict):
        for k, v in obj.items():
            out.update(numeric_leaves(v, prefix + str(k) + "."))
    elif isinstance(obj, (int, float)) and not isinstance(obj, bool):
        out[prefix[:-1]] = float(obj)
    return out


def load_round(path):
    """Accept both the archived wrapper ({"parsed": {...}}) and the raw
    bench.py output; returns the metric dict to diff."""
    with open(path) as f:
        doc = json.load(f)
    if isinstance(doc, dict) and isinstance(doc.get("parsed"), dict):
        doc = doc["parsed"]
    return doc


def diff_rounds(old, new, threshold):
    """-> (rows, regressions). A row is (key, old, new, delta_frac,
    direction, verdict) sorted worst-first; regressions counts rows
    whose directional delta exceeds ``threshold``."""
    a, b = numeric_leaves(old), numeric_leaves(new)
    rows, regressions = [], 0
    for key in sorted(set(a) | set(b)):
        va, vb = a.get(key), b.get(key)
        if va is None or vb is None:
            rows.append((key, va, vb, None, direction(key), "only-one"))
            continue
        if va == vb:
            continue
        delta = (vb - va) / abs(va) if va else float("inf")
        d = direction(key)
        verdict = "info"
        if d is not None:
            worse = delta < -threshold if d == "higher" \
                else delta > threshold
            better = delta > threshold if d == "higher" \
                else delta < -threshold
            verdict = ("REGRESSED" if worse
                       else "improved" if better else "ok")
            if worse:
                regressions += 1
        rows.append((key, va, vb, delta, d, verdict))
    order = {"REGRESSED": 0, "improved": 1, "ok": 2, "info": 3,
             "only-one": 4}
    rows.sort(key=lambda r: (order[r[5]],
                             -abs(r[3]) if r[3] is not None else 0.0))
    return rows, regressions


def _fmt(v):
    if v is None:
        return "-"
    return "%.6g" % v


def main(argv=None):
    p = argparse.ArgumentParser(
        description="diff the numeric metrics + counters blocks of two "
        "BENCH_*.json rounds; exit 1 when a directional metric "
        "regressed past the threshold")
    p.add_argument("old", help="baseline round JSON")
    p.add_argument("new", help="candidate round JSON")
    p.add_argument("--threshold", type=float, default=0.25,
                   help="relative regression tolerance (default 0.25 — "
                   "CPU-probe walls are noisy; tighten for real "
                   "hardware rounds)")
    p.add_argument("--all", action="store_true",
                   help="also print unchanged-direction/informational "
                   "deltas and metrics present in only one round")
    args = p.parse_args(argv)
    rows, regressions = diff_rounds(load_round(args.old),
                                    load_round(args.new),
                                    args.threshold)
    shown = 0
    print("%-52s %12s %12s %9s  %s"
          % ("metric", "old", "new", "delta", "verdict"))
    for key, va, vb, delta, d, verdict in rows:
        if not args.all and verdict in ("info", "only-one", "ok"):
            continue
        shown += 1
        print("%-52s %12s %12s %9s  %s"
              % (key[:52], _fmt(va), _fmt(vb),
                 ("%+.1f%%" % (100.0 * delta)) if delta is not None
                 else "-",
                 verdict + ("" if d is None else " (%s-better)" % d)))
    if not shown:
        print("(no directional deltas beyond %.0f%% — pass --all for "
              "the full diff)" % (100.0 * args.threshold))
    print("\nbench_diff: %d regression(s) past %.0f%% against %s"
          % (regressions, 100.0 * args.threshold, args.old))
    return 1 if regressions else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
