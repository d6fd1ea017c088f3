#!/usr/bin/env python3
"""Quartile spreads of result lines, as the bounds are set from.

    python3 benchmarks/tools/spread.py <file of result lines> [<second set>]

Each file holds the last lines of the runs of one set (one JSON object a
line; other lines are skipped). For every metric: the median, and the
distance between the first and third quartile (Python's
``statistics.quantiles(values, n=4)``) as a share of the median. With two
sets, the wider of the two spreads and the second median against the first.
"""

import json
import statistics
import sys


def read(path):
    runs = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line.startswith("{") and '"metrics"' in line:
                runs.append(json.loads(line))
    return runs


def spreads(runs, skip_first=False):
    out = {}
    for name in runs[0]["metrics"]:
        values = [r["metrics"][name]["value"] for r in runs]
        if skip_first and name == "setup_s":
            values = values[1:]
        q1, _, q3 = statistics.quantiles(values, n=4)
        median = statistics.median(values)
        out[name] = (median, (q3 - q1) / median, values)
    return out


def main():
    sets = [spreads(read(p), skip_first=True) for p in sys.argv[1:]]
    for name in sets[0]:
        line = "%-22s" % name
        for median, spread, values in (s[name] for s in sets):
            line += " median %.6g spread %.4f%% (n=%d)" % (
                median, 100 * spread, len(values))
        if len(sets) == 2:
            a, b = sets[0][name], sets[1][name]
            line += " | wider %.4f%% second/first %+.4f%%" % (
                100 * max(a[1], b[1]), 100 * (b[0] / a[0] - 1))
        print(line)
    runs = [r for p in sys.argv[1:] for r in read(p)]
    print("runs:", len(runs), "not correct:",
          sum(not r["correct"] for r in runs))


if __name__ == "__main__":
    main()
