#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is ``benchmarks/workloads/<cell>.json``; it names its
configuration (``benchmarks/configs/<config>.json``) and its driver
(``benchmarks/drivers/<driver>.py``). The driver builds the system under
test, warms it up, measures for ``--seconds`` and checks what the timed
path produced against the configuration's plain reference. A traced run
(``--trace 1``) reports the per-layer metrics instead: every
``benchmarks/layer_metrics/*.py`` whose declaration lists the cell or its
driver. The last line of standard output is the result. Nothing in this
file names a model, a cell or a metric; see README.md.

``--rehearse`` runs the control flow at the configuration's ``rehearsal``
sizes on the CPU. Its last line names ``cpu`` as the device and its
numbers are never written anywhere.
"""

import time

T0 = time.perf_counter()  # process start, as near as Python lets us see it

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_json(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_module(kind, name):
    """``benchmarks/<kind>/<name>.py`` by its file, since a metric's name
    may hold dots."""
    path = os.path.join(HERE, kind, name + ".py")
    modname = "benchmarks.%s.%s" % (kind, name.replace(".", "_"))
    if modname in sys.modules:
        return sys.modules[modname]
    spec = importlib.util.spec_from_file_location(modname, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[modname] = module
    spec.loader.exec_module(module)
    return module


def layer_metrics(cell, driver, facts):
    """Every reader under ``layer_metrics/`` that lists this cell, its
    driver or every driver (``"*"``), computed from what the run left in
    ``facts``. A reader that
    finds nothing to read returns None and is left out of the line."""
    out = {}
    folder = os.path.join(HERE, "layer_metrics")
    for fname in sorted(os.listdir(folder)):
        if not fname.endswith(".py") or fname.startswith("_"):
            continue
        reader = load_module("layer_metrics", fname[:-3])
        decl = reader.DECLARATION
        drivers = decl.get("drivers", ())
        if not (cell in decl.get("workloads", ())
                or driver in drivers or "*" in drivers):
            continue
        value = reader.compute(facts)
        if value is not None:
            out[decl["name"]] = {"value": value, "unit": decl["unit"]}
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal of the control flow at tiny sizes")
    args = ap.parse_args(argv)

    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    workload = load_json("workloads", args.workload + ".json")
    config = load_json("configs", workload["config"] + ".json")
    driver = load_module("drivers", workload["driver"])

    run = driver.run(dict(
        args=args, workload=workload, config=config, root=ROOT, t0=T0))

    if args.trace:
        metrics = layer_metrics(args.workload, workload["driver"],
                                run["facts"])
    else:
        metrics = run["end_to_end"]
    result = {"correct": bool(run["correct"]),
              "attempted": run["attempted"], "failed": run["failed"],
              "metrics": metrics, "device": run["device"]}
    if args.trace and run.get("breakdown"):
        result["breakdown"] = run["breakdown"]
    result["compared"] = run["compared"]
    for name, c in run["compared"].items():
        print("compared %s = %r limit %r at %s"
              % (name, c["value"], c["limit"], c["at"]), file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
