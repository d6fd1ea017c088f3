#!/usr/bin/env python3
"""Readings from which a cell's limits are set, taken on the chip at the
cell's own size (no measured window: a training cell's readings need none).

    python3 benchmarks/tools/prove.py --workload <cell> --seeds 11,12,13 \
        --what program,control,half --out chiprun_out/prove.jsonl

For every seed the plain reference (float32, ``highest``) follows the first
three steps once; each of ``--what`` is then held against it by
``compare.judge`` under the cell's own limits, and one JSON line is written
per seed and subject, with every number beside its limit and ``correct`` as
a run would report it (the control and the fault have to read false):

    program   the timed program itself, through the driver's Trainer (one
              executable, started from each seed in turn): the lower reading
    bf16      the reference computed in the configuration's own precision:
              a second witness of what a sound bf16 step reads
    control   the reference computed in the nearest precision below (fp8
              e4m3 operands): has to come out as not correct
    half      the reference with half of the batch left out, the mean taken
              over the rest: a planted fault

A seed given twice (``--seeds 43,43``) starts the program from it twice.
``--rehearse`` runs the same at the configuration's rehearsal sizes on the
CPU, for the control flow only.
"""

import argparse
import importlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

SUBJECTS = {"bf16": dict(precision="bf16"),
            "control": dict(precision="fp8"),
            "half": dict(precision="f32", half=True)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--what", default="program,control,half")
    ap.add_argument("--out", default="")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--leaves", action="store_true",
                    help="write every leaf's norms too, to look at by hand")
    args = ap.parse_args()
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax

    from benchmarks import compare
    from benchmarks.drivers import train
    from benchmarks.reference import common
    from benchmarks.run import load_json

    workload = load_json("workloads", args.workload + ".json")
    config = load_json("configs", workload["config"] + ".json")
    train.use_cache(jax)
    train.check_devices(jax, workload["chips"], args.rehearse)
    cfg, rows = train.sized(config, workload, args.rehearse)
    what = args.what.split(",")
    out = open(args.out, "a") if args.out else None

    def emit(record):
        line = json.dumps(record)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    limits = train.limits(workload, args.rehearse)
    seeds = [int(s) for s in args.seeds.split(",")]
    programs = {}
    if "program" in what:
        trainer = train.Trainer(cfg, rows, workload, args.rehearse)
        for seed in seeds:
            t = time.perf_counter()
            trainer.start(seed)
            readings, first = trainer.warm_up()
            programs.setdefault(seed, []).append(readings)
            trainer.free()
            print("program seed %d: %.1f s (first step %.1f s)"
                  % (seed, time.perf_counter() - t, first), file=sys.stderr)
        trainer.exe.close()
        del trainer
    model = importlib.import_module(
        "benchmarks.reference." + cfg["reference"])
    for seed in dict.fromkeys(seeds):
        t = time.perf_counter()
        reference = common.follow(model, cfg, rows, seed)
        print("reference seed %d: %.1f s" % (seed, time.perf_counter() - t),
              file=sys.stderr)
        subjects = {"program" + "'" * i: readings
                    for i, readings in enumerate(programs.get(seed, ()))}
        for name in what:
            if name in SUBJECTS:
                how = SUBJECTS[name]
                t = time.perf_counter()
                try:
                    subjects[name] = common.follow(
                        model, cfg, rows, seed, precision=how["precision"],
                        keep_rows=rows // 2 if how.get("half") else None)
                except Exception as e:  # a control that crashes has failed
                    emit({"seed": seed, "subject": name, "error": repr(e)})
                print("%s seed %d: %.1f s"
                      % (name, seed, time.perf_counter() - t),
                      file=sys.stderr)
        if args.leaves:
            subjects["reference"] = reference
        for name, readings in subjects.items():
            correct, compared = compare.judge(readings, reference, limits)
            record = {"seed": seed, "subject": name, "cell": args.workload,
                      "correct": correct, "losses": readings["losses"],
                      "reference_losses": reference["losses"],
                      "numbers": compared}
            if args.leaves:
                record["grad_norms"] = readings["grad_norms"]
                record["change_norms"] = readings["change_norms"]
            emit(record)


if __name__ == "__main__":
    main()
