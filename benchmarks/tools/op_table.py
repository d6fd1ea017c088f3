#!/usr/bin/env python3
"""Device time of a cell's step by Fluid op, op type and phase: the
operator's table, to be read by hand.

    python3 benchmarks/tools/op_table.py --workload <cell> [--seed n] [--steps 3] [--rehearse]

Builds the cell's ``Trainer`` as a run does, warms it up, and puts
``fluid.profiler.profiler()`` round ``--steps`` steps, each drained: the
profiler's session switches the program's spans on, the engine leaves a
note of the step's executable, and ``stop_profiler`` resolves it and writes
the host table (with self time) and the device tables (by provenance tag,
by op type, by phase). Printed after it: what each family of HLO names
(``trace_reduce.family``: what a result line's ``breakdown`` prints) is
made of, by the type of the Fluid op that each instruction came from.
"""

import argparse
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

FAMILIES, TYPES = 12, 4


def families_by_op_type(ops, phases):
    """{family: {op type or ``(no tag)``: seconds}} over the device
    planes' events, averaged over the planes."""
    from benchmarks import trace_reduce
    from benchmarks.layer_metrics._phases import instruction_name

    out = {}
    for rows in ops.values():
        for start, end, name in rows:
            op_type = phases.get(instruction_name(name),
                                 (None, None, None))[1] or "(no tag)"
            row = out.setdefault(trace_reduce.family(name), {})
            row[op_type] = row.get(op_type, 0.0) + (end - start)
    planes = max(len(ops), 1)
    return {family: {t: ns / planes / 1e9 for t, ns in row.items()}
            for family, row in out.items()}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--steps", type=int, default=3)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax

    from benchmarks import trace_reduce
    from benchmarks.drivers import train
    from benchmarks.run import load_json

    workload = load_json("workloads", args.workload + ".json")
    config = load_json("configs", workload["config"] + ".json")
    train.use_cache(jax)
    train.check_devices(jax, workload["chips"], args.rehearse)
    cfg, rows = train.sized(config, workload, args.rehearse)
    trainer = train.Trainer(cfg, rows, workload, args.rehearse)
    trainer.start(args.seed)
    trainer.warm_up()

    import paddle_tpu.fluid as fluid
    from paddle_tpu import flags
    from paddle_tpu.observability import opprof

    out = tempfile.mkdtemp(prefix="op_table_")
    flags.set_flags({"trace_dir": os.path.join(out, "trace")})
    profile = os.path.join(out, "profile.txt")
    with fluid.profiler.profiler(profile_path=profile):
        for i in range(args.steps):
            train._scalar(trainer.step(train.WARMUP_STEPS + i))
    with open(profile) as f:
        print(f.read())

    reduced = trace_reduce.reduce_dir(os.path.join(out, "trace"))
    if reduced is None:
        print("no device plane in the trace (a CPU rehearsal): no family "
              "table")
        return 0
    table = families_by_op_type(reduced["ops"], opprof.instruction_phases())
    busy = reduced["busy_s"]
    print("HLO families of %d steps by the Fluid op type of their "
          "instructions (busy %.4f s)" % (args.steps, busy))
    totals = sorted(((sum(row.values()), family)
                     for family, row in table.items()), reverse=True)
    for seconds, family in totals[:FAMILIES]:
        parts = sorted(table[family].items(), key=lambda kv: -kv[1])
        print("%-32s %8.2f ms/step %5.1f%%  %s" % (
            family[:32], 1000.0 * seconds / args.steps,
            100.0 * seconds / busy,
            ", ".join("%s %.2f" % (t, 1000.0 * s / args.steps)
                      for t, s in parts[:TYPES])))
    return 0


if __name__ == "__main__":
    sys.exit(main())
