#!/usr/bin/env python3
"""Where a cell's set-up goes inside the program: the Program's
construction, each executable's cache-miss build and first call, and the
first calls' tracing seconds by Fluid op type, by role and by layer. To be
read by hand.

    python3 benchmarks/tools/setup_table.py --workload <cell> [--seed n] [--top 24] [--rehearse]

Builds the cell's ``Trainer`` as a run does and warms it up; nothing is
switched on. The program records its seams by itself (``minimize``,
``append_backward``, ``trace``, ``compile`` with what JAX and its
compilation cache report) and, inside each first call, one span
``op:<type>`` for every Fluid op whose lowering ran under JAX's trace
(``layer_metrics/_setup_spans.py``). Self time is the program's
(``tracing.self_time``); the shares are of the first calls' ``jax_trace_s``
argument, the jaxpr tracing alone.

A layer is the ``layerN.`` in an op's output names (a parameter's gradient,
an optimizer op), else in its input names (an op that reads the layer's
parameter); ``-`` is an op that touches only other persistable state (the
embedding, the head, the learning rate). An op with neither (a reshape, the
attention, a residual add) is booked with the op traced before it.
"""

import argparse
import collections
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

LAYER = re.compile(r"(?:^|[^A-Za-z0-9])layer(\d+)\.")
ROLES = ("forward", "backward", "optimizer")
METRICS = ("desc_build_s", "jax_trace_s", "mlir_lower_s", "first_call_rest_s",
           "forward_trace_s", "backward_trace_s", "optimizer_trace_s",
           "append_backward_s", "optimizer_build_s", "compile_cache_hit_pct",
           "engine_executables")


def layer_of(op, block):
    """The ``layerN.`` of the op's names, outputs first; ``-`` for an op
    that touches only other persistable state (the embedding, the head, the
    learning rate); None for one that touches none."""
    names = op.output_arg_names() + op.input_arg_names()
    for name in names:
        found = LAYER.search(name)
        if found:
            return int(found.group(1))
    for name in names:
        var = block.find_var_recursive(name)
        if var is not None and var.persistable:
            return "-"
    return None


def layers_by_span(ops, executables):
    """{id(span): layer or ``-``} for the op spans of the first calls, in
    the order they were traced. ``executables`` is [(first-call span, its
    ``BlockProgram``)]."""
    out = {}
    for first, program in executables:
        last, live = "-", program.ops
        mine = sorted((s for s in ops if first.ts_us <= s.ts_us
                       <= first.ts_us + first.dur_us), key=lambda s: s.ts_us)
        for s in mine:
            block, index = s.args["idx"].split("_")
            found = (layer_of(live[int(index)], program.block)
                     if block == "0" and int(index) < len(live) else None)
            last = out[id(s)] = last if found is None else found
    return out


def print_rows(title, rows, total, top=None):
    """``rows``: {label: (calls, seconds)}; shares are of ``total``."""
    print("\n%-34s %6s %9s %7s" % (title, "calls", "self s", "share"))
    ordered = sorted(rows.items(), key=lambda kv: -kv[1][1])
    for label, (calls, seconds) in ordered[:top]:
        print("%-34s %6d %9.3f %6.1f%%"
              % (label, calls, seconds, 100.0 * seconds / total))
    rest = ordered[top:] if top else []
    if rest:
        print("%-34s %6d %9.3f %6.1f%%" % (
            "(%d more)" % len(rest), sum(c for _, (c, _) in rest),
            sum(s for _, (_, s) in rest),
            100.0 * sum(s for _, (_, s) in rest) / total))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--top", type=int, default=24)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax

    from benchmarks.drivers import train
    from benchmarks.layer_metrics import _setup_spans as setup
    from benchmarks.run import load_json, load_module

    workload = load_json("workloads", args.workload + ".json")
    config = load_json("configs", workload["config"] + ".json")
    train.use_cache(jax)
    train.check_devices(jax, workload["chips"], args.rehearse)
    cfg, rows = train.sized(config, workload, args.rehearse)
    trainer = train.Trainer(cfg, rows, workload, args.rehearse)
    trainer.start(args.seed)
    _, first_step_s = trainer.warm_up()

    from paddle_tpu import observability as obs

    spans = obs.spans()
    firsts = setup.first_calls(spans)
    print("%s on %s, seed %d: first step %.3f s"
          % (args.workload, jax.devices()[0].device_kind, args.seed,
             first_step_s))
    print("\nthe seams, in order (seconds)")
    for s in sorted(spans, key=lambda s: s.ts_us):
        if s.name in ("minimize", "append_backward", "trace"):
            print("%-16s %8.3f  %s" % (s.name, s.dur_us / 1e6, s.args or ""))
        elif s in firsts:
            a = s.args
            print("%-16s %8.3f  %s at run %s: jaxpr tracing %.3f, MLIR "
                  "lowering %.3f, backend %.3f (cache: %d hit, %d missed, "
                  "read %.3f, saved %.3f), the rest %.3f" % (
                      s.name, s.dur_us / 1e6, a["fun_name"], a.get("step"),
                      a.get("jax_trace_s", 0.0), a.get("jax_lower_s", 0.0),
                      a.get("backend_compile_s", 0.0),
                      a.get("cache_hits", 0), a.get("cache_misses", 0),
                      a.get("cache_retrieval_s", 0.0),
                      a.get("compile_saved_s", 0.0), setup.rest_seconds(s)))
    print("\nas the set-up readers give them")
    for name in METRICS:
        reader = load_module("layer_metrics", name)
        print("%-24s %s %s" % (name, reader.compute({}),
                               reader.DECLARATION["unit"]))

    ops = setup.op_spans(spans)
    if not ops:
        print("\nthe program records no op span: nothing to table")
        return 0
    traced = sum(s.args.get("jax_trace_s", 0.0) for s in firsts)

    def table(title, label, top=None):
        seconds = setup.self_seconds_by(ops, label)
        calls = collections.Counter(label(s) for s in ops)
        print_rows(title, {k: (calls[k], v) for k, v in seconds.items()},
                   traced, top)
        return seconds

    by_type = table("Fluid op type, of %.3f s traced" % traced,
                    lambda s: s.name[len(setup.OP):], args.top)
    in_ops = sum(by_type.values())
    print("%-34s %6d %9.3f %6.1f%%" % (
        "all ops", len(ops), in_ops, 100.0 * in_ops / traced))
    body = sum(s.dur_us for s in setup.inside(spans, firsts)
               if s.name == "traced-fn") / 1e6
    if body:
        print("the rest: %.3f s in the engine's traced function outside "
              "its ops' lowerings (`traced-fn`, self), %.3f s of JAX's "
              "tracing outside that function" % (body - in_ops,
                                                 traced - body))
    table("role", lambda s: s.args["role"])

    by_name = {c.name: c.block_program
               for c in trainer.exe.engine._cache.values()}
    layer = layers_by_span(ops, [(f, by_name[f.args["fun_name"]])
                                 for f in firsts
                                 if f.args["fun_name"] in by_name])
    if any(v != "-" for v in layer.values()):
        split = setup.self_seconds_by(
            ops, lambda s: (layer[id(s)], s.args["role"]))
        print("\n%-8s %6s %s %10s" % (
            "layer", "calls", " ".join("%10s" % r for r in ROLES), "self s"))
        for name in sorted({k[0] for k in split}, key=str):
            row = [split.get((name, r), 0.0) for r in ROLES]
            print("%-8s %6d %s %10.3f" % (
                name, sum(layer[id(s)] == name for s in ops),
                " ".join("%10.3f" % v for v in row), sum(row)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
