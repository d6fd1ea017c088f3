#!/usr/bin/env python
"""Lint a Program with the paddle_tpu.analysis verifier.

Two modes:

  * ``--program FILE`` — lint a serialized program (the native
    ``ProgramDescData.serialize_to_string`` bytes, a pickle of those
    bytes, or a pickled Program).
  * ``--model NAME`` (repeatable; default: every book model plus
    mnist_mlp) — build the named ``tests/book`` model, append an Adam
    training pass so the backward/optimizer segments are linted too, and
    verify main + startup programs with the real feed/fetch lists.

All six checkers run (use-before-def, shape-dtype, waw-hazard,
grad-pairing, dead-op, sharding). ``--opt-level N`` first runs the
transform pipeline (analysis/transforms.py) over each program and lints
the *transformed* desc — the same desc the engine would compile at that
level. ``--memory`` additionally prints each main program's memory plan
(analysis/memory.py): liveness peak + top-10 contributors, the
donate/held split, and the remat segment choice under ``--budget-mb``
(default: the device-derived HBM budget, usually absent on CPU — remat
reads "off"). ``--freeze`` additionally runs each built model through
the inference freeze + INT8 post-training-quantization pipeline
(paddle_tpu.inference) and prints the op/var counts before/after, the
batch-norm folds, and the quantized-vs-skipped table with per-op
calibrated ranges. ``--spmd`` additionally prints each
program's static SPMD report (analysis/spmd.py) under the --mesh/--rule
table: sharding table, predicted collective schedule with bytes,
per-device peak vs replicated peak, and the replicated-optimizer-state
(ZeRO-1) ledger; add ``--zero1`` to analyze with the sharded weight
update ON — the schedule gains the per-param all-gathers and the
ledger reads post-sharding (near zero when the plan covers the
optimizer state). ``--flags`` cross-references the README flags table
against the flags.py DEFS registry and exits 1 on missing/stale rows.
``--provenance`` lints the opprof lowering provenance: every registered
op type's ``pt.<type>.<block>_<idx>`` scope tag round-trips through
``parse_tag``, a real mnist_mlp training compile covers every live op
with a provenance entry + registry cost row and at least one tag lands
in the compiled HLO op_metadata, and no paddle_tpu module imports from
tools/ (library -> CLI layering). Exit code 1 iff any ERROR finding.

  python tools/lint_program.py --model mnist_mlp --spmd --mesh dp=2
  python tools/lint_program.py --model mnist_mlp --spmd --zero1
  python tools/lint_program.py --flags

  python tools/lint_program.py
  python tools/lint_program.py --list-passes
  python tools/lint_program.py --model fit_a_line --model word2vec -v
  python tools/lint_program.py --mesh dp=4,tp=2 --rule '.*fc.*w:,tp'
  python tools/lint_program.py --program /tmp/main.prog --opt-level 2
  python tools/lint_program.py --model mnist_mlp --memory --budget-mb 4
  python tools/lint_program.py --model recognize_digits_conv --freeze
"""

import argparse
import importlib.util
import os
import pickle
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

# Lint on the host CPU backend; never grabs TPU devices.
os.environ.setdefault("JAX_PLATFORMS", "cpu")


def _load_book_builders():
    """Import tests/book/test_book_models.py by path (tests/ is not a
    package) and return its BOOK_BUILDERS registry plus the mnist MLP."""
    builders = {}
    spec = importlib.util.spec_from_file_location(
        "_book_models",
        os.path.join(REPO_ROOT, "tests", "book", "test_book_models.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    builders.update(mod.BOOK_BUILDERS)

    spec = importlib.util.spec_from_file_location(
        "_mnist_mlp", os.path.join(REPO_ROOT, "tests", "test_mnist_mlp.py"))
    mlp = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mlp)

    def mnist_mlp():
        img, label, avg_loss, acc = mlp.build_mlp()
        return ["img", "label"], acc, avg_loss

    builders["mnist_mlp"] = mnist_mlp
    return builders


def _parse_mesh_axes(spec):
    """'dp=4,tp=2' -> {'dp': 4, 'tp': 2} (static; no devices)."""
    if not spec:
        return None
    axes = {}
    for part in spec.split(","):
        name, _, size = part.partition("=")
        axes[name.strip()] = int(size)
    return axes


def _parse_mesh(spec):
    """'dp=4,tp=2' -> Mesh (over however many host devices exist)."""
    axes = _parse_mesh_axes(spec)
    if axes is None:
        return None
    from paddle_tpu.parallel.mesh import make_mesh

    return make_mesh(axes)


def _parse_rules(rule_args):
    """['pat:axis0,axis1', ...] -> ShardingRules; empty axis slots ('')
    mean an unsharded dim."""
    if not rule_args:
        return None
    from jax.sharding import PartitionSpec
    from paddle_tpu.parallel.sharding import ShardingRules

    rules = ShardingRules()
    for raw in rule_args:
        pat, _, spec = raw.rpartition(":")
        if not pat:
            raise SystemExit("bad --rule %r (want PATTERN:axis0,axis1)" % raw)
        entries = [a.strip() or None for a in spec.split(",")]
        rules.add(pat, PartitionSpec(*entries))
    return rules


def _list_passes():
    """Every registered pass: name, kind (checker/transform), and whether
    it runs by default — checkers iff in DEFAULT_PASSES, transforms iff
    enabled at the opt_level flag's default value."""
    from paddle_tpu import flags
    from paddle_tpu.analysis.passes import DEFAULT_PASSES, PASS_REGISTRY

    default_level = flags.DEFS["opt_level"][1]
    print("%-22s %-10s %s" % ("pass", "kind", "default"))
    for name in sorted(PASS_REGISTRY):
        cls = PASS_REGISTRY[name]
        kind = getattr(cls, "kind", "checker")
        if kind == "transform":
            on = getattr(cls, "min_level", 2) <= default_level
            note = "on (level>=%d)" % cls.min_level if on else \
                "off (level>=%d)" % cls.min_level
        else:
            note = "on" if name in DEFAULT_PASSES else "off"
        print("%-22s %-10s %s" % (name, kind, note))


def _maybe_optimize(program, args, feed_names=None, fetch_names=None):
    """Apply the transform pipeline when --opt-level was given; returns
    the desc to lint (the transformed clone, or the input unchanged)."""
    if args.opt_level is None:
        return program
    from paddle_tpu.analysis import optimize_program

    desc, report = optimize_program(
        program, level=args.opt_level,
        feed_names=feed_names, fetch_names=fetch_names)
    print(report.render())
    return desc


def _print_memory_plan(program_or_desc, args, fetch_names=None):
    """The --memory report: liveness peak + top contributors, donation
    split, and the remat choice under the requested budget, straight off
    MemoryPlan.render() — the same planner the engine runs at opt 3."""
    from paddle_tpu.analysis import memory as memplan

    if args.budget_mb is not None:
        budget = int(args.budget_mb * (1 << 20))
    else:
        budget = memplan.hbm_budget_bytes()
    plan = memplan.plan_memory(program_or_desc, fetch_names=fetch_names,
                               budget_bytes=budget)
    print("-- memory plan (budget: %s) --"
          % ("%d MiB" % (budget >> 20) if budget else "none"))
    print(plan.render())


def _print_spmd_report(program_or_desc, args, feed_names=None,
                       fetch_names=None):
    """The --spmd report: the static SPMD analysis (analysis/spmd.py)
    under the --mesh/--rule table — sharding table, predicted collective
    schedule with per-collective bytes, per-device peak vs replicated
    peak, and the replicated-optimizer-state (ZeRO-1) ledger. Feed
    shapes come from the desc with dynamic dims resolved to --batch."""
    from paddle_tpu.analysis.spmd import analyze_spmd

    # analyze_spmd is purely static — a {axis: size} dict is enough, no
    # devices are ever touched for the report itself
    mesh = _parse_mesh_axes(args.mesh) or {"dp": 2}
    rules = _parse_rules(args.rule)
    desc = getattr(program_or_desc, "desc", program_or_desc)
    gb = desc.block(0)
    feed_shapes = {}
    for n in (feed_names or ()):
        vd = gb.find_var_recursive(n)
        if vd is not None and vd.shape is not None:
            feed_shapes[n] = tuple(
                args.batch if int(d) < 0 else int(d) for d in vd.shape)
    report = analyze_spmd(desc, mesh=mesh, shard_rules=rules,
                          feed_names=feed_names,
                          feed_shapes=feed_shapes,
                          fetch_names=fetch_names, zero1=args.zero1)
    print("-- spmd report --")
    print(report.render())


def _flags_doc_lint():
    """The --flags mode: cross-reference the README flags table against
    the flags.py DEFS registry (flags.flags_doc_issues) and fail on any
    missing, stale, or duplicated row."""
    from paddle_tpu import flags

    issues = flags.flags_doc_issues()
    if not issues:
        print("flags doc: README table and flags.py DEFS are in sync "
              "(%d flags)" % len(flags.DEFS))
        return 0
    for issue in issues:
        print("flags doc: %s" % issue)
    print("\nflags doc: %d issue(s)" % len(issues))
    return 1


def _provenance_lint():
    """The --provenance mode: three checks over the opprof lowering
    provenance (observability/opprof.py).

    (a) Every registered op type's scope tag survives the full jit path
        join — ``parse_tag("jit(f)/.../pt.<type>.<b>_<i>/hlo")`` must
        recover exactly the tag ``provenance_tag`` emitted.
    (b) A real compile: run the mnist MLP one training step with the
        opprof flag on and metrics enabled, then assert every live
        (post-DCE) op in every compiled executable landed in the
        provenance map, that at least one ``pt.*`` tag reached the
        compiled HLO op_metadata, and that the opprof registry has a
        cost row for every provenance tag.
    (c) Layering: no module under paddle_tpu/ imports from tools/ (the
        library must never depend on the CLI layer — tools/ imports
        the library, never the other way).

    Exit 1 on any failure.
    """
    import re

    import numpy as np

    from paddle_tpu import flags
    from paddle_tpu import observability as obs
    from paddle_tpu.core.registry import OpRegistry
    from paddle_tpu.observability import opprof

    issues = []

    # (a) tag round-trip for every registered op type
    types = OpRegistry.all_types()
    for t in types:
        tag = opprof.provenance_tag(t, 0, 3)
        path = "jit(run)/transpose(jvp(run))/%s/dot_general" % tag
        if opprof.parse_tag(path) != tag or opprof.tag_op_type(tag) != t:
            issues.append("op type %r: scope tag %r does not round-trip "
                          "through parse_tag" % (t, tag))
    print("provenance: %d registered op type(s) checked for scope-tag "
          "round-trip" % len(types))

    # (b) live compile coverage on the mnist MLP
    import paddle_tpu.fluid as fluid
    from paddle_tpu import unique_name
    from paddle_tpu.executor import Executor
    from paddle_tpu.framework import Program, program_guard

    builders = _load_book_builders()
    old_gen = unique_name.switch()
    was_enabled = obs.enabled()
    old_opprof = flags.get_flag("opprof")
    try:
        flags.set_flags({"opprof": True})
        obs.set_enabled(True)
        opprof.reset()
        main, startup = Program(), Program()
        with program_guard(main, startup):
            feeds, fetch, loss = builders["mnist_mlp"]()
            fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
        exe = Executor()
        scope = fluid.Scope()
        rng = np.random.RandomState(0)
        with fluid.scope_guard(scope):
            exe.run(startup)
            exe.run(main,
                    feed={"img": rng.randn(8, 784).astype(np.float32),
                          "label": np.ones((8, 1), np.int64)},
                    fetch_list=[loss.name])
        compiled = [cb for cb in exe.engine._cache.values()
                    if getattr(cb, "provenance", None)]
        if not compiled:
            issues.append("mnist_mlp compile recorded no provenance map "
                          "(opprof flag not threaded through _compile?)")
        live_tags = set()
        for cb in compiled:
            block = cb.block_program.block
            for i, op in enumerate(cb.block_program.ops):
                tag = opprof.provenance_tag(
                    op.type, getattr(block, "idx", 0), i)
                live_tags.add(tag)
                if tag not in cb.provenance:
                    issues.append("live op %s #%d: no provenance entry "
                                  "(expected tag %r)" % (op.type, i, tag))
        snap = opprof.registry_snapshot()
        if not snap["instr_tags"]:
            issues.append("no pt.* scope tag reached the compiled HLO "
                          "op_metadata (named_scope lost in lowering?)")
        missing_costs = sorted(live_tags - set(snap["costs"]))
        for tag in missing_costs:
            issues.append("tag %r has no cost row in the opprof registry "
                          "(register_executable skipped it)" % tag)
        print("provenance: mnist_mlp compiled %d executable(s), %d live "
              "op(s), %d tagged HLO instruction(s), %d cost row(s)"
              % (len(compiled), len(live_tags), len(snap["instr_tags"]),
                 len(snap["costs"])))
    finally:
        flags.set_flags({"opprof": old_opprof})
        obs.set_enabled(was_enabled)
        unique_name.switch(old_gen)

    # (c) layering: the library never imports from the tools/ CLI layer
    pat = re.compile(r"^\s*(?:from\s+tools\b|import\s+tools\b)", re.M)
    n_scanned = 0
    for dirpath, _dirs, files in os.walk(os.path.join(REPO_ROOT,
                                                      "paddle_tpu")):
        for fname in files:
            if not fname.endswith(".py"):
                continue
            path = os.path.join(dirpath, fname)
            n_scanned += 1
            with open(path) as f:
                if pat.search(f.read()):
                    issues.append("%s imports from tools/ (library -> CLI "
                                  "layering violation)"
                                  % os.path.relpath(path, REPO_ROOT))
    print("provenance: %d paddle_tpu module(s) scanned for tools/ imports"
          % n_scanned)

    if not issues:
        print("\nprovenance lint: OK")
        return 0
    for issue in issues:
        print("provenance lint: %s" % issue)
    print("\nprovenance lint: %d issue(s)" % len(issues))
    return 1


def _freeze_report(main, startup, feed_names, fetch_names):
    """The --freeze report: run the real freeze + PTQ pipeline
    (inference/freeze.py, inference/quantize.py) over the built model and
    print the op/var before/after counts, the BN-fold tally, and the
    quantized-vs-skipped table with each op's calibrated activation
    range and weight scale."""
    import numpy as np

    import paddle_tpu.fluid as fluid
    from paddle_tpu.executor import Executor
    from paddle_tpu.inference import freeze_program
    from paddle_tpu.inference.quantize import (
        calibrate_program,
        quantize_desc,
    )

    exe = Executor()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
    frozen, rep = freeze_program(main, feed_names, fetch_names,
                                 scope=scope)
    print("-- freeze report --")
    print(rep.render())
    # synthetic calibration feeds off the desc shapes (-1 -> small
    # batch); integer feeds get ones — valid ids for any vocab/label
    # space of size >= 2 and non-degenerate sequence lengths
    gb = main.desc.global_block()
    rng = np.random.RandomState(0)
    feed = {}
    for n in feed_names:
        vd = gb.find_var_recursive(n)
        shape = [4 if int(d) < 0 else int(d)
                 for d in (list(vd.shape) or [4])]
        if "int" in str(vd.dtype).lower():
            feed[n] = np.ones(shape, np.int64)
        else:
            feed[n] = (rng.randn(*shape) * 0.5).astype(np.float32)
    with fluid.scope_guard(scope):
        stats = calibrate_program(frozen, [feed, feed], scope=scope,
                                  executor=exe, max_batches=2)
        work = frozen.desc.clone()
        qrep = quantize_desc(work, scope, stats.ranges())
    print("-- quantization report --")
    print(qrep.render())


def _lint_built_model(name, builder, args):
    from paddle_tpu import unique_name
    from paddle_tpu.analysis import Severity, verify_program
    from paddle_tpu.framework import Program, program_guard

    import paddle_tpu.fluid as fluid

    old_gen = unique_name.switch()
    try:
        main, startup = Program(), Program()
        with program_guard(main, startup):
            feeds, fetch, loss = builder()
            if args.train:
                fluid.optimizer.Adam(learning_rate=1e-3).minimize(loss)
        mesh = _parse_mesh(args.mesh)
        rules = _parse_rules(args.rule)
        fetches = [loss.name, fetch.name]
        print("== %s ==" % name)
        main_desc = _maybe_optimize(main, args, feed_names=feeds,
                                    fetch_names=fetches)
        report = verify_program(
            main_desc, feed_names=feeds,
            fetch_names=fetches,
            mesh=mesh, shard_rules=rules)
        startup_report = verify_program(startup)
        report.extend(startup_report.findings)
        if args.memory:
            _print_memory_plan(main_desc, args, fetch_names=fetches)
        if args.spmd:
            _print_spmd_report(main_desc, args, feed_names=feeds,
                               fetch_names=fetches)
        if args.freeze:
            try:
                _freeze_report(main, startup, feeds, [fetch.name])
            except Exception as e:  # per-model: a freeze failure is a
                # report line, not a lint abort
                print("-- freeze report failed: %s: %s --"
                      % (type(e).__name__, e))
    finally:
        unique_name.switch(old_gen)

    min_sev = Severity.INFO if args.verbose else Severity.WARNING
    print(report.render(min_severity=min_sev))
    return report


def _lint_file(path, args):
    from paddle_tpu.analysis import Severity, verify_program
    from paddle_tpu.core.desc import ProgramDescData
    from paddle_tpu.framework import Program

    with open(path, "rb") as f:
        blob = f.read()
    program = None
    try:
        program = Program.parse_from_string(blob)
    except Exception:
        obj = pickle.loads(blob)
        if isinstance(obj, (bytes, str)):
            program = Program.parse_from_string(obj)
        elif isinstance(obj, ProgramDescData):
            program = obj
        else:
            program = obj  # a pickled Program
    print("== %s ==" % path)
    program = _maybe_optimize(program, args)
    report = verify_program(program, mesh=_parse_mesh(args.mesh),
                            shard_rules=_parse_rules(args.rule))
    if args.memory:
        _print_memory_plan(program, args)
    if args.spmd:
        _print_spmd_report(program, args)
    min_sev = Severity.INFO if args.verbose else Severity.WARNING
    print(report.render(min_severity=min_sev))
    return report


def main(argv=None):
    parser = argparse.ArgumentParser(
        description="Static program linter (paddle_tpu.analysis)")
    parser.add_argument("--program", metavar="FILE",
                        help="serialized/pickled program to lint")
    parser.add_argument("--model", action="append", default=[],
                        help="book model name to build and lint "
                             "(repeatable; default: all)")
    parser.add_argument("--no-train", dest="train", action="store_false",
                        help="lint the forward program only (skip "
                             "append_backward + optimizer)")
    parser.add_argument("--mesh", default="",
                        help="mesh axes for the sharding checker, e.g. "
                             "dp=4,tp=2")
    parser.add_argument("--rule", action="append", default=[],
                        help="sharding rule PATTERN:axis0,axis1 "
                             "(repeatable; empty slot = unsharded dim)")
    parser.add_argument("--opt-level", type=int, default=None,
                        metavar="N",
                        help="run the transform pipeline at level N and "
                             "lint the transformed desc (0 off, 1 "
                             "fuse-attention, 2 + fusion/folding/cse)")
    parser.add_argument("--memory", action="store_true",
                        help="print each main program's memory plan "
                             "(liveness peak + top contributors, "
                             "donation split, remat choice) after "
                             "linting it")
    parser.add_argument("--budget-mb", type=float, default=None,
                        metavar="MB",
                        help="HBM budget for the --memory remat policy "
                             "(default: device limit x "
                             "PADDLE_TPU_HBM_BUDGET_FRAC, if knowable)")
    parser.add_argument("--freeze", action="store_true",
                        help="after linting each built model, run the "
                             "inference freeze + INT8 PTQ pipeline over "
                             "it and print the op/var before/after "
                             "counts, BN folds, and the quantized-vs-"
                             "skipped table with calibrated ranges")
    parser.add_argument("--spmd", action="store_true",
                        help="print each program's static SPMD report "
                             "(analysis/spmd.py) under --mesh/--rule "
                             "(default mesh dp=2): sharding table, "
                             "predicted collective schedule with bytes, "
                             "per-device peak vs replicated peak, and "
                             "the replicated-optimizer-state ledger")
    parser.add_argument("--batch", type=int, default=8, metavar="N",
                        help="batch size used to resolve dynamic feed "
                             "dims for --spmd (default 8)")
    parser.add_argument("--zero1", action="store_true",
                        help="analyze --spmd with the ZeRO-1 sharded "
                             "weight update on (PADDLE_TPU_ZERO "
                             "semantics): the schedule gains the per-"
                             "param all-gathers and the optimizer-state "
                             "ledger reads post-sharding")
    parser.add_argument("--flags", action="store_true",
                        help="cross-reference the README flags table "
                             "against the flags.py DEFS registry and "
                             "exit 1 on missing/stale/duplicate rows")
    parser.add_argument("--provenance", action="store_true",
                        help="lint the opprof lowering provenance: every "
                             "registered op type's scope tag round-trips "
                             "through parse_tag, a real mnist_mlp compile "
                             "covers every live op with a tagged HLO "
                             "cost row, and no paddle_tpu module imports "
                             "from tools/")
    parser.add_argument("--list-passes", action="store_true",
                        help="list every registered pass (name, kind, "
                             "default on/off) and exit")
    parser.add_argument("--timing", action="store_true",
                        help="collect per-pass wall time via the "
                             "telemetry registry (paddle_tpu."
                             "observability) and print the table after "
                             "linting")
    parser.add_argument("-v", "--verbose", action="store_true",
                        help="show INFO findings too")
    args = parser.parse_args(argv)

    if args.list_passes:
        _list_passes()
        return 0

    if args.flags:
        return _flags_doc_lint()

    if args.provenance:
        return _provenance_lint()

    if args.mesh:
        # a Mesh over N>1 axes needs N host devices; force them before
        # jax initializes (lint never touches real accelerators)
        total = 1
        for size in (_parse_mesh_axes(args.mesh) or {}).values():
            total *= max(size, 1)
        if total > 1 and "xla_force_host_platform_device_count" \
                not in os.environ.get("XLA_FLAGS", ""):
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + " --xla_force_host_platform_device_count=%d" % total)

    if args.timing:
        from paddle_tpu import observability

        observability.set_enabled(True)

    reports = []
    if args.program:
        reports.append(_lint_file(args.program, args))
    else:
        builders = _load_book_builders()
        names = args.model or sorted(builders)
        for name in names:
            if name not in builders:
                raise SystemExit(
                    "unknown model %r; known: %s" % (name, sorted(builders)))
            reports.append(_lint_built_model(name, builders[name], args))

    if args.timing:
        _print_timing()

    n_err = sum(len(r.errors) for r in reports)
    n_warn = sum(len(r.warnings) for r in reports)
    print("\nlint: %d program(s), %d error(s), %d warning(s)"
          % (len(reports), n_err, n_warn))
    return 1 if n_err else 0


def _print_timing():
    """Per-pass wall-time table from the telemetry registry: every
    ``analysis.<checker>.ms`` and ``transform.<pass>.ms`` histogram the
    lint run filled."""
    from paddle_tpu import observability

    hists = observability.snapshot()["histograms"]
    rows = [(name, h) for name, h in sorted(hists.items())
            if name.startswith(("analysis.", "transform."))]
    print("\n== per-pass timings ==")
    if not rows:
        print("(no pass timings recorded)")
        return
    print("%-36s %6s %10s %10s" % ("pass", "calls", "total ms", "mean ms"))
    for name, h in rows:
        print("%-36s %6d %10.2f %10.2f"
              % (name[:-3] if name.endswith(".ms") else name,
                 h["count"], h["total"], h["mean"] or 0.0))


if __name__ == "__main__":
    sys.exit(main())
