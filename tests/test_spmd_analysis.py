"""Static SPMD analysis (analysis/spmd.py): propagation units on toy
chains, the collective-schedule emission law held EXACTLY against
compiled HLO for the bert and resnet book models under dp and dp×tp
meshes, the spmd-* checkers, ShardingRules.coverage, and the
spmd.prediction_delta seam."""

import re

import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import paddle_tpu.fluid as fluid
from paddle_tpu import flags, models
from paddle_tpu import observability as obs
from paddle_tpu.analysis import VerificationError, verify_program
from paddle_tpu.analysis.spmd import (
    REPLICATION_BLOWUP_BYTES,
    analyze_spmd,
    hlo_collectives,
    measured_collectives,
)
from paddle_tpu.core.desc import ProgramDescData
from paddle_tpu.parallel import ShardingRules, make_mesh


# ---------------------------------------------------------------------------
# toy-chain propagation units (raw descs — no engine, no devices)
# ---------------------------------------------------------------------------

def _toy_desc():
    prog = ProgramDescData()
    b = prog.block(0)
    return prog, b


def test_no_mesh_is_empty_report():
    prog, b = _toy_desc()
    b.create_var("x", shape=[8, 4])
    b.append_op("relu", {"X": ["x"]}, {"Out": ["y"]})
    assert analyze_spmd(prog, mesh=None).empty
    assert analyze_spmd(prog, mesh={"dp": 1}).empty
    assert "no mesh" in analyze_spmd(prog, mesh=None).render()


def test_elementwise_conflict_detected():
    prog, b = _toy_desc()
    b.create_var("a", shape=[8, 16], persistable=True, is_parameter=True)
    b.create_var("b", shape=[8, 16], persistable=True, is_parameter=True)
    b.create_var("out", shape=[8, 16])
    b.append_op("elementwise_add", {"X": ["a"], "Y": ["b"]},
                {"Out": ["out"]})
    rules = ShardingRules([(r"^a$", P("dp")), (r"^b$", P("tp"))])
    rep = analyze_spmd(prog, mesh={"dp": 2, "tp": 2}, shard_rules=rules)
    assert rep.conflicts, "dp-vs-tp on dim 0 must be flagged"
    var, dim, ax_a, ax_b, op_type = rep.conflicts[0]
    assert dim == 0 and op_type == "elementwise_add"
    assert {tuple(ax_a), tuple(ax_b)} == {("dp",), ("tp",)}


def test_unknown_op_is_barrier_and_loses_sharding():
    prog, b = _toy_desc()
    b.create_var("x", shape=[8, 4])
    b.create_var("y", shape=[8, 4])
    b.append_op("alien_op", {"X": ["x"]}, {"Out": ["y"]})
    rep = analyze_spmd(prog, mesh={"dp": 2}, feed_names=["x"],
                       feed_shapes={"x": (8, 4)})
    assert rep.shardings["x"] == (("dp",), ())
    assert not any(rep.shardings["y"])
    assert any(op_type == "alien_op" for op_type, _, _ in rep.barriers)


def test_replication_blowup_near_miss():
    # 1 MiB of f32 = 262144 elements; one row under the threshold stays
    # quiet, at the threshold it fires
    small = [511, 512]   # 511*512*4 = 1046528 < 1 MiB
    big = [512, 512]     # exactly 1 MiB
    for shape, expect in ((small, False), (big, True)):
        prog, b = _toy_desc()
        b.create_var("x", shape=[8, 4])
        b.create_var("y", shape=shape)
        b.append_op("alien_op", {"X": ["x"]}, {"Out": ["y"]})
        rep = analyze_spmd(prog, mesh={"dp": 2}, feed_names=["x"],
                           feed_shapes={"x": (8, 4)})
        assert bool(rep.replication) is expect, (shape, rep.replication)
    assert REPLICATION_BLOWUP_BYTES == 1 << 20


def _mul_chain():
    """x[8,16] @ w[16,4] -> y -> mean -> loss, with hand-written grads."""
    prog, b = _toy_desc()
    b.create_var("x", shape=[8, 16])
    b.create_var("w", shape=[16, 4], persistable=True, is_parameter=True)
    b.create_var("y", shape=[8, 4])
    b.create_var("loss", shape=[1])
    b.create_var("loss@GRAD", shape=[1])
    b.create_var("y@GRAD", shape=[8, 4])
    b.create_var("w@GRAD", shape=[16, 4])
    b.create_var("x@GRAD", shape=[8, 16])
    b.append_op("mul", {"X": ["x"], "Y": ["w"]}, {"Out": ["y"]},
                {"x_num_col_dims": 1, "y_num_col_dims": 1})
    b.append_op("mean", {"X": ["y"]}, {"Out": ["loss"]})
    b.append_op("fill_constant", {}, {"Out": ["loss@GRAD"]})
    b.append_op("mean_grad", {"X": ["y"], "Out@GRAD": ["loss@GRAD"]},
                {"X@GRAD": ["y@GRAD"]})
    b.append_op("mul_grad",
                {"X": ["x"], "Y": ["w"], "Out@GRAD": ["y@GRAD"]},
                {"X@GRAD": ["x@GRAD"], "Y@GRAD": ["w@GRAD"]},
                {"x_num_col_dims": 1, "y_num_col_dims": 1})
    return prog


def test_param_grad_psum_and_forward_mean_psum():
    rep = analyze_spmd(_mul_chain(), mesh={"dp": 2}, feed_names=["x"],
                       feed_shapes={"x": (8, 16)})
    by_var = {c.var: c for c in rep.collectives}
    # the replicated param's grad contracts the batch-sharded dim: one
    # psum over dp, payload = the FULL param bytes (16*4*4)
    assert "w@GRAD" in by_var
    assert by_var["w@GRAD"].axes == ("dp",)
    assert by_var["w@GRAD"].nbytes == 16 * 4 * 4
    assert by_var["w@GRAD"].phase == "backward"
    # the live forward mean over the sharded batch: scalar psum
    assert "loss" in by_var and by_var["loss"].nbytes == 4
    # activation grads emit nothing
    assert "x@GRAD" not in by_var
    assert rep.psum_count == 2


def test_liveness_gates_emission():
    # with an explicit fetch list and NO optimizer consuming w@GRAD, the
    # whole backward chain is dead — its psum must be suppressed, the
    # forward loss psum kept (mirror of the engine's DCE)
    rep = analyze_spmd(_mul_chain(), mesh={"dp": 2}, feed_names=["x"],
                       feed_shapes={"x": (8, 16)}, fetch_names=["loss"])
    assert {c.var for c in rep.collectives} == {"loss"}
    assert rep.suppressed_dead >= 1


def test_row_parallel_mul_emits_forward_psum():
    prog, b = _toy_desc()
    b.create_var("x", shape=[8, 16])
    b.create_var("w", shape=[16, 4], persistable=True, is_parameter=True)
    b.create_var("y", shape=[8, 4])
    b.append_op("mul", {"X": ["x"], "Y": ["w"]}, {"Out": ["y"]},
                {"x_num_col_dims": 1, "y_num_col_dims": 1})
    rules = ShardingRules([(r"^w$", P("tp", None))])  # row-parallel
    rep = analyze_spmd(prog, mesh={"tp": 2}, shard_rules=rules,
                       data_axes=("dp",))
    psums = [c for c in rep.collectives if c.kind == "psum"]
    assert len(psums) == 1 and psums[0].axes == ("tp",)
    assert psums[0].phase == "forward" and psums[0].var == "y"
    assert psums[0].nbytes == 8 * 4 * 4


def test_fetch_of_sharded_var_costs_all_gather():
    prog, b = _toy_desc()
    b.create_var("x", shape=[8, 4])
    b.create_var("y", shape=[8, 4])
    b.append_op("relu", {"X": ["x"]}, {"Out": ["y"]})
    rep = analyze_spmd(prog, mesh={"dp": 2}, feed_names=["x"],
                       feed_shapes={"x": (8, 4)}, fetch_names=["y"])
    ags = [c for c in rep.collectives if c.kind == "all_gather"]
    assert len(ags) == 1 and ags[0].var == "y"
    assert ags[0].nbytes == 8 * 4 * 4  # the full gathered value


def test_per_device_peak_shrinks_and_zero1_ledger():
    main, startup, h = models.mnist.get_model()
    rep = analyze_spmd(main.desc, mesh={"dp": 2},
                       shard_rules=ShardingRules(),
                       feed_shapes={"img": (8, 784), "label": (8, 1)},
                       fetch_names=[h["loss"].name])
    assert 0 < rep.per_device_peak_bytes < rep.replicated_peak_bytes
    # adam moments replicate; ZeRO-1 over dp=2 reclaims half of them
    assert rep.opt_state.replicated_bytes > 0
    assert rep.opt_state.zero1_savings_bytes == \
        rep.opt_state.replicated_bytes // 2
    assert "ZeRO-1" in rep.render()


# ---------------------------------------------------------------------------
# ShardingRules.coverage + the spmd-unsharded-param checker
# ---------------------------------------------------------------------------

def test_coverage_helper():
    main, _, _ = models.mnist.get_model()
    params = sorted(vd.name
                    for vd in main.desc.block(0).vars.values()
                    if vd.is_parameter)
    first = params[0]
    rules = ShardingRules([("^%s$" % re.escape(first), P(None, None)),
                           (r"never_matches_anything", P(None))])
    cov = rules.coverage(main)
    assert first in cov.matched
    assert cov.unmatched  # fc_1/fc_2 weights and every bias fall through
    assert "never_matches_anything" in cov.rules_unused
    # empty table: nothing matched, nothing unused
    empty = ShardingRules().coverage(main.desc)
    assert not empty.matched and not empty.rules_unused
    assert empty.unmatched


def test_unsharded_param_fails_lint():
    main, _, h = models.mnist.get_model()
    mesh = make_mesh({"dp": 2})
    first = sorted(vd.name for vd in main.desc.block(0).vars.values()
                   if vd.is_parameter)[0]
    # deliberately incomplete: matches exactly one param of many
    incomplete = ShardingRules([("^%s$" % re.escape(first),
                                 P(None, None))])
    with pytest.raises(VerificationError) as ei:
        verify_program(main.desc, feed_names=["img", "label"],
                       fetch_names=[h["loss"].name], mesh=mesh,
                       shard_rules=incomplete, raise_on_error=True)
    assert "spmd-unsharded-param" in str(ei.value)
    # an EMPTY table means replicate-everything on purpose: no error
    verify_program(main.desc, feed_names=["img", "label"],
                   fetch_names=[h["loss"].name], mesh=mesh,
                   shard_rules=ShardingRules(), raise_on_error=True)
    # no mesh: checker is silent regardless of the table
    verify_program(main.desc, feed_names=["img", "label"],
                   fetch_names=[h["loss"].name],
                   shard_rules=incomplete, raise_on_error=True)


# ---------------------------------------------------------------------------
# HLO parser units
# ---------------------------------------------------------------------------

_FAKE_HLO = """
  %all-reduce.1 = f32[16,4]{1,0} all-reduce(f32[16,4]{1,0} %p0), channel_id=1
  %all-reduce-start.2 = (f32[8]{0}) all-reduce-start(f32[8]{0} %p1), channel_id=2
  %all-reduce-done.2 = f32[8]{0} all-reduce-done(%all-reduce-start.2)
  %all-reduce.3 = (f32[4]{0}, s32[2]{0}) all-reduce(f32[4]{0} %a, s32[2]{0} %b), channel_id=3
  %all-gather.4 = f32[16,4]{1,0} all-gather(f32[8,4]{1,0} %p2), channel_id=4
  %dot.2 = f32[32,16]{1,0} dot(%x, %y), lhs_contracting_dims={1}
  ROOT %fusion.3 = f32[4,32]{1,0:T(8,128)} fusion(%z), kind=kLoop
  %all-reduce.5 = (f32[32,16]{1,0}, /*index=1*/f32[4,32]{1,0}) all-reduce(%dot.2, /*index=1*/%fusion.3), channel_id=5
  %all-gather.6 = f32[64,16]{1,0} all-gather(%dot.2), channel_id=6, dimensions={0}
"""


def test_hlo_collectives_parser():
    colls = hlo_collectives(_FAKE_HLO)
    by_name = {c["name"]: c for c in colls}
    assert "all-reduce.1" in by_name
    assert by_name["all-reduce.1"]["nbytes"] == 16 * 4 * 4
    # async pair: the -start carries the payload, the -done is skipped
    assert "all-reduce-start.2" in by_name
    assert not any("-done" in n for n in by_name)
    # combined all-reduce over 2 tensors = 2 logical psums
    assert by_name["all-reduce.3"]["n_operands"] == 2
    assert by_name["all-reduce.3"]["nbytes"] == 4 * 4 + 2 * 4
    # operands printed as bare names (what compiled.as_text() gives):
    # multiplicity from the operand list, payload from the operands'
    # definitions — for an all-gather the shard, not the gathered result
    assert by_name["all-reduce.5"]["n_operands"] == 2
    assert by_name["all-reduce.5"]["nbytes"] == (32 * 16 + 4 * 32) * 4
    assert by_name["all-gather.6"]["nbytes"] == 32 * 16 * 4
    m = measured_collectives(_FAKE_HLO)
    assert m["psum_count"] == 6  # 1 + 1(async) + 2(combined) + 2(untyped)
    assert m["all_gather_count"] == 2
    assert m["total_bytes"] == 256 + 32 + 24 + 128 + 2560 + 2048


# ---------------------------------------------------------------------------
# the acceptance bar: predicted schedule == compiled HLO, bert + resnet,
# dp=2 and dp=2×tp=2 (empty rule table = pure data parallelism)
# ---------------------------------------------------------------------------

def _build_model(which):
    rng = np.random.RandomState(0)
    if which == "resnet":
        main, startup, h = models.resnet.get_model(
            dataset="cifar10", depth=20, class_num=10, lr=0.1)
        feed = {"img": rng.randn(8, 3, 32, 32).astype(np.float32),
                "label": rng.randint(0, 10, (8, 1)).astype(np.int64)}
    else:
        # use_fused_attention=False + opt_level=0 below: the shard_map-
        # wrapped flash dispatch reshards discretionarily under tp (see
        # spmd.py docstring), so the exact-match bar uses the plain-op
        # attention graph — the analyzer flags the fused form instead
        kw = dict(d_model=64, n_layers=2, n_heads=2, d_inner=128)
        main, startup, h = models.bert.get_model(
            batch_size=8, seq_len=32, vocab_size=512, dropout=0.0,
            lr=1e-4, max_position=512, use_fused_attention=False, **kw)
        feed = models.bert.make_fake_batch(8, 32, 512, kw["n_heads"])
    return main, startup, h["loss"], feed


@pytest.mark.parametrize("which,axes", [
    ("bert", {"dp": 2}),
    ("bert", {"dp": 2, "tp": 2}),
    ("resnet", {"dp": 2}),
    ("resnet", {"dp": 2, "tp": 2}),
])
def test_predicted_schedule_matches_compiled_hlo(which, axes):
    main, startup, loss, feed = _build_model(which)
    mesh = make_mesh(axes)
    exe = fluid.Executor()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        eng = exe.engine
        feed_names, feed_values = eng._coerce_feed(main.desc.block(0),
                                                   feed)
        compiled = eng.get_compiled(
            main.desc, 0, feed_names, feed_values, [loss.name], False,
            True, False, 1, mesh=mesh, shard_rules=ShardingRules(),
            opt_level=0)
        plan = compiled.spmd_plan  # the engine seam attached it
        assert plan is not None and not plan.empty
        mutated = [eng._state_value(scope, n)
                   for n in compiled.mutated_names]
        readonly = [eng._state_value(scope, n)
                    for n in compiled.readonly_names]
        hlo = compiled.jitted.lower(
            feed_values, mutated, readonly,
            (np.uint32(0), np.uint32(1))).compile().as_text()
    meas = measured_collectives(hlo)
    # counts EXACT; bytes must land within 10% of the HLO shard shapes
    # (empirically they are byte-exact — keep the asserted bar at the
    # acceptance tolerance so dtype-layout drift can't flake CI)
    assert plan.psum_count == meas["psum_count"], (
        which, axes, plan.render())
    predicted, measured = plan.total_bytes, meas["total_bytes"]
    assert measured > 0
    assert abs(predicted - measured) <= 0.10 * measured, (
        which, axes, predicted, measured)


# ---------------------------------------------------------------------------
# the spmd.prediction_delta seam (engine first-run, mesh cache miss)
# ---------------------------------------------------------------------------

def test_prediction_delta_telemetry_at_cache_miss_seam():
    flags.set_flags({"metrics": True, "spmd_predict": True})
    try:
        main, startup, h = models.mnist.get_model()
        rng = np.random.RandomState(0)
        feed = {"img": rng.randn(8, 784).astype(np.float32),
                "label": rng.randint(0, 10, (8, 1)).astype(np.int64)}
        mesh = make_mesh({"dp": 2})
        exe = fluid.Executor()
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe.run(startup)
            for _ in range(2):  # second run must NOT re-emit (first-only)
                exe.run(main, feed=feed, fetch_list=[h["loss"]],
                        mesh=mesh, shard_rules=ShardingRules())
        events = [s for s in obs.spans()
                  if s.name == "spmd.prediction_delta"]
        assert len(events) == 1
        args = events[0].args
        assert args["psums_predicted"] == args["psums_measured"]
        assert args["bytes_predicted"] == args["bytes_measured"]
        assert args["peak_bytes_predicted"] > 0
        assert obs.snapshot()["gauges"]["spmd.measured_psums"] == \
            args["psums_measured"]
    finally:
        flags.reset_flag("metrics")
        flags.reset_flag("spmd_predict")


# ---------------------------------------------------------------------------
# ZeRO-1 sharded weight update: the exact-match bar extends to the
# reduce-scatter/all-gather schedule, the post-sharding ledger, loss
# parity against the replicated update, and bucketed overlap
# ---------------------------------------------------------------------------

def _compiled_schedule(main, startup, loss, feed, axes):
    """Compile at the engine's cache-miss seam and return (plan, measured)
    for the current flag state."""
    mesh = make_mesh(axes)
    exe = fluid.Executor()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        eng = exe.engine
        feed_names, feed_values = eng._coerce_feed(main.desc.block(0),
                                                   feed)
        compiled = eng.get_compiled(
            main.desc, 0, feed_names, feed_values, [loss.name], False,
            True, False, 1, mesh=mesh, shard_rules=ShardingRules(),
            opt_level=0)
        plan = compiled.spmd_plan
        assert plan is not None and not plan.empty
        mutated = [eng._state_value(scope, n)
                   for n in compiled.mutated_names]
        readonly = [eng._state_value(scope, n)
                    for n in compiled.readonly_names]
        hlo = compiled.jitted.lower(
            feed_values, mutated, readonly,
            (np.uint32(0), np.uint32(1))).compile().as_text()
    return plan, measured_collectives(hlo)


@pytest.mark.parametrize("which,axes", [
    ("bert", {"dp": 2}),
    ("resnet", {"dp": 2}),
    pytest.param("bert", {"dp": 2, "tp": 2}, marks=pytest.mark.slow),
    pytest.param("resnet", {"dp": 2, "tp": 2}, marks=pytest.mark.slow),
])
def test_zero1_schedule_matches_compiled_hlo(which, axes):
    """With the sharded update on, the analyzer must predict the whole
    reduce-scatter/all-gather schedule — psum AND all-gather counts
    EXACT against the compiled HLO (XLA's CPU lowering folds the
    reduce-scatter into the all-reduce the parser already counts as a
    psum; the per-param all-gather of the updated shard is the new,
    separately-counted collective)."""
    flags.set_flags({"zero": True})
    try:
        main, startup, loss, feed = _build_model(which)
        plan, meas = _compiled_schedule(main, startup, loss, feed, axes)
    finally:
        flags.reset_flag("zero")
    assert plan.zero1, "plan must record the sharded update was on"
    assert plan.all_gather_count > 0
    assert plan.psum_count == meas["psum_count"], (
        which, axes, plan.render())
    assert plan.all_gather_count == meas["all_gather_count"], (
        which, axes, plan.render())
    assert abs(plan.total_bytes - meas["total_bytes"]) \
        <= 0.10 * meas["total_bytes"], (which, axes)
    # the acceptance ledger: optimizer state is partitioned, only the
    # scalar accumulators (and resnet's excluded BN slots) replicate
    budget = 16 * 1024 if which == "resnet" else 1024
    assert plan.opt_state.replicated_bytes <= budget, (
        which, plan.opt_state.replicated_bytes)


def test_zero1_bucketed_schedule_stays_exact():
    """Bucketed reduction only fences WHEN grads fire — it must not add,
    drop, or resize any collective, so the exact-match bar holds at any
    bucket size and the schedule matches the unbucketed one."""
    schedules = {}
    for bucket in (0.0, 1.0):
        flags.set_flags({"zero": True, "grad_bucket_mb": bucket})
        try:
            main, startup, h = models.mnist.get_model()
            rng = np.random.RandomState(0)
            feed = {"img": rng.randn(8, 784).astype(np.float32),
                    "label": rng.randint(0, 10, (8, 1)).astype(np.int64)}
            plan, meas = _compiled_schedule(main, startup, h["loss"],
                                            feed, {"dp": 2})
        finally:
            flags.reset_flag("zero")
            flags.reset_flag("grad_bucket_mb")
        assert plan.psum_count == meas["psum_count"], plan.render()
        assert plan.all_gather_count == meas["all_gather_count"], \
            plan.render()
        schedules[bucket] = (meas["psum_count"],
                            meas["all_gather_count"])
    assert schedules[0.0] == schedules[1.0]


def test_zero1_ledger_reads_post_sharding():
    """analyze_spmd(zero1=True) reports the POST-sharding optimizer
    ledger: the Adam moments are partitioned so replicated_bytes falls
    to the scalar accumulators, and the render says which world the
    numbers describe."""
    main, startup, h = models.mnist.get_model()
    rep = analyze_spmd(main.desc, mesh={"dp": 2},
                       shard_rules=ShardingRules(),
                       feed_shapes={"img": (8, 784), "label": (8, 1)},
                       fetch_names=[h["loss"].name], zero1=True)
    assert rep.zero1
    base = analyze_spmd(main.desc, mesh={"dp": 2},
                        shard_rules=ShardingRules(),
                        feed_shapes={"img": (8, 784), "label": (8, 1)},
                        fetch_names=[h["loss"].name])
    assert not base.zero1
    # moments move off the replicated ledger; only beta-pow scalars stay
    assert rep.opt_state.replicated_bytes < \
        base.opt_state.replicated_bytes // 100
    assert "post-sharding" in rep.render()


def test_zero1_loss_parity_with_replicated_update():
    """The sharded update is an EXECUTION layout, not a different
    optimizer: training under zero must track the replicated update to
    numerical noise (empirically bit-exact on CPU)."""
    frng = np.random.RandomState(7)
    feed = {"img": frng.randn(8, 784).astype(np.float32),
            "label": frng.randint(0, 10, (8, 1)).astype(np.int64)}
    losses = {}
    for zero in (False, True):
        flags.set_flags({"zero": zero})
        try:
            main, startup, h = models.mnist.get_model()
            exe = fluid.Executor()
            scope = fluid.Scope()
            with fluid.scope_guard(scope):
                exe.run(startup)
                out = []
                for _ in range(3):
                    r = exe.run(main, feed=feed, fetch_list=[h["loss"]],
                                mesh=make_mesh({"dp": 2}),
                                shard_rules=ShardingRules())
                    out.append(float(np.asarray(r[0]).ravel()[0]))
            losses[zero] = out
        finally:
            flags.reset_flag("zero")
    assert np.allclose(losses[False], losses[True],
                       rtol=1e-5, atol=1e-7), losses


@pytest.mark.slow
def test_zero1_loss_parity_resnet():
    """Same parity bar on a book model with Momentum slots and BN
    (whose param groups the plan deliberately leaves replicated)."""
    frng = np.random.RandomState(11)
    feed = {"img": frng.randn(8, 3, 32, 32).astype(np.float32),
            "label": frng.randint(0, 10, (8, 1)).astype(np.int64)}
    losses = {}
    for zero in (False, True):
        flags.set_flags({"zero": zero})
        try:
            main, startup, loss, _ = _build_model("resnet")
            exe = fluid.Executor()
            scope = fluid.Scope()
            with fluid.scope_guard(scope):
                exe.run(startup)
                out = []
                for _ in range(2):
                    r = exe.run(main, feed=feed, fetch_list=[loss],
                                mesh=make_mesh({"dp": 2}),
                                shard_rules=ShardingRules())
                    out.append(float(np.asarray(r[0]).ravel()[0]))
            losses[zero] = out
        finally:
            flags.reset_flag("zero")
    assert np.allclose(losses[False], losses[True],
                       rtol=1e-5, atol=1e-7), losses


# ---------------------------------------------------------------------------
# sync_batch_norm: the distributed-BN op joins the rule table
# ---------------------------------------------------------------------------

def _bn_model(sync):
    main = fluid.Program()
    startup = fluid.Program()
    with fluid.program_guard(main, startup):
        layers = fluid.layers
        img = layers.data("img", shape=[3, 8, 8], dtype="float32")
        label = layers.data("label", shape=[1], dtype="int64")
        conv = layers.conv2d(img, num_filters=4, filter_size=3,
                             padding=1,
                             param_attr=fluid.ParamAttr(name="zbw"))
        bn = (layers.sync_batch_norm if sync else layers.batch_norm)(
            conv, act="relu")
        pool = layers.pool2d(bn, pool_size=8, pool_type="avg")
        fc = layers.fc(pool, size=10,
                       param_attr=fluid.ParamAttr(name="zfw"))
        loss = layers.reduce_mean(
            layers.softmax_with_cross_entropy(fc, label))
        fluid.optimizer.Momentum(learning_rate=0.1,
                                 momentum=0.9).minimize(loss)
    rng = np.random.RandomState(3)
    feed = {"img": rng.randn(8, 3, 8, 8).astype(np.float32),
            "label": rng.randint(0, 10, (8, 1)).astype(np.int64)}
    return main, startup, loss, feed


def test_sync_batch_norm_matches_batch_norm_losses():
    """Under GSPMD, batch_norm already computes GLOBAL batch statistics
    (the partitioner psums the jnp.mean over the batch-sharded x), so
    the explicit sync op must be numerically identical to it."""
    losses = {}
    for sync in (False, True):
        main, startup, loss, feed = _bn_model(sync)
        exe = fluid.Executor()
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe.run(startup)
            out = []
            for _ in range(3):
                r = exe.run(main, feed=feed, fetch_list=[loss],
                            mesh=make_mesh({"dp": 2}),
                            shard_rules=ShardingRules())
                out.append(float(np.asarray(r[0]).ravel()[0]))
        losses[sync] = out
    assert losses[False] == losses[True], losses


def test_sync_batch_norm_schedule_predicted_exactly():
    """The analyzer's batch_norm rule covers the sync alias: two stat
    psums per training BN, schedule exact against the compiled HLO."""
    main, startup, loss, feed = _bn_model(sync=True)
    plan, meas = _compiled_schedule(main, startup, loss, feed, {"dp": 2})
    assert plan.psum_count == meas["psum_count"], plan.render()
    assert plan.all_gather_count == meas["all_gather_count"]
    stat_psums = [c for c in plan.collectives
                  if c.kind == "psum" and "batch_norm" in c.reason]
    assert len(stat_psums) == 2  # mean + var over the dp axis
    assert all(c.axes == ("dp",) for c in stat_psums)


def test_no_seam_without_flag():
    flags.set_flags({"metrics": True})
    try:
        main, startup, h = models.mnist.get_model()
        rng = np.random.RandomState(0)
        feed = {"img": rng.randn(8, 784).astype(np.float32),
                "label": rng.randint(0, 10, (8, 1)).astype(np.int64)}
        exe = fluid.Executor()
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe.run(startup)
            exe.run(main, feed=feed, fetch_list=[h["loss"]],
                    mesh=make_mesh({"dp": 2}),
                    shard_rules=ShardingRules())
        assert not [s for s in obs.spans()
                    if s.name == "spmd.prediction_delta"]
        # but the static plan event still fires on the cache miss
        assert [s for s in obs.spans() if s.name == "spmd_plan"]
    finally:
        flags.reset_flag("metrics")
