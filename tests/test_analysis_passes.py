"""paddle_tpu.analysis — each checker fires on a crafted bad program, a
real training program lints clean, and the executor hook raises before
lowering. The crafted programs isolate one defect each and run only the
checker under test (the full pipeline is exercised by the clean-program
and executor tests)."""

import time

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu import flags
from paddle_tpu.analysis import (
    Severity,
    VerificationError,
    build_graph,
    verify_graph,
    verify_program,
)
from paddle_tpu.analysis.passes import (
    AnalysisContext,
    DeadOpPass,
    GradPairingPass,
    ShapeDtypePass,
    ShardingConsistencyPass,
    UseBeforeDefPass,
    WriteAfterWritePass,
)
from paddle_tpu.core.types import VarType
from paddle_tpu.framework import (
    OpRole,
    Program,
    convert_np_dtype_to_dtype_,
    program_guard,
)

from test_mnist_mlp import build_mlp


def _run_pass(program, pass_obj, **ctx_kwargs):
    ctx = AnalysisContext(**ctx_kwargs)
    return verify_graph(build_graph(program), ctx, passes=[pass_obj])


def _fill(block, name, shape=(4,), dtype="float32", value=0.0,
          declare=True):
    if declare:
        block.create_var(name=name, shape=list(shape), dtype=dtype)
    block.append_op(
        type="fill_constant", outputs={"Out": [name]},
        attrs={"shape": list(shape),
               "dtype": int(convert_np_dtype_to_dtype_(dtype)),
               "value": value})


# -- use-before-def ------------------------------------------------------

def test_use_before_def_undeclared_is_error():
    prog = Program()
    block = prog.global_block()
    block.create_var(name="out", shape=[4], dtype="float32")
    block.append_op(type="relu", inputs={"X": ["missing"]},
                    outputs={"Out": ["out"]})

    report = _run_pass(prog, UseBeforeDefPass())
    assert len(report.errors) == 1
    f = report.errors[0]
    assert "missing" in f.var_names and f.op_type == "relu"


def test_use_before_def_unwritten_nonfeed_is_warning():
    prog = Program()
    block = prog.global_block()
    block.create_var(name="x", shape=[4], dtype="float32")
    block.create_var(name="out", shape=[4], dtype="float32")
    block.append_op(type="relu", inputs={"X": ["x"]},
                    outputs={"Out": ["out"]})

    # x declared but never written and not fed -> WARNING, not ERROR
    report = _run_pass(prog, UseBeforeDefPass(), feed_names=["img"])
    assert not report.errors
    assert len(report.warnings) == 1 and "x" in report.warnings[0].var_names

    # same program with x fed -> clean
    assert not len(_run_pass(prog, UseBeforeDefPass(), feed_names=["x"]))


# -- shape-dtype ---------------------------------------------------------

def test_dtype_clash_float_int_is_error():
    prog = Program()
    block = prog.global_block()
    _fill(block, "a", dtype="float32")
    _fill(block, "b", dtype="int64")
    block.create_var(name="c", shape=[4], dtype="float32")
    block.append_op(type="elementwise_add",
                    inputs={"X": ["a"], "Y": ["b"]},
                    outputs={"Out": ["c"]})

    report = _run_pass(prog, ShapeDtypePass())
    assert any(f.severity == Severity.ERROR
               and set(f.var_names) == {"a", "b"} for f in report)


def test_declared_shape_mismatch_is_warning():
    prog = Program()
    block = prog.global_block()
    _fill(block, "a", shape=(2, 3))
    block.create_var(name="out", shape=[2, 3], dtype="float32")
    block.append_op(type="relu", inputs={"X": ["a"]},
                    outputs={"Out": ["out"]})
    # corrupt the declared shape after the fact — append_op's build-time
    # inference would have fixed it, but a hand-edited or deserialized
    # program carries whatever the desc says
    prog.desc.block(0).vars["out"].shape = [7, 7]

    report = _run_pass(prog, ShapeDtypePass())
    assert not report.errors
    assert any("declared shape" in f.message and "out" in f.var_names
               for f in report.warnings)


# -- waw-hazard ----------------------------------------------------------

def test_waw_hazard_fires():
    prog = Program()
    block = prog.global_block()
    _fill(block, "v", value=1.0)
    _fill(block, "v", value=2.0, declare=False)

    report = _run_pass(prog, WriteAfterWritePass())
    assert len(report.warnings) == 1
    assert "v" in report.warnings[0].var_names


def test_waw_with_intervening_read_is_clean():
    prog = Program()
    block = prog.global_block()
    _fill(block, "v", value=1.0)
    block.create_var(name="r", shape=[4], dtype="float32")
    block.append_op(type="relu", inputs={"X": ["v"]},
                    outputs={"Out": ["r"]})
    _fill(block, "v", value=2.0, declare=False)

    assert not len(_run_pass(prog, WriteAfterWritePass()))


# -- grad-pairing --------------------------------------------------------

def test_orphan_grad_is_error():
    prog = Program()
    block = prog.global_block()
    _fill(block, "x")
    block.create_var(name="ghost@GRAD", shape=[4], dtype="float32")
    block.append_op(type="relu_grad", inputs={"X": ["x"]},
                    outputs={"X@GRAD": ["ghost@GRAD"]},
                    attrs={"op_role": OpRole.Backward})

    report = _run_pass(prog, GradPairingPass())
    assert len(report.errors) == 1
    assert "ghost@GRAD" in report.errors[0].var_names
    assert "orphan" in report.errors[0].message


def test_grad_dtype_mismatch_is_warning():
    prog = Program()
    block = prog.global_block()
    _fill(block, "x", dtype="float32")
    block.create_var(name="x@GRAD", shape=[4], dtype="float32")
    block.append_op(type="relu_grad", inputs={"X": ["x"]},
                    outputs={"X@GRAD": ["x@GRAD"]},
                    attrs={"op_role": OpRole.Backward})
    # stale metadata scenario: the desc claims an int gradient
    prog.desc.block(0).vars["x@GRAD"].dtype = VarType.INT64

    report = _run_pass(prog, GradPairingPass())
    assert not report.errors
    assert any(set(f.var_names) == {"x@GRAD", "x"}
               for f in report.warnings)


# -- dead-op -------------------------------------------------------------

def test_dead_op_fires_with_fetch_names():
    prog = Program()
    block = prog.global_block()
    _fill(block, "live")
    _fill(block, "dead")

    report = _run_pass(prog, DeadOpPass(), fetch_names=["live"])
    assert len(report.warnings) == 1
    assert "dead" in report.warnings[0].var_names

    # without fetch info every terminal op is a potential fetch: silent
    assert not len(_run_pass(prog, DeadOpPass()))


# -- sharding ------------------------------------------------------------

def test_sharding_unknown_axis_is_error():
    from jax.sharding import PartitionSpec
    from paddle_tpu.parallel.mesh import make_mesh
    from paddle_tpu.parallel.sharding import ShardingRules

    prog = Program()
    _fill(prog.global_block(), "fc_w", shape=(8, 8))

    rules = ShardingRules()
    rules.add("fc_w", PartitionSpec(None, "tp"))
    report = _run_pass(prog, ShardingConsistencyPass(),
                       mesh=make_mesh({"dp": 2}), shard_rules=rules)
    assert len(report.errors) == 1
    assert "'tp'" in report.errors[0].message

    # same rule against a mesh that has the axis: no error
    ok = _run_pass(prog, ShardingConsistencyPass(),
                   mesh=make_mesh({"dp": 2, "tp": 2}), shard_rules=rules)
    assert not ok.errors


# -- clean program + executor wiring ------------------------------------

def _build_mlp_training():
    main, startup = Program(), Program()
    with program_guard(main, startup):
        img, label, avg_loss, acc = build_mlp()
        fluid.optimizer.SGD(learning_rate=0.1).minimize(avg_loss)
    return main, startup, avg_loss, acc


def test_clean_program_has_no_findings():
    main, startup, avg_loss, acc = _build_mlp_training()
    report = verify_program(main, feed_names=["img", "label"],
                            fetch_names=[avg_loss.name, acc.name])
    assert not report.errors, report.render()
    assert not report.warnings, report.render()
    assert not len(verify_program(startup))


def test_executor_verify_raises_before_lowering():
    prog = Program()
    block = prog.global_block()
    out = block.create_var(name="out", shape=[4], dtype="float32")
    block.append_op(type="relu", inputs={"X": ["missing"]},
                    outputs={"Out": ["out"]})

    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        with pytest.raises(VerificationError) as ei:
            exe.run(prog, feed={}, fetch_list=[out], verify=True)
    assert "missing" in str(ei.value)


def test_verify_env_flag_default_on():
    prog = Program()
    block = prog.global_block()
    out = block.create_var(name="out", shape=[4], dtype="float32")
    block.append_op(type="relu", inputs={"X": ["missing"]},
                    outputs={"Out": ["out"]})

    flags.set_flags({"verify": True})
    try:
        exe = fluid.Executor(fluid.CPUPlace())
        with fluid.scope_guard(fluid.Scope()):
            with pytest.raises(VerificationError):
                exe.run(prog, feed={}, fetch_list=[out])
            # explicit verify=False overrides the flag; the failure is
            # now the engine's (missing feed), not the verifier's
            with pytest.raises(Exception) as ei:
                exe.run(prog, feed={}, fetch_list=[out], verify=False)
            assert not isinstance(ei.value, VerificationError)
    finally:
        flags.reset_flag("verify")


def test_verifier_overhead_under_5_percent():
    """The verifier runs once per compiled executable; its wall-clock must
    be noise against the mnist_mlp train step it guards (compile
    included)."""
    main, startup, avg_loss, acc = _build_mlp_training()

    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        t0 = time.perf_counter()
        exe.run(startup)
        x = np.random.RandomState(0).randn(64, 784).astype(np.float32)
        y = np.zeros((64, 1), np.int64)
        for _ in range(3):
            exe.run(main, feed={"img": x, "label": y},
                    fetch_list=[avg_loss, acc])
        train_time = time.perf_counter() - t0

    # least of three: one reading is at the mercy of the other test
    # workers sharing the host (read 0.082 s once against 0.025 alone)
    readings = []
    for _ in range(3):
        t0 = time.perf_counter()
        verify_program(main, feed_names=["img", "label"],
                       fetch_names=[avg_loss.name, acc.name])
        readings.append(time.perf_counter() - t0)
    verify_time = min(readings)

    assert verify_time < 0.05 * train_time, (
        "verifier took %.3fs against %.3fs of training" %
        (verify_time, train_time))
