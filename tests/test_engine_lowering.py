"""The engine's choice of block lowering (``engine.executor.lowering_for``):
which lowering runs, whether ZeRO-1 and the SDC gradient digest ride on
it, and the combination that raises; and a fetched state var that the
step updates, read back after the next step."""

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu import flags
from paddle_tpu.engine.executor import Lowering, lowering_for
from paddle_tpu.framework import Program, program_guard
from paddle_tpu.parallel.mesh import make_mesh

# (is_test, accumulate_steps, remat_segments, on a mesh, sdc) -> the
# decision, with the zero flag on and grad_bucket_mb at 4
DECISIONS = {
    "plain": ((False, 1, 0, False, False),
              Lowering("plain", 1, 0, False, 0.0, False)),
    "mesh_zero": ((False, 1, 0, True, False),
                  Lowering("plain", 1, 0, True, 4.0, False)),
    "mesh_zero_accumulated": ((False, 4, 0, True, False),
                              Lowering("accumulated", 4, 0, False, 0.0,
                                       False)),
    "mesh_zero_remat": ((False, 1, 2, True, False),
                        Lowering("remat", 1, 2, False, 0.0, False)),
    "mesh_zero_test_program": ((True, 1, 0, True, False),
                               Lowering("plain", 1, 0, False, 0.0, False)),
    "accumulated_remat": ((False, 2, 2, False, False), NotImplementedError),
    "sdc_plain": ((False, 1, 0, False, True),
                  Lowering("plain", 1, 0, False, 0.0, True)),
    "sdc_remat": ((False, 1, 2, False, True),
                  Lowering("remat", 1, 2, False, 0.0, False)),
}


@pytest.mark.parametrize("case", list(DECISIONS))
def test_lowering_decision_table(case):
    (is_test, steps, segments, on_mesh, sdc), want = DECISIONS[case]
    mesh = make_mesh({"dp": 2}) if on_mesh else None
    flags.set_flags({"zero": True, "grad_bucket_mb": 4.0})
    try:
        if want is NotImplementedError:
            with pytest.raises(NotImplementedError, match="cannot combine"):
                lowering_for(is_test, steps, segments, mesh, sdc)
        else:
            assert lowering_for(is_test, steps, segments, mesh, sdc) == want
    finally:
        flags.reset_flag("zero")
        flags.reset_flag("grad_bucket_mb")


def _mlp():
    main, startup = Program(), Program()
    with program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[12], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="int64")
        h = fluid.layers.fc(input=x, size=16, act="relu",
                            param_attr=fluid.ParamAttr(name="w1"))
        pred = fluid.layers.fc(input=h, size=4,
                               param_attr=fluid.ParamAttr(name="w2"))
        loss = fluid.layers.mean(fluid.layers.softmax_with_cross_entropy(
            logits=pred, label=y))
        fluid.optimizer.Adam(learning_rate=0.05).minimize(loss)
    return main, startup, loss


@pytest.mark.parametrize("return_numpy", [True, False])
def test_fetched_mutated_state_reads_back_after_the_next_step(return_numpy):
    """``w1`` is read and re-emitted by the step (its state is donated)
    and fetched as well: the first step's fetch still reads the first
    step's value after a second step has run."""
    main, startup, loss = _mlp()
    exe = fluid.Executor()
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    rng = np.random.RandomState(0)
    feeds = [{"x": rng.randn(16, 12).astype(np.float32),
              "y": rng.randint(0, 4, (16, 1)).astype(np.int64)}
             for _ in range(2)]
    start = np.array(scope.get("w1"))
    _, w1_first = exe.run(main, feed=feeds[0], fetch_list=[loss, "w1"],
                          scope=scope, return_numpy=return_numpy)
    after_first = np.array(scope.get("w1"))
    _, w1_second = exe.run(main, feed=feeds[1], fetch_list=[loss, "w1"],
                           scope=scope, return_numpy=return_numpy)
    after_second = np.array(scope.get("w1"))
    assert not np.array_equal(start, after_first)
    assert not np.array_equal(after_first, after_second)
    np.testing.assert_array_equal(np.asarray(w1_first), after_first)
    np.testing.assert_array_equal(np.asarray(w1_second), after_second)
