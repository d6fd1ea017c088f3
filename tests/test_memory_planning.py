"""Liveness analysis (analysis/memory.py): intervals and the peak on
known toy programs, and persistable state pinned for the whole program."""

import paddle_tpu.fluid as fluid
from paddle_tpu import models
from paddle_tpu.analysis.memory import analyze_liveness
from paddle_tpu.framework import Program, program_guard


# -- liveness units ---------------------------------------------------------
def _toy_chain():
    """x -> scale -> a -> scale -> b: two ops, fully known dataflow."""
    main, startup = Program(), Program()
    with program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[4], dtype="float32")
        a = fluid.layers.scale(x, scale=2.0)
        b = fluid.layers.scale(a, scale=3.0)
    return main, a.name, b.name


def test_liveness_intervals_toy_chain():
    main, a_name, b_name = _toy_chain()
    rep = analyze_liveness(main.desc, feed_shapes={"x": (8, 4)})

    x_iv = rep.intervals["x"]
    a_iv = rep.intervals[a_name]
    b_iv = rep.intervals[b_name]
    # x arrives materialized (feed) and dies after its only reader (op 0)
    assert x_iv.start == 0 and x_iv.end == 0
    # a is born by op 0 and read by op 1; b is born by op 1
    assert a_iv.start == 0 and a_iv.end == 1
    assert b_iv.start == 1 and b_iv.end == 1
    # dynamic batch dim resolved from the feed shape: 8*4*4 bytes each
    assert x_iv.nbytes == a_iv.nbytes == b_iv.nbytes == 8 * 4 * 4


def test_liveness_peak_matches_hand_count():
    main, a_name, b_name = _toy_chain()
    rep = analyze_liveness(main.desc, feed_shapes={"x": (8, 4)})
    # at op 0 {x, a} are live; at op 1 {a, b}: peak is two 128-byte
    # buffers either way
    assert rep.peak_bytes == 2 * 8 * 4 * 4
    live_names = {iv.name for iv in rep.live_at(rep.peak_order)}
    assert live_names in ({"x", a_name}, {a_name, b_name})
    top = rep.top_contributors(10)
    assert len(top) == 2 and all(iv.nbytes == 128 for iv in top)


def test_liveness_persistable_pinned_whole_program():
    main, startup, h = models.mnist.get_model(lr=0.1)
    rep = analyze_liveness(
        main.desc, feed_shapes={"img": (16, 784), "label": (16, 1)})
    params = [p.name for p in main.all_parameters()]
    assert params
    n_orders = rep.n_orders
    for p in params:
        iv = rep.intervals[p]
        assert iv.persistable
        assert iv.start == 0 and iv.end == n_orders - 1
    # a weight gradient lives strictly inside the program
    grads = [n for n in rep.intervals
             if n.endswith("@GRAD") and not rep.intervals[n].persistable]
    assert grads
    assert any(rep.intervals[g].start > 0 for g in grads)
