"""paddle_tpu.analysis.transforms — each transform pass rewrites its
target composition (must-rewrite) and leaves a near-miss alone; the
attention rewrite fires on the real bert/transformer programs; a
bert-style program trains to the same loss at opt level 0 and 2; every
transformed desc passes the static verifier with zero errors; the
engine's executable cache evicts by capacity and recency, and its key
holds the program and the call, never the state."""

import pickle

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu import flags, models
from paddle_tpu import observability as obs
from paddle_tpu.analysis import optimize_program, verify_program
from paddle_tpu.analysis.transforms import (
    AttentionFusePass,
    ConstantFoldPass,
    CSEPass,
    ElemwiseActFusePass,
)
from paddle_tpu.framework import Program, convert_np_dtype_to_dtype_


def _fill(block, name, shape=(4,), dtype="float32", value=0.0,
          persistable=False):
    block.create_var(name=name, shape=list(shape), dtype=dtype,
                     persistable=persistable)
    block.append_op(
        type="fill_constant", outputs={"Out": [name]},
        attrs={"shape": list(shape),
               "dtype": int(convert_np_dtype_to_dtype_(dtype)),
               "value": value})


def _op_types(desc):
    return [op.type for op in desc.block(0).ops]


# -- fuse-attention ------------------------------------------------------

def _build_unfused_attention(extra_scores_reader=False):
    """The raw inference composition the pass targets: scores = q @ k^T
    (scaled), probs = softmax(scores), out = probs @ v."""
    prog = Program()
    b = prog.global_block()
    for name in ("q", "k", "v"):
        b.create_var(name=name, shape=[2, 2, 8, 4], dtype="float32")
    b.create_var(name="scores", shape=[2, 2, 8, 8], dtype="float32")
    b.create_var(name="probs", shape=[2, 2, 8, 8], dtype="float32")
    b.create_var(name="out", shape=[2, 2, 8, 4], dtype="float32")
    b.append_op(type="matmul", inputs={"X": ["q"], "Y": ["k"]},
                outputs={"Out": ["scores"]},
                attrs={"transpose_X": False, "transpose_Y": True,
                       "alpha": 0.5})
    b.append_op(type="softmax", inputs={"X": ["scores"]},
                outputs={"Out": ["probs"]}, attrs={"axis": -1})
    b.append_op(type="matmul", inputs={"X": ["probs"], "Y": ["v"]},
                outputs={"Out": ["out"]},
                attrs={"transpose_X": False, "transpose_Y": False,
                       "alpha": 1.0})
    fetches = ["out"]
    if extra_scores_reader:
        b.create_var(name="peek", shape=[2, 2, 8, 8], dtype="float32")
        b.append_op(type="scale", inputs={"X": ["scores"]},
                    outputs={"Out": ["peek"]}, attrs={"scale": 1.0})
        fetches.append("peek")
    return prog, fetches


def test_attention_fuse_must_rewrite():
    prog, fetches = _build_unfused_attention()
    desc, report = optimize_program(
        prog, level=1, feed_names=["q", "k", "v"], fetch_names=fetches)
    assert report.rewrites.get("fuse-attention") == 1
    types = _op_types(desc)
    assert types.count("fused_attention") == 1
    assert "softmax" not in types and "matmul" not in types
    fused = [op for op in desc.block(0).ops
             if op.type == "fused_attention"][0]
    assert fused.attrs["scale"] == 0.5
    assert fused.output("Out") == ["out"]  # fetch name preserved
    rep = verify_program(desc, feed_names=["q", "k", "v"],
                         fetch_names=fetches)
    assert not rep.errors


def test_attention_fuse_near_miss_extra_reader():
    # scores feeds a second consumer -> fusing would lose its value
    prog, fetches = _build_unfused_attention(extra_scores_reader=True)
    desc, report = optimize_program(
        prog, level=1, feed_names=["q", "k", "v"], fetch_names=fetches)
    assert report.rewrites.get("fuse-attention", 0) == 0
    assert "fused_attention" not in _op_types(desc)


# -- fuse-elemwise-act ---------------------------------------------------

def _build_add_act(extra_sum_reader=False):
    prog = Program()
    b = prog.global_block()
    _fill(b, "x", value=1.0)
    _fill(b, "y", value=-2.0)
    b.create_var(name="s", shape=[4], dtype="float32")
    b.create_var(name="out", shape=[4], dtype="float32")
    b.append_op(type="elementwise_add", inputs={"X": ["x"], "Y": ["y"]},
                outputs={"Out": ["s"]}, attrs={"axis": -1})
    b.append_op(type="relu", inputs={"X": ["s"]}, outputs={"Out": ["out"]})
    fetches = ["out"]
    if extra_sum_reader:
        b.create_var(name="peek", shape=[4], dtype="float32")
        b.append_op(type="scale", inputs={"X": ["s"]},
                    outputs={"Out": ["peek"]}, attrs={"scale": 1.0})
        fetches.append("peek")
    return prog, fetches


def test_elemwise_act_fuse_must_rewrite():
    prog, fetches = _build_add_act()
    desc, report = optimize_program(
        prog, level=2, fetch_names=fetches,
        passes=[ElemwiseActFusePass()])
    assert report.rewrites.get("fuse-elemwise-act") == 1
    types = _op_types(desc)
    assert types.count("fused_elemwise_activation") == 1
    assert "elementwise_add" not in types and "relu" not in types
    fused = [op for op in desc.block(0).ops
             if op.type == "fused_elemwise_activation"][0]
    assert list(fused.attrs["functor_list"]) == ["elementwise_add", "relu"]
    assert not verify_program(desc, fetch_names=fetches).errors
    # the fused op computes the same values through its lowering
    exe = fluid.Executor()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        (got,) = exe.run(prog, fetch_list=["out"], opt_level=0)
    np.testing.assert_allclose(got, np.zeros(4, np.float32))


def test_elemwise_act_fuse_near_miss_extra_reader():
    prog, fetches = _build_add_act(extra_sum_reader=True)
    desc, report = optimize_program(
        prog, level=2, fetch_names=fetches,
        passes=[ElemwiseActFusePass()])
    assert report.rewrites.get("fuse-elemwise-act", 0) == 0
    assert "fused_elemwise_activation" not in _op_types(desc)


# -- fold-constants ------------------------------------------------------

def test_fold_constants_must_rewrite():
    prog = Program()
    b = prog.global_block()
    _fill(b, "a", value=2.0)
    _fill(b, "c", value=3.0)
    b.create_var(name="s", shape=[4], dtype="float32")
    b.create_var(name="r", shape=[4], dtype="float32")
    b.append_op(type="elementwise_add", inputs={"X": ["a"], "Y": ["c"]},
                outputs={"Out": ["s"]})
    b.append_op(type="scale", inputs={"X": ["s"]}, outputs={"Out": ["r"]},
                attrs={"scale": 2.0, "bias": 0.0})
    desc, report = optimize_program(
        prog, level=2, fetch_names=["r"], passes=[ConstantFoldPass()])
    assert report.rewrites.get("fold-constants") == 2
    # everything collapsed to the single fill that writes the fetch
    ops = desc.block(0).ops
    assert [op.type for op in ops] == ["fill_constant"]
    assert ops[0].attrs["value"] == 10.0
    assert ops[0].output("Out") == ["r"]
    assert not verify_program(desc, fetch_names=["r"]).errors


def test_fold_constants_near_miss_persistable_output():
    # a persistable output is scope state: its real writer must survive
    prog = Program()
    b = prog.global_block()
    _fill(b, "a", value=2.0)
    b.create_var(name="r", shape=[4], dtype="float32", persistable=True)
    b.append_op(type="scale", inputs={"X": ["a"]}, outputs={"Out": ["r"]},
                attrs={"scale": 2.0, "bias": 0.0})
    desc, report = optimize_program(
        prog, level=2, fetch_names=["r"], passes=[ConstantFoldPass()])
    assert report.rewrites.get("fold-constants", 0) == 0
    assert "scale" in _op_types(desc)


# -- cse -----------------------------------------------------------------

def _build_cse(second_scale=2.0):
    prog = Program()
    b = prog.global_block()
    _fill(b, "x", value=1.5)
    for name in ("a", "b", "c"):
        b.create_var(name=name, shape=[4], dtype="float32")
    b.append_op(type="scale", inputs={"X": ["x"]}, outputs={"Out": ["a"]},
                attrs={"scale": 2.0, "bias": 0.0})
    b.append_op(type="scale", inputs={"X": ["x"]}, outputs={"Out": ["b"]},
                attrs={"scale": second_scale, "bias": 0.0})
    b.append_op(type="elementwise_add", inputs={"X": ["a"], "Y": ["b"]},
                outputs={"Out": ["c"]})
    return prog


def test_cse_must_rewrite():
    prog = _build_cse(second_scale=2.0)  # b is a duplicate of a
    desc, report = optimize_program(
        prog, level=2, fetch_names=["c"], passes=[CSEPass()])
    assert report.rewrites.get("cse") == 1
    types = _op_types(desc)
    assert types.count("scale") == 1
    add = [op for op in desc.block(0).ops
           if op.type == "elementwise_add"][0]
    assert add.input("X") == add.input("Y") == ["a"]
    assert not verify_program(desc, fetch_names=["c"]).errors


def test_cse_near_miss_different_attrs():
    prog = _build_cse(second_scale=3.0)  # same op type, different math
    desc, report = optimize_program(
        prog, level=2, fetch_names=["c"], passes=[CSEPass()])
    assert report.rewrites.get("cse", 0) == 0
    assert _op_types(desc).count("scale") == 2


# -- the real models -----------------------------------------------------

def _bert_unfused(dropout=0.0):
    return models.bert.get_model(
        batch_size=2, seq_len=16, vocab_size=100, d_model=32, n_layers=2,
        n_heads=2, d_inner=64, dropout=dropout, lr=1e-3, max_position=64,
        use_fused_attention=False)


def test_attention_rewrite_fires_on_bert_training():
    main, _, h = _bert_unfused()
    feeds = sorted(models.bert.make_fake_batch(2, 16, 100, 2))
    desc, report = optimize_program(
        main, level=1, feed_names=feeds, fetch_names=[h["loss"].name])
    assert report.rewrites.get("fuse-attention") == 2  # one per layer
    types = _op_types(desc)
    assert types.count("fused_attention") == 2
    assert types.count("fused_attention_grad") == 2
    assert "softmax" not in types
    rep = verify_program(desc, feed_names=feeds,
                         fetch_names=[h["loss"].name])
    assert not rep.errors


def test_attention_rewrite_fires_on_transformer_training():
    main, _, h = models.transformer.get_model(
        batch_size=2, seq_len=16, vocab_size=100, d_model=32, n_heads=2,
        d_inner=64, n_layers=2, dropout=0.0, lr=1e-3,
        use_fused_attention=False)
    feeds = sorted(models.transformer.make_fake_batch(2, 16, 100))
    # 2 encoder self + 2 decoder cross rewrite; the 2 causal decoder
    # self-attentions emit the fused op directly even when unfused is
    # requested (the composition cannot express a structural causal mask)
    desc, report = optimize_program(
        main, level=1, feed_names=feeds, fetch_names=[h["loss"].name])
    assert report.rewrites.get("fuse-attention") == 4
    assert _op_types(desc).count("fused_attention") == 6
    rep = verify_program(desc, feed_names=feeds,
                         fetch_names=[h["loss"].name])
    assert not rep.errors


def test_level1_is_identity_on_hand_fused_bert():
    # the default model already emits fused_attention: nothing to rewrite,
    # and the ORIGINAL desc object comes back (no clone, no cache split)
    main, _, h = models.bert.get_model(
        batch_size=2, seq_len=16, vocab_size=100, d_model=32, n_layers=2,
        n_heads=2, d_inner=64, dropout=0.0, lr=1e-3, max_position=64)
    desc, report = optimize_program(main, level=1,
                                    fetch_names=[h["loss"].name])
    assert report.total == 0
    assert desc is main.desc


def test_bert_trains_to_same_loss_opt0_vs_opt2():
    batch = models.bert.make_fake_batch(2, 16, 100, 2, varlen=True)
    losses = {}
    for level in (0, 2):
        main, startup, h = _bert_unfused(dropout=0.0)
        exe = fluid.Executor()
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe.run(startup)
            steps = []
            for _ in range(3):
                (loss,) = exe.run(main, feed=batch,
                                  fetch_list=[h["loss"]], opt_level=level)
                steps.append(float(np.asarray(loss).ravel()[0]))
            losses[level] = steps
    assert all(np.isfinite(losses[0])) and all(np.isfinite(losses[2]))
    np.testing.assert_allclose(losses[0], losses[2], rtol=1e-5, atol=5e-4)


# -- engine executable cache ---------------------------------------------

def test_engine_cache_lru_capacity_and_recency():
    flags.set_flags({"executable_cache_size": 2})
    try:
        exe = fluid.Executor()  # capacity read at engine construction
        engine = exe.engine
        progs = []
        for mult in (2.0, 3.0, 4.0):
            main = fluid.Program()
            startup = fluid.Program()
            with fluid.program_guard(main, startup):
                x = fluid.layers.data(name="x", shape=[4], dtype="float32")
                y = fluid.layers.scale(x, scale=mult)
            progs.append((main, y))
        feed = {"x": np.ones((2, 4), np.float32)}
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            def run(i):
                (out,) = exe.run(progs[i][0], feed=feed,
                                 fetch_list=[progs[i][1]])
                return out

            np.testing.assert_allclose(run(0), 2.0 * feed["x"])
            keys0 = set(engine._cache)
            assert len(keys0) == 1
            run(1)
            (key_a,) = keys0
            (key_b,) = set(engine._cache) - keys0
            run(0)  # cache hit must refresh recency (move_to_end)
            assert next(reversed(engine._cache)) == key_a
            run(2)  # overflow: capacity 2 evicts the LRU entry -> B
            assert len(engine._cache) == 2
            assert key_a in engine._cache
            assert key_b not in engine._cache
    finally:
        flags.reset_flag("executable_cache_size")


def _two_layer_regression():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[4], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="float32")
        h = fluid.layers.fc(input=x, size=8, act="relu")
        loss = fluid.layers.mean(fluid.layers.square_error_cost(
            fluid.layers.fc(input=h, size=1), y))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    feed = {"x": np.ones((2, 4), np.float32),
            "y": np.ones((2, 1), np.float32)}
    return main, startup, loss, feed


@pytest.mark.parametrize("level", [1, 2, 3])
def test_one_executable_serves_two_scopes(level):
    """The executable cache is keyed on the program and the call, never
    on the state: the same Program run against a second Scope is a cache
    hit at every opt level."""
    main, startup, loss, feed = _two_layer_regression()
    exe = fluid.Executor()
    scopes = [fluid.Scope(), fluid.Scope()]
    for scope in scopes:
        exe.run(startup, scope=scope)
    obs.set_enabled(True)
    obs.reset()
    for scope in scopes:
        exe.run(main, feed=feed, fetch_list=[loss], scope=scope,
                opt_level=level)
    counters = obs.snapshot()["counters"]
    assert counters["engine.cache_miss"] == 1
    assert counters["engine.cache_hit"] == 1


def test_cache_key_pickles_and_holds_no_object_identity():
    """What a persistent executable cache needs of the key: it survives
    a round trip through pickle equal to itself, and no part of it is
    the id() of the scope the step ran against."""
    main, startup, loss, feed = _two_layer_regression()
    exe = fluid.Executor()
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    exe.run(main, feed=feed, fetch_list=[loss], scope=scope)

    def ints(part):
        if isinstance(part, (tuple, list)):
            for p in part:
                yield from ints(p)
        elif isinstance(part, int) and not isinstance(part, bool):
            yield part

    keys = [c._cache_key for c in exe.engine._cache.values()]
    assert len(keys) == 2  # startup and main
    for key in keys:
        assert pickle.loads(pickle.dumps(key)) == key
        assert id(scope) not in set(ints(key))


@pytest.mark.parametrize("how", ["flag", "argument"])
def test_opt_level_above_3_raises(how):
    main, startup, loss, feed = _two_layer_regression()
    exe = fluid.Executor()
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    kwargs = {}
    if how == "flag":
        flags.set_flags({"opt_level": 4})
    else:
        kwargs["opt_level"] = 4
    try:
        with pytest.raises(ValueError, match="0 to 3"):
            exe.run(main, feed=feed, fetch_list=[loss], scope=scope,
                    **kwargs)
    finally:
        flags.reset_flag("opt_level")
