"""Remat lowering (``exe.run(..., remat_segments=s)``): gradients taken
through a jax.checkpoint-segmented forward must match the explicit
``append_backward`` gradient chain (engine/lowering.py lower_block_remat
— the TPU-native form of the reference's memory-optimization passes,
framework/details/memory_optimize_pass.cc)."""

import numpy as np
import pytest

import jax

import paddle_tpu.fluid as fluid
from paddle_tpu.framework import Program, program_guard


def _build_mlp(optimizer="sgd", with_bn=True, with_clip=False,
               dropout=0.0):
    main, startup = Program(), Program()
    with program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[12], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="int64")
        h = fluid.layers.fc(input=x, size=16, act="relu",
                            param_attr=fluid.ParamAttr(name="w1"))
        if with_bn:
            h = fluid.layers.batch_norm(h)
        if dropout:
            h = fluid.layers.dropout(h, dropout_prob=dropout)
        h = fluid.layers.fc(input=h, size=16, act="gelu",
                            param_attr=fluid.ParamAttr(name="w1b"))
        pred = fluid.layers.fc(input=h, size=4,
                               param_attr=fluid.ParamAttr(name="w2"))
        loss = fluid.layers.mean(fluid.layers.softmax_with_cross_entropy(
            logits=pred, label=y))
        if with_clip:
            fluid.clip.set_gradient_clip(
                fluid.clip.GradientClipByGlobalNorm(0.01))
        if optimizer == "adam":
            fluid.optimizer.Adam(learning_rate=0.05).minimize(loss)
        else:
            fluid.optimizer.SGD(learning_rate=0.5).minimize(loss)
        fluid.clip.set_gradient_clip(None)
    return main, startup, loss


def _build_conv():
    main, startup = Program(), Program()
    with program_guard(main, startup):
        img = fluid.layers.data(name="img", shape=[3, 8, 8],
                                dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="int64")
        h = fluid.layers.conv2d(img, num_filters=8, filter_size=3,
                                padding=1, act=None, bias_attr=False,
                                param_attr=fluid.ParamAttr(name="cw1"))
        h = fluid.layers.batch_norm(h, act="relu")
        h = fluid.layers.conv2d(h, num_filters=8, filter_size=3,
                                padding=1, act=None, bias_attr=False,
                                param_attr=fluid.ParamAttr(name="cw2"))
        h = fluid.layers.batch_norm(h, act="relu")
        h = fluid.layers.pool2d(h, pool_size=8, pool_type="avg",
                                global_pooling=True)
        pred = fluid.layers.fc(h, size=4,
                               param_attr=fluid.ParamAttr(name="cw3"))
        loss = fluid.layers.mean(fluid.layers.softmax_with_cross_entropy(
            logits=pred, label=y))
        fluid.optimizer.Momentum(learning_rate=0.1,
                                 momentum=0.9).minimize(loss)
    return main, startup, loss


def _train(build, feeder, param_names, remat_segments, steps=4, seed=7,
           fetch_extra=(), **bkw):
    main, startup, loss = build(**bkw)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    rng = np.random.RandomState(seed)
    with fluid.scope_guard(scope):
        exe.run(startup)
        losses = []
        for _ in range(steps):
            feed = feeder(rng)
            vals = exe.run(main, feed=feed,
                           fetch_list=[loss] + list(fetch_extra),
                           remat_segments=remat_segments)
            losses.append(float(np.asarray(vals[0]).reshape(-1)[0]))
        params = {n: np.asarray(jax.device_get(scope.get(n)))
                  for n in param_names}
    return losses, params


def _mlp_feed(rng, batch=32):
    return {"x": rng.randn(batch, 12).astype(np.float32),
            "y": rng.randint(0, 4, (batch, 1)).astype(np.int64)}


def _conv_feed(rng, batch=8):
    return {"img": rng.randn(batch, 3, 8, 8).astype(np.float32),
            "y": rng.randint(0, 4, (batch, 1)).astype(np.int64)}


@pytest.mark.parametrize("optimizer", ["sgd", "adam"])
def test_remat_matches_explicit_chain_mlp(optimizer):
    names = ("w1", "w1b", "w2")
    l0, p0 = _train(_build_mlp, _mlp_feed, names, 0, optimizer=optimizer)
    l3, p3 = _train(_build_mlp, _mlp_feed, names, 3, optimizer=optimizer)
    np.testing.assert_allclose(l3, l0, rtol=1e-5, atol=1e-6)
    for n in names:
        np.testing.assert_allclose(p3[n], p0[n], rtol=1e-4, atol=1e-6)


def test_remat_matches_with_clip_and_bn():
    names = ("w1", "w2")
    l0, p0 = _train(_build_mlp, _mlp_feed, names, 0, with_clip=True)
    l4, p4 = _train(_build_mlp, _mlp_feed, names, 4, with_clip=True)
    np.testing.assert_allclose(l4, l0, rtol=1e-5, atol=1e-6)
    for n in names:
        np.testing.assert_allclose(p4[n], p0[n], rtol=1e-4, atol=1e-6)


def test_remat_dropout_masks_reproduce():
    """The per-op rng stream ids are identical in both lowerings, so even
    WITH dropout the remat step is numerically the same step."""
    names = ("w1", "w2")
    l0, p0 = _train(_build_mlp, _mlp_feed, names, 0, dropout=0.3)
    l2, p2 = _train(_build_mlp, _mlp_feed, names, 2, dropout=0.3)
    np.testing.assert_allclose(l2, l0, rtol=1e-5, atol=1e-6)
    for n in names:
        np.testing.assert_allclose(p2[n], p0[n], rtol=1e-4, atol=1e-6)


def test_remat_conv_bn_momentum():
    names = ("cw1", "cw2", "cw3")
    l0, p0 = _train(_build_conv, _conv_feed, names, 0)
    l2, p2 = _train(_build_conv, _conv_feed, names, 2)
    np.testing.assert_allclose(l2, l0, rtol=1e-5, atol=1e-6)
    for n in names:
        np.testing.assert_allclose(p2[n], p0[n], rtol=1e-4, atol=1e-5)


def test_remat_bn_running_stats_update():
    """Persistable forward side effects (BN running stats) flow through
    the aux path identically."""
    def run(remat):
        main, startup, loss = _build_mlp()
        exe = fluid.Executor(fluid.CPUPlace())
        scope = fluid.Scope()
        rng = np.random.RandomState(3)
        with fluid.scope_guard(scope):
            exe.run(startup)
            for _ in range(3):
                exe.run(main, feed=_mlp_feed(rng), fetch_list=[loss],
                        remat_segments=remat)
            stats = [np.asarray(jax.device_get(scope.get(n)))
                     for n in sorted(scope.local_var_names())
                     if "batch_norm" in n and ("mean" in n or "variance" in n)]
        assert stats, "no BN running stats found in scope"
        return stats

    for a, b in zip(run(0), run(2)):
        np.testing.assert_allclose(b, a, rtol=1e-5, atol=1e-6)


def test_remat_more_segments_than_ops_clamps():
    names = ("w1",)
    l0, _ = _train(_build_mlp, _mlp_feed, names, 0)
    lbig, _ = _train(_build_mlp, _mlp_feed, names, 1000)
    np.testing.assert_allclose(lbig, l0, rtol=1e-5, atol=1e-6)


def test_remat_through_flash_attention_kernels():
    """remat gradients THROUGH the Pallas path: the fused_attention
    lowering's raw-lse custom_vjp (flash_attention_raw_lse) is what jax
    autodiff differentiates inside the checkpointed segments — parity
    with the explicit fused_attention_grad chain, interpret mode."""
    from paddle_tpu.layers.nn import fused_attention

    def build():
        main, startup = Program(), Program()
        with program_guard(main, startup):
            x = fluid.layers.data(name="x", shape=[2, 128, 16],
                                  dtype="float32")
            t = fluid.layers.data(name="t", shape=[2, 128, 16],
                                  dtype="float32")
            w = fluid.layers.create_parameter([16, 16], "float32",
                                              name="fa_w")
            xp = fluid.layers.matmul(x, w)
            out = fused_attention(xp, xp, xp, causal=True)
            loss = fluid.layers.mean(fluid.layers.square(
                fluid.layers.elementwise_sub(out, t)))
            fluid.optimizer.SGD(learning_rate=0.5).minimize(loss)
        for op in main.desc.global_block().ops:
            if op.type.startswith("fused_attention"):
                op.attrs["force_flash"] = True   # Pallas, interpret
        return main, startup, loss

    rng = np.random.RandomState(0)
    xv = rng.randn(2, 2, 128, 16).astype(np.float32)
    tv = rng.randn(2, 2, 128, 16).astype(np.float32)

    def train(remat):
        main, startup, loss = build()
        exe = fluid.Executor(fluid.CPUPlace())
        scope = fluid.Scope()
        losses = []
        with fluid.scope_guard(scope):
            exe.run(startup)
            scope.set("fa_w", np.eye(16, dtype=np.float32) * 0.5)
            for _ in range(3):
                (l,) = exe.run(main, feed={"x": xv, "t": tv},
                               fetch_list=[loss], remat_segments=remat)
                losses.append(float(np.asarray(l).reshape(-1)[0]))
            w = np.asarray(jax.device_get(scope.get("fa_w")))
        return losses, w

    l0, w0 = train(0)
    l2, w2 = train(2)
    assert l0[-1] < l0[0]
    np.testing.assert_allclose(l2, l0, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(w2, w0, rtol=1e-4, atol=1e-6)


def test_remat_serves_loss_grad_fetch():
    """Fetching the backward-seed var (loss@GRAD) returns the same fill
    constant the explicit chain binds."""
    main, startup, loss = _build_mlp()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        feed = _mlp_feed(np.random.RandomState(0))
        g0 = exe.run(main, feed=feed,
                     fetch_list=[loss.name + "@GRAD"])[0]
        g2 = exe.run(main, feed=feed, fetch_list=[loss.name + "@GRAD"],
                     remat_segments=2)[0]
    np.testing.assert_allclose(np.asarray(g2), np.asarray(g0))


def test_remat_rejects_inference_program():
    main, startup = Program(), Program()
    with program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[4], dtype="float32")
        pred = fluid.layers.fc(x, size=2)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        with pytest.raises(NotImplementedError, match="training program"):
            exe.run(main, feed={"x": np.zeros((2, 4), np.float32)},
                    fetch_list=[pred], remat_segments=2)


def test_remat_rejects_combination_with_accumulation():
    main, startup, loss = _build_mlp()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        with pytest.raises(NotImplementedError, match="cannot combine"):
            exe.run(main, feed=_mlp_feed(np.random.RandomState(0)),
                    fetch_list=[loss], accumulate_steps=2,
                    remat_segments=2)
