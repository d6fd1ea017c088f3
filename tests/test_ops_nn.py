"""Numeric parity tests for the CNN/transformer core ops vs torch CPU.

Mirrors the reference's OpTest methodology (reference:
python/paddle/fluid/tests/unittests/test_conv2d_op.py,
test_batch_norm_op.py, test_layer_norm_op.py) but uses torch's CPU autograd
as the trusted oracle instead of finite differences for the heavy ops.
"""

import numpy as np
import pytest
import torch
import torch.nn.functional as F

import paddle_tpu.fluid as fluid
from paddle_tpu.framework import Program, program_guard
from paddle_tpu.core.types import convert_np_dtype_to_dtype_


def run_single_op(op_type, inputs, output_slots, attrs=None, grad_inputs=(),
                  loss_slot=None):
    """Build a one-op program (+ mean loss + backward if grad_inputs),
    return dict of fetched outputs and input grads."""
    main = Program()
    startup = Program()
    with program_guard(main, startup):
        block = main.global_block()
        feed = {}
        in_names = {}
        for slot, items in inputs.items():
            names = []
            for name, arr in items:
                arr = np.asarray(arr)
                block.create_var(
                    name=name, shape=list(arr.shape),
                    dtype=convert_np_dtype_to_dtype_(arr.dtype),
                    stop_gradient=(arr.dtype.kind in "iub"),
                )
                feed[name] = arr
                names.append(name)
            in_names[slot] = names
        out_names = {}
        for slot in output_slots:
            n = "out_%s" % slot.lower()
            block.create_var(name=n, shape=None, dtype="float32")
            out_names[slot] = [n]
        block.append_op(type=op_type, inputs=in_names, outputs=out_names,
                        attrs=attrs or {})
        fetch = [out_names[s][0] for s in output_slots]
        if grad_inputs:
            lslot = loss_slot or output_slots[0]
            loss = fluid.layers.mean(block.vars[out_names[lslot][0]])
            fluid.append_backward(loss)
            fetch = fetch + ["%s@GRAD" % g for g in grad_inputs]
        exe = fluid.Executor(fluid.CPUPlace())
        res = exe.run(main, feed=feed, fetch_list=fetch)
    return dict(zip(fetch, res))


def _t(arr):
    t = torch.from_numpy(np.asarray(arr, dtype=np.float32))
    t.requires_grad_(True)
    return t


class TestConv2d:
    @pytest.mark.parametrize("stride,pad,dilation,groups", [
        (1, 0, 1, 1), (2, 1, 1, 1), (1, 1, 2, 1), (1, 1, 1, 2),
    ])
    def test_forward_backward(self, stride, pad, dilation, groups):
        rng = np.random.RandomState(0)
        x = rng.randn(2, 4, 8, 8).astype(np.float32)
        w = rng.randn(6, 4 // groups, 3, 3).astype(np.float32)

        got = run_single_op(
            "conv2d",
            {"Input": [("x", x)], "Filter": [("w", w)]},
            ["Output"],
            attrs={"strides": [stride, stride], "paddings": [pad, pad],
                   "dilations": [dilation, dilation], "groups": groups},
            grad_inputs=["x", "w"],
        )
        tx, tw = _t(x), _t(w)
        ref = F.conv2d(tx, tw, stride=stride, padding=pad,
                       dilation=dilation, groups=groups)
        ref.mean().backward()
        np.testing.assert_allclose(got["out_output"], ref.detach().numpy(),
                                   atol=1e-4, rtol=1e-4)
        np.testing.assert_allclose(got["x@GRAD"], tx.grad.numpy(),
                                   atol=1e-5, rtol=1e-4)
        np.testing.assert_allclose(got["w@GRAD"], tw.grad.numpy(),
                                   atol=1e-5, rtol=1e-4)

    def test_depthwise(self):
        rng = np.random.RandomState(1)
        x = rng.randn(2, 4, 8, 8).astype(np.float32)
        w = rng.randn(4, 1, 3, 3).astype(np.float32)
        got = run_single_op(
            "depthwise_conv2d",
            {"Input": [("x", x)], "Filter": [("w", w)]},
            ["Output"],
            attrs={"strides": [1, 1], "paddings": [1, 1],
                   "dilations": [1, 1], "groups": 4},
            grad_inputs=["x"],
        )
        tx = _t(x)
        ref = F.conv2d(tx, torch.from_numpy(w), padding=1, groups=4)
        ref.mean().backward()
        np.testing.assert_allclose(got["out_output"], ref.detach().numpy(),
                                   atol=1e-4, rtol=1e-4)
        np.testing.assert_allclose(got["x@GRAD"], tx.grad.numpy(),
                                   atol=1e-5, rtol=1e-4)

    @pytest.mark.parametrize("stride,pad,dilation,groups", [
        (2, 1, 1, 1), (1, 0, 1, 1), (2, 1, 1, 2), (1, 1, 2, 1),
        (2, 0, 2, 4),
    ])
    def test_conv2d_transpose(self, stride, pad, dilation, groups):
        rng = np.random.RandomState(2)
        x = rng.randn(2, 4, 8, 8).astype(np.float32)
        w = rng.randn(4, 8 // groups, 3, 3).astype(np.float32)  # IOHW
        got = run_single_op(
            "conv2d_transpose",
            {"Input": [("x", x)], "Filter": [("w", w)]},
            ["Output"],
            attrs={"strides": [stride, stride], "paddings": [pad, pad],
                   "dilations": [dilation, dilation], "groups": groups},
            grad_inputs=["x", "w"],
        )
        tx, tw = _t(x), _t(w)
        ref = F.conv_transpose2d(tx, tw, stride=stride, padding=pad,
                                 dilation=dilation, groups=groups)
        ref.mean().backward()
        np.testing.assert_allclose(got["out_output"], ref.detach().numpy(),
                                   atol=1e-4, rtol=1e-4)
        np.testing.assert_allclose(got["x@GRAD"], tx.grad.numpy(),
                                   atol=1e-5, rtol=1e-4)
        np.testing.assert_allclose(got["w@GRAD"], tw.grad.numpy(),
                                   atol=1e-5, rtol=1e-4)


def _layout_case(op_type):
    """One small call of an op whose lowering is NCHW only:
    (inputs, output slot, attrs)."""
    rng = np.random.RandomState(11)
    if op_type == "pool2d":
        x = rng.randn(2, 3, 8, 8).astype(np.float32)
        return ({"X": [("x", x)]}, "Out",
                {"pooling_type": "max", "ksize": [2, 2], "strides": [2, 2],
                 "paddings": [0, 0]})
    conv = {"strides": [1, 1], "paddings": [1, 1], "dilations": [1, 1],
            "groups": 1}
    if op_type == "conv2d":
        x = rng.randn(2, 4, 8, 8).astype(np.float32)
        w = rng.randn(6, 4, 3, 3).astype(np.float32)
        return ({"Input": [("x", x)], "Filter": [("w", w)]}, "Output", conv)
    x = rng.randint(-127, 128, (2, 4, 8, 8)).astype(np.int8)
    w = rng.randint(-127, 128, (6, 4, 3, 3)).astype(np.int8)
    return ({"Input": [("x", x)], "Filter": [("w", w)]}, "Output",
            dict(conv, scale_x=4.0, scale_w=[float(i + 1) for i in range(6)]))


class TestDataFormat:
    """``data_format`` on conv2d / pool2d / quantized_conv2d: a loaded
    program may say "AnyLayout" (the reference's default), which is
    NCHW; "NHWC" is refused with the op's name, not computed as NCHW on
    NHWC data."""

    @pytest.mark.parametrize(
        "op_type", ["conv2d", "pool2d", "quantized_conv2d"])
    def test_nhwc_is_refused(self, op_type):
        inputs, slot, attrs = _layout_case(op_type)
        with pytest.raises(ValueError, match="%s: data_format 'NHWC'"
                           % op_type):
            run_single_op(op_type, inputs, [slot],
                          attrs=dict(attrs, data_format="NHWC"))

    @pytest.mark.parametrize(
        "op_type", ["conv2d", "pool2d", "quantized_conv2d"])
    def test_anylayout_is_nchw(self, op_type):
        inputs, slot, attrs = _layout_case(op_type)
        fetch = "out_%s" % slot.lower()
        want = run_single_op(op_type, inputs, [slot], attrs=attrs)[fetch]
        got = run_single_op(op_type, inputs, [slot],
                            attrs=dict(attrs, data_format="AnyLayout"))[fetch]
        np.testing.assert_array_equal(got, want)


class TestPool2d:
    @pytest.mark.parametrize("ptype", ["max", "avg"])
    def test_forward_backward(self, ptype):
        rng = np.random.RandomState(3)
        x = rng.randn(2, 3, 8, 8).astype(np.float32)
        got = run_single_op(
            "pool2d", {"X": [("x", x)]}, ["Out"],
            attrs={"pooling_type": ptype, "ksize": [2, 2],
                   "strides": [2, 2], "paddings": [0, 0]},
            grad_inputs=["x"],
        )
        tx = _t(x)
        if ptype == "max":
            ref = F.max_pool2d(tx, 2, 2)
        else:
            ref = F.avg_pool2d(tx, 2, 2)
        ref.mean().backward()
        np.testing.assert_allclose(got["out_out"], ref.detach().numpy(),
                                   atol=1e-5, rtol=1e-5)
        np.testing.assert_allclose(got["x@GRAD"], tx.grad.numpy(),
                                   atol=1e-5, rtol=1e-5)

    def test_global_pooling(self):
        rng = np.random.RandomState(4)
        x = rng.randn(2, 3, 5, 7).astype(np.float32)
        got = run_single_op(
            "pool2d", {"X": [("x", x)]}, ["Out"],
            attrs={"pooling_type": "avg", "ksize": [1, 1],
                   "global_pooling": True},
        )
        np.testing.assert_allclose(
            got["out_out"], x.mean(axis=(2, 3), keepdims=True),
            atol=1e-5, rtol=1e-5)

    def test_pool_padded_avg_exclusive(self):
        rng = np.random.RandomState(5)
        x = rng.randn(1, 2, 7, 7).astype(np.float32)
        got = run_single_op(
            "pool2d", {"X": [("x", x)]}, ["Out"],
            attrs={"pooling_type": "avg", "ksize": [3, 3], "strides": [2, 2],
                   "paddings": [1, 1], "exclusive": True},
        )
        ref = F.avg_pool2d(torch.from_numpy(x), 3, 2, padding=1,
                           count_include_pad=False)
        np.testing.assert_allclose(got["out_out"], ref.numpy(),
                                   atol=1e-5, rtol=1e-5)


class TestBatchNorm:
    def test_train_forward_backward_and_stats(self):
        rng = np.random.RandomState(6)
        x = rng.randn(4, 3, 5, 5).astype(np.float32)
        scale = rng.rand(3).astype(np.float32) + 0.5
        bias = rng.randn(3).astype(np.float32)
        mean0 = np.zeros(3, np.float32)
        var0 = np.ones(3, np.float32)
        momentum = 0.9

        got = run_single_op(
            "batch_norm",
            {"X": [("x", x)], "Scale": [("scale", scale)],
             "Bias": [("bias", bias)], "Mean": [("mean0", mean0)],
             "Variance": [("var0", var0)]},
            ["Y", "MeanOut", "VarianceOut"],
            attrs={"momentum": momentum, "epsilon": 1e-5, "is_test": False},
            grad_inputs=["x", "scale", "bias"], loss_slot="Y",
        )
        tx, ts, tb = _t(x), _t(scale), _t(bias)
        rm = torch.from_numpy(mean0.copy())
        rv = torch.from_numpy(var0.copy())
        ref = F.batch_norm(tx, rm, rv, ts, tb, training=True,
                           momentum=1 - momentum, eps=1e-5)
        ref.mean().backward()
        np.testing.assert_allclose(got["out_y"], ref.detach().numpy(),
                                   atol=1e-4, rtol=1e-4)
        np.testing.assert_allclose(got["x@GRAD"], tx.grad.numpy(),
                                   atol=1e-5, rtol=1e-4)
        np.testing.assert_allclose(got["scale@GRAD"], ts.grad.numpy(),
                                   atol=1e-5, rtol=1e-4)
        np.testing.assert_allclose(got["bias@GRAD"], tb.grad.numpy(),
                                   atol=1e-5, rtol=1e-4)
        batch_mean = x.mean(axis=(0, 2, 3))
        np.testing.assert_allclose(
            got["out_meanout"],
            momentum * mean0 + (1 - momentum) * batch_mean,
            atol=1e-5, rtol=1e-5)

    def test_inference_uses_global_stats(self):
        rng = np.random.RandomState(7)
        x = rng.randn(4, 3, 5, 5).astype(np.float32)
        scale = np.ones(3, np.float32)
        bias = np.zeros(3, np.float32)
        mean0 = rng.randn(3).astype(np.float32)
        var0 = rng.rand(3).astype(np.float32) + 0.5
        got = run_single_op(
            "batch_norm",
            {"X": [("x", x)], "Scale": [("scale", scale)],
             "Bias": [("bias", bias)], "Mean": [("mean0", mean0)],
             "Variance": [("var0", var0)]},
            ["Y"],
            attrs={"momentum": 0.9, "epsilon": 1e-5, "is_test": True},
        )
        ref = (x - mean0.reshape(1, 3, 1, 1)) / np.sqrt(
            var0.reshape(1, 3, 1, 1) + 1e-5)
        np.testing.assert_allclose(got["out_y"], ref, atol=1e-4, rtol=1e-4)


    @pytest.mark.parametrize("is_test", [False, True])
    def test_layer_nhwc_equals_nchw_on_transposed_input(self, is_test):
        """``layers.batch_norm(data_layout="NHWC")``: output, and in
        training the gradients of scale and bias, equal the NCHW layer's
        on the transposed input."""
        rng = np.random.RandomState(12)
        x = rng.randn(4, 3, 5, 6).astype(np.float32)
        scale = rng.rand(3).astype(np.float32) + 0.5
        bias = rng.randn(3).astype(np.float32)
        got = {}
        for layout in ("NCHW", "NHWC"):
            main, startup = Program(), Program()
            with program_guard(main, startup):
                shape = [3, 5, 6] if layout == "NCHW" else [5, 6, 3]
                inp = fluid.layers.data(name="x", shape=shape,
                                        dtype="float32")
                inp.stop_gradient = False
                y = fluid.layers.batch_norm(
                    inp, is_test=is_test, data_layout=layout,
                    param_attr=fluid.ParamAttr(name="bn_scale"),
                    bias_attr=fluid.ParamAttr(name="bn_bias"))
                # a loss that is not symmetric in the positions
                loss = fluid.layers.mean(y * y * inp)
                fetch = [y]
                if not is_test:
                    fluid.append_backward(loss)
                    fetch += ["bn_scale@GRAD", "bn_bias@GRAD", "x@GRAD"]
            exe = fluid.Executor(fluid.CPUPlace())
            scope = fluid.Scope()
            exe.run(startup, scope=scope)
            scope.set("bn_scale", scale)
            scope.set("bn_bias", bias)
            feed = x if layout == "NCHW" else x.transpose(0, 2, 3, 1)
            out = exe.run(main, feed={"x": feed}, fetch_list=fetch,
                          scope=scope)
            if layout == "NHWC":  # back to NCHW to compare
                out[0] = out[0].transpose(0, 3, 1, 2)
                if not is_test:
                    out[3] = out[3].transpose(0, 3, 1, 2)
            got[layout] = out
        assert len(got["NCHW"]) == (1 if is_test else 4)
        for a, b in zip(got["NHWC"], got["NCHW"]):
            np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-4)


class TestLayerNorm:
    def test_forward_backward(self):
        rng = np.random.RandomState(8)
        x = rng.randn(4, 16).astype(np.float32)
        scale = rng.rand(16).astype(np.float32) + 0.5
        bias = rng.randn(16).astype(np.float32)
        got = run_single_op(
            "layer_norm",
            {"X": [("x", x)], "Scale": [("scale", scale)],
             "Bias": [("bias", bias)]},
            ["Y"],
            attrs={"epsilon": 1e-5, "begin_norm_axis": 1},
            grad_inputs=["x", "scale", "bias"], loss_slot="Y",
        )
        tx, ts, tb = _t(x), _t(scale), _t(bias)
        ref = F.layer_norm(tx, (16,), ts, tb, eps=1e-5)
        ref.mean().backward()
        np.testing.assert_allclose(got["out_y"], ref.detach().numpy(),
                                   atol=1e-5, rtol=1e-4)
        np.testing.assert_allclose(got["x@GRAD"], tx.grad.numpy(),
                                   atol=1e-5, rtol=1e-3)
        np.testing.assert_allclose(got["scale@GRAD"], ts.grad.numpy(),
                                   atol=1e-5, rtol=1e-3)


class TestDropout:
    def test_train_mask_statistics_and_test_identity(self):
        rng = np.random.RandomState(9)
        x = np.ones((64, 64), np.float32)
        got = run_single_op(
            "dropout", {"X": [("x", x)]}, ["Out"],
            attrs={"dropout_prob": 0.5,
                   "dropout_implementation": "upscale_in_train"},
        )
        out = got["out_out"]
        kept = out != 0
        assert 0.35 < kept.mean() < 0.65
        np.testing.assert_allclose(out[kept], 2.0, atol=1e-6)

        # is_test via attr
        got = run_single_op(
            "dropout", {"X": [("x", x)]}, ["Out"],
            attrs={"dropout_prob": 0.5, "is_test": True,
                   "dropout_implementation": "upscale_in_train"},
        )
        np.testing.assert_allclose(got["out_out"], x, atol=1e-6)


class TestEmbedding:
    def test_lookup_and_grad(self):
        rng = np.random.RandomState(10)
        table = rng.randn(20, 8).astype(np.float32)
        ids = rng.randint(0, 20, (6, 1)).astype(np.int64)
        got = run_single_op(
            "lookup_table",
            {"W": [("w", table)], "Ids": [("ids", ids)]},
            ["Out"], attrs={},
            grad_inputs=["w"],
        )
        ref = table[ids.reshape(-1)].reshape(6, 1, 8)
        assert got["out_out"].reshape(6, 8).shape == (6, 8)
        np.testing.assert_allclose(
            got["out_out"].reshape(-1, 8), ref.reshape(-1, 8),
            atol=1e-6)
        # grad: scatter-add of upstream (1/out.size each) into rows
        g = got["w@GRAD"]
        expected = np.zeros_like(table)
        up = 1.0 / ref.size
        for i in ids.reshape(-1):
            expected[i] += up
        np.testing.assert_allclose(g, expected, atol=1e-6, rtol=1e-4)


class TestMatmulVariants:
    @pytest.mark.parametrize("ta,tb", [(False, False), (True, False),
                                       (False, True), (True, True)])
    def test_matmul_transpose(self, ta, tb):
        rng = np.random.RandomState(11)
        a = rng.randn(*( (5, 4) if ta else (4, 5) )).astype(np.float32)
        b = rng.randn(*( (6, 5) if tb else (5, 6) )).astype(np.float32)
        got = run_single_op(
            "matmul", {"X": [("a", a)], "Y": [("b", b)]}, ["Out"],
            attrs={"transpose_X": ta, "transpose_Y": tb},
            grad_inputs=["a", "b"],
        )
        ta_, tb_ = _t(a), _t(b)
        ref = (ta_.t() if ta else ta_) @ (tb_.t() if tb else tb_)
        ref.mean().backward()
        np.testing.assert_allclose(got["out_out"], ref.detach().numpy(),
                                   atol=1e-5, rtol=1e-5)
        np.testing.assert_allclose(got["a@GRAD"], ta_.grad.numpy(),
                                   atol=1e-6, rtol=1e-5)
        np.testing.assert_allclose(got["b@GRAD"], tb_.grad.numpy(),
                                   atol=1e-6, rtol=1e-5)

    def test_batched_matmul(self):
        rng = np.random.RandomState(12)
        a = rng.randn(3, 4, 5).astype(np.float32)
        b = rng.randn(3, 5, 6).astype(np.float32)
        got = run_single_op(
            "matmul", {"X": [("a", a)], "Y": [("b", b)]}, ["Out"],
            attrs={}, grad_inputs=["a"],
        )
        ta_, tb_ = _t(a), _t(b)
        ref = ta_ @ tb_
        ref.mean().backward()
        np.testing.assert_allclose(got["out_out"], ref.detach().numpy(),
                                   atol=1e-5, rtol=1e-5)
        np.testing.assert_allclose(got["a@GRAD"], ta_.grad.numpy(),
                                   atol=1e-6, rtol=1e-5)


class TestGroupNorm:
    def test_forward(self):
        rng = np.random.RandomState(13)
        x = rng.randn(2, 8, 4, 4).astype(np.float32)
        scale = rng.rand(8).astype(np.float32) + 0.5
        bias = rng.randn(8).astype(np.float32)
        got = run_single_op(
            "group_norm",
            {"X": [("x", x)], "Scale": [("scale", scale)],
             "Bias": [("bias", bias)]},
            ["Y"], attrs={"groups": 4, "epsilon": 1e-5},
        )
        ref = F.group_norm(torch.from_numpy(x), 4,
                           torch.from_numpy(scale), torch.from_numpy(bias),
                           eps=1e-5)
        np.testing.assert_allclose(got["out_y"], ref.numpy(),
                                   atol=1e-5, rtol=1e-4)


class TestSoftmaxWithCE:
    def test_soft_label_false(self):
        rng = np.random.RandomState(14)
        logits = rng.randn(8, 10).astype(np.float32)
        label = rng.randint(0, 10, (8, 1)).astype(np.int64)
        got = run_single_op(
            "softmax_with_cross_entropy",
            {"Logits": [("logits", logits)], "Label": [("label", label)]},
            ["Loss"], attrs={},
            grad_inputs=["logits"], loss_slot="Loss",
        )
        tl = _t(logits)
        ref = F.cross_entropy(tl, torch.from_numpy(label.reshape(-1)),
                              reduction="none")
        ref.mean().backward()
        np.testing.assert_allclose(got["out_loss"].reshape(-1),
                                   ref.detach().numpy(), atol=1e-5, rtol=1e-4)
        np.testing.assert_allclose(got["logits@GRAD"], tl.grad.numpy(),
                                   atol=1e-6, rtol=1e-4)


class TestGatedShortConv:
    @pytest.mark.parametrize("taps", [3, 4])
    def test_against_a_depthwise_conv1d(self, taps):
        """C * conv(B * x), [B, C, x] the chunks of X: torch's depthwise
        ``conv1d`` over time, ``taps - 1`` zeros before each sequence and
        the overhang cut (a causal ``Conv1d`` as the model's family writes
        it), and its autograd for both gradients."""
        rng = np.random.RandomState(15 + taps)
        z = rng.randn(2, 10, 18).astype(np.float32)
        w = rng.uniform(-0.6, 0.6, (6, taps)).astype(np.float32)
        got = run_single_op(
            "gated_short_conv", {"X": [("z", z)], "Filter": [("w", w)]},
            ["Out"], grad_inputs=["z", "w"])
        tz, tw = _t(z), _t(w)
        b, c, x = tz.chunk(3, dim=-1)
        conv = F.conv1d((b * x).transpose(1, 2), tw.unsqueeze(1),
                        padding=taps - 1, groups=6)[..., :10]
        ref = c * conv.transpose(1, 2)
        ref.mean().backward()
        np.testing.assert_allclose(got["out_out"], ref.detach().numpy(),
                                   atol=1e-5, rtol=1e-5)
        np.testing.assert_allclose(got["z@GRAD"], tz.grad.numpy(),
                                   atol=1e-6, rtol=1e-4)
        np.testing.assert_allclose(got["w@GRAD"], tw.grad.numpy(),
                                   atol=1e-6, rtol=1e-4)

    def test_the_filter_has_to_fit_the_input(self):
        z = np.zeros((1, 4, 10), np.float32)
        w = np.zeros((3, 3), np.float32)
        with pytest.raises(Exception, match="3 x the filter"):
            run_single_op("gated_short_conv",
                          {"X": [("z", z)], "Filter": [("w", w)]}, ["Out"])
