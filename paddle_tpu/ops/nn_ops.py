"""NN ops: conv, pool, batch_norm, layer_norm, dropout, embedding...

Reference: paddle/fluid/operators/conv_op.cc (+conv_cudnn_op.cu.cc),
pool_op.cc, batch_norm_op.cc, layer_norm_op.cc, dropout_op.cc,
lookup_table_op.cc. Lowerings emit lax convolutions (MXU) and keep the
public NCHW layout contract; XLA's TPU layout assignment picks the physical
layout, so no data_layout_transform pass is needed (reference:
paddle/fluid/framework/data_layout_transform.cc becomes a no-op concern).
Pre-round record (one v5e, July 2026): an end-to-end NHWC ResNet-50
formulation timed within +0.3% of this NCHW lowering (tools/resnet_probe.py
full-nhwc) — the logical layout is immaterial under XLA:TPU.
PR 31's reading (one v5e, the resnet50.train_b256 cell, five pairs on
shared seeds): the whole-program NHWC/HWIO rewrite read 2,555.1 samples/s
against 2,544.6 for this lowering, +0.41% in every pair and under the
cell's 1% bound; the pass, its flag and the NHWC branches here were
deleted on that reading (PERF.md, Findings PR 31).
"""

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from paddle_tpu.core.registry import register_op, register_no_grad_op
from paddle_tpu.ops.common import (amp_cast, fp32_accum,
                                   lowered_into_a_step, require_nchw, single)


@register_op("conv2d")
def conv2d(ctx, ins, attrs):
    require_nchw(ctx, attrs)
    x = single(ins, "Input")  # NCHW
    w = single(ins, "Filter")  # OIHW (I = C/groups)
    # Under AMP the conv runs wholly in bf16 (the MXU accumulates fp32
    # internally) and the OUTPUT STAYS bf16 — casting activations back to
    # fp32 between ops doubles HBM traffic for every elementwise/norm op
    # in between, which is the actual bottleneck (measured 21% step-time
    # cost on ResNet-50); norms/losses upcast internally where accuracy
    # needs it.
    x, w = amp_cast(x, w)
    return {"Output": [_conv2d_apply(x, w, attrs)]}


def _conv2d_apply(x, w, attrs):
    strides = tuple(attrs.get("strides", [1, 1]))
    paddings = attrs.get("paddings", [0, 0])
    dilations = tuple(attrs.get("dilations", [1, 1]))
    groups = attrs.get("groups", 1)
    pad = [(paddings[0], paddings[0]), (paddings[1], paddings[1])]
    dn = lax.conv_dimension_numbers(
        x.shape, w.shape, ("NCHW", "OIHW", "NCHW"))
    return lax.conv_general_dilated(
        x, w, window_strides=strides, padding=pad, rhs_dilation=dilations,
        dimension_numbers=dn, feature_group_count=groups,
        preferred_element_type=(
            jnp.float32 if x.dtype == jnp.float32 else None),
    )


@register_no_grad_op("conv2d_grad")
def conv2d_grad(ctx, ins, attrs):
    """Direct conv gradients (reference: the hand-written grad kernels of
    conv_cudnn_op.cu.cc / conv_op.h GemmConvGradKernel). The conv is
    bilinear, so each gradient is a ``jax.linear_transpose`` of the conv
    with the other operand fixed — this emits ONLY the transposed
    convolution, never a recomputed forward primal for XLA to CSE away
    (the round-2 per-op jax.vjp residue)."""
    require_nchw(ctx, attrs)
    x = single(ins, "Input")
    w = single(ins, "Filter")
    g = single(ins, "Output@GRAD")
    xa, wa = amp_cast(x, w)
    # cotangent dtype must match the forward output's (bf16 under AMP,
    # fp32 via preferred_element_type otherwise — same rule as the fwd op)
    out_dt = jax.eval_shape(lambda: _conv2d_apply(xa, wa, attrs)).dtype
    g = g.astype(out_dt)
    dx = jax.linear_transpose(lambda xx: _conv2d_apply(xx, wa, attrs), xa)(g)[0]
    dw = jax.linear_transpose(lambda ww: _conv2d_apply(xa, ww, attrs), wa)(g)[0]
    return {"Input@GRAD": [dx.astype(x.dtype)],
            "Filter@GRAD": [dw.astype(w.dtype)]}


@register_no_grad_op("depthwise_conv2d_grad")
def depthwise_conv2d_grad(ctx, ins, attrs):
    x = single(ins, "Input")
    attrs = dict(attrs)
    attrs["groups"] = x.shape[1]
    return conv2d_grad(ctx, ins, attrs)


@register_op("depthwise_conv2d")
def depthwise_conv2d(ctx, ins, attrs):
    x = single(ins, "Input")
    attrs = dict(attrs)
    attrs["groups"] = x.shape[1]
    return conv2d(ctx, ins, attrs)


@register_op("conv2d_transpose")
def conv2d_transpose(ctx, ins, attrs):
    """Gradient-style transposed conv: input-dilate by stride, convolve with
    the spatially-flipped, IO-swapped kernel (reference semantics:
    paddle/fluid/operators/conv_transpose_op.cc; output size
    (H-1)*s - 2p + d*(k-1) + 1)."""
    x = single(ins, "Input")  # NCHW
    w = single(ins, "Filter")  # IOHW (I = C_in, O = C_out/groups)
    strides = tuple(attrs.get("strides", [1, 1]))
    paddings = attrs.get("paddings", [0, 0])
    dilations = tuple(attrs.get("dilations", [1, 1]))
    groups = attrs.get("groups", 1)

    c_in, o_g, kh, kw = w.shape
    # IOHW -> OIHW with grouping: (g, C_in/g, O_g, kh, kw) -> (g*O_g, C_in/g,)
    w_ = w.reshape(groups, c_in // groups, o_g, kh, kw)
    w_ = jnp.transpose(w_, (0, 2, 1, 3, 4)).reshape(
        groups * o_g, c_in // groups, kh, kw)
    w_ = jnp.flip(w_, axis=(2, 3))

    pad = [
        (dilations[0] * (kh - 1) - paddings[0],
         dilations[0] * (kh - 1) - paddings[0]),
        (dilations[1] * (kw - 1) - paddings[1],
         dilations[1] * (kw - 1) - paddings[1]),
    ]
    dn = lax.conv_dimension_numbers(x.shape, w_.shape, ("NCHW", "OIHW", "NCHW"))
    out = lax.conv_general_dilated(
        x, w_,
        window_strides=(1, 1),
        padding=pad,
        lhs_dilation=strides,
        rhs_dilation=dilations,
        dimension_numbers=dn,
        feature_group_count=groups,
    )
    return {"Output": [out]}


@register_op("pool2d")
def pool2d(ctx, ins, attrs):
    require_nchw(ctx, attrs)
    x = single(ins, "X")  # NCHW
    ptype = attrs.get("pooling_type", "max")
    ksize = attrs.get("ksize", [2, 2])
    strides = attrs.get("strides", [1, 1])
    paddings = attrs.get("paddings", [0, 0])
    global_pooling = attrs.get("global_pooling", False)
    exclusive = attrs.get("exclusive", True)
    adaptive = attrs.get("adaptive", False)
    ceil_mode = attrs.get("ceil_mode", False)

    if global_pooling or (adaptive and list(ksize) == [1, 1]):
        if ptype == "max":
            out = jnp.max(x, axis=(2, 3), keepdims=True)
        else:
            # fp32 accumulation for low-precision (H*W-element sums)
            out = jnp.mean(fp32_accum(x), axis=(2, 3),
                           keepdims=True).astype(x.dtype)
        return {"Out": [out]}

    window = (1, 1, ksize[0], ksize[1])
    strides_ = (1, 1, strides[0], strides[1])
    if ceil_mode:
        # pad right/bottom enough that the last partial window is included
        def _extra(in_sz, k, s, p):
            out_sz = -(-(in_sz + 2 * p - k) // s) + 1
            needed = (out_sz - 1) * s + k - in_sz - p
            return max(needed, p)

        eh = _extra(x.shape[2], ksize[0], strides[0], paddings[0])
        ew = _extra(x.shape[3], ksize[1], strides[1], paddings[1])
        sp = ((paddings[0], eh), (paddings[1], ew))
    else:
        sp = ((paddings[0], paddings[0]), (paddings[1], paddings[1]))
    pads = ((0, 0), (0, 0), sp[0], sp[1])

    if ptype == "max":
        init = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else jnp.iinfo(x.dtype).min
        out = lax.reduce_window(x, init, lax.max, window, strides_, pads)
    else:
        summed = lax.reduce_window(x, 0.0, lax.add, window, strides_, pads)
        if exclusive:
            ones = jnp.ones_like(x)
            counts = lax.reduce_window(ones, 0.0, lax.add, window, strides_, pads)
            out = summed / counts
        else:
            out = summed / (ksize[0] * ksize[1])
    return {"Out": [out]}


def _bn_axes(x, layout):
    if layout == "NCHW" and x.ndim == 4:
        return (0, 2, 3), (1, -1, 1, 1)
    if x.ndim == 2:
        return (0,), (1, -1)
    return tuple(range(x.ndim - 1)), (1,) * (x.ndim - 1) + (-1,)


@register_op(
    "batch_norm",
    no_grad_inputs=("Mean", "Variance"),
    grad_needs_outputs=("SavedMean", "SavedVariance"),
)
def batch_norm(ctx, ins, attrs):
    x = single(ins, "X")  # NCHW or ND(C last? paddle: NCHW default)
    scale = single(ins, "Scale")
    bias = single(ins, "Bias")
    mean_in = single(ins, "Mean")
    var_in = single(ins, "Variance")
    momentum = attrs.get("momentum", 0.9)
    eps = attrs.get("epsilon", 1e-5)
    layout = attrs.get("data_layout", "NCHW")
    is_test = attrs.get("is_test", False) or ctx.is_test
    use_global = attrs.get("use_global_stats", False) or is_test

    axes, param_shape = _bn_axes(x, layout)

    # Stats and normalization compute in fp32 even for bf16 activations
    # (bf16 mean/var over a 512×H×W batch loses precision and running
    # stats must stay fp32); inputs/outputs stay in the activation dtype
    # so the op adds no HBM traffic — XLA keeps the fp32 values in
    # registers inside the fusion.
    orig_dtype = x.dtype
    xc = fp32_accum(x)

    if use_global:
        mean = mean_in
        var = var_in
        mean_out, var_out = mean_in, var_in
        saved_mean, saved_var = mean_in, var_in
    else:
        mean = jnp.mean(xc, axis=axes)
        # biased variance (reference uses biased for normalization)
        var = jnp.mean(jnp.square(xc), axis=axes) - jnp.square(mean)
        mean_s = lax.stop_gradient(mean)
        var_s = lax.stop_gradient(var)
        mean_out = momentum * mean_in + (1.0 - momentum) * mean_s
        var_out = momentum * var_in + (1.0 - momentum) * var_s
        saved_mean = mean_s
        saved_var = var_s

    inv_std = lax.rsqrt(var + eps)
    y = (xc - mean.reshape(param_shape)) * inv_std.reshape(param_shape)
    y = y * scale.reshape(param_shape) + bias.reshape(param_shape)
    y = y.astype(orig_dtype)
    return {
        "Y": [y],
        "MeanOut": [mean_out],
        "VarianceOut": [var_out],
        "SavedMean": [saved_mean],
        "SavedVariance": [saved_var],
    }


@register_no_grad_op("batch_norm_grad")
def batch_norm_grad(ctx, ins, attrs):
    """Direct BN backward from the SAVED batch statistics (reference:
    batch_norm_op.cc BatchNormGradKernel, which likewise consumes
    SavedMean/SavedVariance) — the generic jax.vjp path recomputed the
    mean/variance reductions over the full activation instead."""
    x = single(ins, "X")
    scale = single(ins, "Scale")
    g = single(ins, "Y@GRAD")
    saved_mean = single(ins, "SavedMean")
    saved_var = single(ins, "SavedVariance")
    eps = attrs.get("epsilon", 1e-5)
    layout = attrs.get("data_layout", "NCHW")
    is_test = attrs.get("is_test", False) or ctx.is_test
    use_global = attrs.get("use_global_stats", False) or is_test

    axes, param_shape = _bn_axes(x, layout)
    n = 1
    for a in axes:
        n *= x.shape[a]

    xc = fp32_accum(x)
    g32 = fp32_accum(g)
    if saved_mean is None or saved_var is None:
        # program declared BN without its saved-stat outputs (minimal
        # hand-built graphs): recompute the batch stats
        if use_global:
            saved_mean = single(ins, "Mean")
            saved_var = single(ins, "Variance")
        else:
            saved_mean = jnp.mean(xc, axis=axes)
            saved_var = (jnp.mean(jnp.square(xc), axis=axes)
                         - jnp.square(saved_mean))
    mean = saved_mean.reshape(param_shape)
    inv_std = lax.rsqrt(saved_var + eps).reshape(param_shape)
    xhat = (xc - mean) * inv_std

    dbias = jnp.sum(g32, axis=axes)
    dscale = jnp.sum(g32 * xhat, axis=axes)
    dxhat = g32 * scale.reshape(param_shape)
    if use_global:
        # stats are constants: the normalization is an affine map of x
        dx = dxhat * inv_std
    else:
        dx = inv_std * (
            dxhat
            - (dbias.reshape(param_shape) * scale.reshape(param_shape)
               + xhat * dscale.reshape(param_shape)
               * scale.reshape(param_shape)) / n)
    return {"X@GRAD": [dx.astype(x.dtype)],
            "Scale@GRAD": [dscale.astype(scale.dtype)],
            "Bias@GRAD": [dbias.astype(scale.dtype)]}


# sync_batch_norm (reference: sync_batch_norm_op.cu, which all-reduces
# the per-device sums) is batch_norm's natural GSPMD semantics: the
# jnp.mean reductions above run over the batch-sharded activation, so
# the partitioner inserts the cross-replica psums itself and the batch
# statistics are already global. The distributed op is therefore a pure
# alias of the local kernels.
register_op(
    "sync_batch_norm",
    no_grad_inputs=("Mean", "Variance"),
    grad_needs_outputs=("SavedMean", "SavedVariance"),
)(batch_norm)
register_no_grad_op("sync_batch_norm_grad")(batch_norm_grad)


def _fused_attention_args(ctx, ins, attrs):
    """Shared forward/backward argument resolution — the grad op MUST see
    the same dtypes, mask, dropout seed (same per-op rng stream id), and
    dispatch decision the forward saw."""
    q, k, v = amp_cast(single(ins, "Q"), single(ins, "K"), single(ins, "V"))
    lens = single(ins, "SeqLens") if ins.get("SeqLens") else None
    if lens is not None:
        lens = lens.reshape(-1)  # accept [B] or [B, 1] feeds
    rate = float(attrs.get("dropout_rate", 0.0))
    if attrs.get("is_test", False) or ctx.is_test:
        rate = 0.0
    if rate > 0.0:
        seed = jax.random.randint(ctx.rng(), (), 0, jnp.iinfo(jnp.int32).max)
    else:
        seed = 0
    return q, k, v, lens, rate, seed


def _ring_attention_from_attrs(q, k, v, attrs):
    from paddle_tpu.parallel.ring_attention import ring_attention

    return ring_attention(
        q, k, v, axis_name=str(attrs.get("sp_axis", "sp")),
        causal=bool(attrs.get("causal", False)),
        scale=attrs.get("scale", None),
        batch_axis=attrs.get("sp_batch_axis", None) or None)


def _check_ring_supported(rate, lens):
    if rate > 0.0:
        raise NotImplementedError(
            "fused_attention: dropout inside the ring-attention path "
            "is not supported; set dropout_rate=0 when "
            "sequence_parallel=True")
    if lens is not None:
        raise NotImplementedError(
            "fused_attention: seq_lens masks are not supported with "
            "sequence_parallel=True (pad to full length instead)")


@register_op("fused_attention", needs_rng=True, no_grad_inputs=("SeqLens",),
             grad_needs_outputs=("Out", "Lse"))
def fused_attention_op(ctx, ins, attrs):
    """Whole-attention fusion: Pallas flash kernel on TPU, XLA composition
    elsewhere (inputs Q/K/V are [B, H, T, D]; optional SeqLens [B] masks
    keys past each sequence's length — the TPU-native form of the
    reference's additive [B, H, T, T] padding masks). ``dropout_rate``
    is attention-weight dropout executed inside the kernel (counter-based
    hash RNG, reproduced exactly by the backward kernels).

    The kernel path also emits the per-row logsumexp as ``Lse``: with
    (Out, Lse) saved, the registered fused_attention_grad runs the
    backward kernels DIRECTLY instead of differentiating a re-lowered
    forward — the generic-vjp route re-executed the forward custom call
    inside the backward (custom calls never CSE), which the round-5
    seq-2048 trace measured at ~1.3 ms/layer/step of pure waste."""
    from paddle_tpu.kernels.flash_attention import dispatch_attention_lse

    q, k, v, lens, rate, seed = _fused_attention_args(ctx, ins, attrs)
    if bool(attrs.get("sequence_parallel", False)):
        # long-sequence path: exact attention with the T axis sharded over
        # the mesh's sp axis via ppermute ring (parallel/ring_attention.py)
        # — the framework-level entry to sequence/context parallelism
        _check_ring_supported(rate, lens)
        return {"Out": [_ring_attention_from_attrs(q, k, v, attrs)]}
    out, lse = dispatch_attention_lse(
        q, k, v, bool(attrs.get("causal", False)),
        attrs.get("scale", None), lens, rate, seed,
        # test hook: True runs the kernels (interpret mode off-TPU),
        # False the XLA composition. Not a "__" name: the engine strips
        # those before lowerings (lowering.clean_attrs)
        attrs.get("force_flash", None),
        raw_lse=True,  # kernel-native layout: zero-relayout backward read
        window=attrs.get("window", None))
    # the XLA branch's lse binds the program's Lse var too (the direct
    # grad op ignores it there and XLA DCEs it when nothing reads it)
    return {"Out": [out], "Lse": [lse]}


@register_no_grad_op("fused_attention_grad", needs_rng=True)
def fused_attention_grad_op(ctx, ins, attrs):
    """Direct attention backward. When the forward took the Pallas path
    and saved (Out, Lse), this calls the flash backward (one fused
    kernel, or the dQ and dK/dV pair at long sequences) with the saved
    softmax residuals — no forward re-execution.
    Every other branch (ring, XLA composition, a program built without
    the Lse output) differentiates the same forward dispatch inline,
    which is exactly what the generic vjp route did."""
    from paddle_tpu.kernels.flash_attention import (_LSE_LANES,
                                                    _on_tpu,
                                                    dispatch_attention_lse,
                                                    flash_backward_spmd,
                                                    flash_dispatch_ok,
                                                    pick_block)

    q, k, v, lens, rate, seed = _fused_attention_args(ctx, ins, attrs)
    causal = bool(attrs.get("causal", False))
    scale = attrs.get("scale", None)
    window = attrs.get("window", None)
    g = single(ins, "Out@GRAD")
    g = jnp.asarray(g, q.dtype).reshape(q.shape)
    if bool(attrs.get("sequence_parallel", False)):
        _check_ring_supported(rate, lens)
        _, vjp = jax.vjp(
            lambda q_, k_, v_: _ring_attention_from_attrs(q_, k_, v_,
                                                          attrs),
            q, k, v)
        dq, dk, dv = vjp(g)
        return {"Q@GRAD": [dq], "K@GRAD": [dk], "V@GRAD": [dv]}
    Tq, Tk = q.shape[2], k.shape[2]
    force = attrs.get("force_flash", None)
    flash_ok = flash_dispatch_ok(Tq, Tk) if force is None else bool(force)
    out = single(ins, "Out") if ins.get("Out") else None
    lse = single(ins, "Lse") if ins.get("Lse") else None
    if flash_ok and out is not None and lse is not None:
        bq, bk = pick_block(Tq, q.dtype), pick_block(Tk, q.dtype)
        scale_ = scale if scale is not None else q.shape[-1] ** -0.5
        B, H, _, _ = q.shape
        # the forward saved lse in the kernel's own [B*H, Tq, LANES]
        # layout (raw_lse) — this reshape/slice is an identity there, no
        # relayout; it also accepts the public [B, H, Tq] form from an
        # older program desc
        lse_k = jnp.broadcast_to(
            jnp.asarray(lse, jnp.float32).reshape(B * H, Tq, -1)[..., :1],
            (B * H, Tq, _LSE_LANES))
        # spmd-aware entry: under a mesh-targeted trace the backward
        # runs shard_mapped over the same dp/tp decomposition the forward
        # dispatch used; single-device traces call straight in. One
        # Pallas call (dQ, dK and dV from a single pass over the scores)
        # where the sequence lets the dQ accumulator stay in VMEM, the dQ
        # and dK/dV kernels beyond: _flash_backward decides from the
        # shapes, and takes the fused kernel's blocks from the table
        dq, dk, dv = flash_backward_spmd(
            q, k, v, out.astype(q.dtype), lse_k, g, lens,
            seed, causal, scale_, rate, min(bq, Tq), min(bk, Tk),
            not _on_tpu(), window)
        return {"Q@GRAD": [dq], "K@GRAD": [dk], "V@GRAD": [dv]}

    # program lacks the saved residuals (old desc) or took the XLA branch:
    # differentiate the SAME shared dispatch the forward ran
    _, vjp = jax.vjp(
        lambda q_, k_, v_: dispatch_attention_lse(
            q_, k_, v_, causal, scale, lens, rate, seed, force,
            window=window)[0],
        q, k, v)
    dq, dk, dv = vjp(g)
    return {"Q@GRAD": [dq], "K@GRAD": [dk], "V@GRAD": [dv]}


@register_op("layer_norm")
def layer_norm(ctx, ins, attrs):
    x = single(ins, "X")
    scale = single(ins, "Scale")
    bias = single(ins, "Bias")
    eps = attrs.get("epsilon", 1e-5)
    begin = attrs.get("begin_norm_axis", 1)
    axes = tuple(range(begin, x.ndim))
    # fp32 internal compute for low-precision activations (see batch_norm)
    orig_dtype = x.dtype
    x = fp32_accum(x)
    mean = jnp.mean(x, axis=axes, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=axes, keepdims=True)
    y = (x - mean) * lax.rsqrt(var + eps)
    norm_shape = x.shape[begin:]
    if scale is not None:
        y = y * scale.reshape(norm_shape)
    if bias is not None:
        y = y + bias.reshape(norm_shape)
    return {
        "Y": [y.astype(orig_dtype)],
        "Mean": [jnp.squeeze(mean)],
        "Variance": [jnp.squeeze(var)],
    }


@register_op("rms_norm")
def rms_norm(ctx, ins, attrs):
    """Y = X / sqrt(mean(X^2) + eps) * Scale over the last axis, computed
    in float32 and returned in X's dtype."""
    x, scale = single(ins, "X"), single(ins, "Scale")
    x32 = fp32_accum(x)
    y = x32 * lax.rsqrt(jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
                        + attrs.get("epsilon", 1e-6))
    if scale is not None:
        y = y * scale
    return {"Y": [y.astype(x.dtype)]}


@register_op("gated_mlp")
def gated_mlp(ctx, ins, attrs):
    """Out = (silu(X Gate) * (X Up)) Down over the last axis: X [..., d],
    Gate and Up [d, w], Down [w, d]. One op type for a dense layer's MLP
    and a shared expert, so that a trace reads their time by type. bf16
    operands under AMP, the gate's product in float32; the gradient is the
    engine's generic ``jax.vjp`` of this lowering. ``gated_mlp.calls``
    (``metrics`` flag) counts the ops lowered into a step."""
    from paddle_tpu import observability as obs

    x = single(ins, "X")
    x2, wg, wu, wd = amp_cast(x.reshape(-1, x.shape[-1]),
                              single(ins, "Gate"), single(ins, "Up"),
                              single(ins, "Down"))
    pet = None if x2.dtype == jnp.bfloat16 else jnp.float32
    gate = jnp.matmul(x2, wg, preferred_element_type=pet)
    up = jnp.matmul(x2, wu, preferred_element_type=pet)
    hidden = (jax.nn.silu(gate.astype(jnp.float32))
              * up.astype(jnp.float32)).astype(x2.dtype)
    out = jnp.matmul(hidden, wd, preferred_element_type=pet)
    if lowered_into_a_step(ctx, "gated_mlp"):
        obs.inc("gated_mlp.calls")
    return {"Out": [out.reshape(x.shape[:-1] + (wd.shape[-1],))]}


def _shift(x, steps):
    """x [B, T, d] moved ``steps`` positions later along T (earlier where
    negative), zeros coming in: out[t] = x[t - steps]."""
    if steps == 0:
        return x
    t = x.shape[1]
    if steps > 0:
        return jnp.pad(x, ((0, 0), (steps, 0), (0, 0)))[:, :t]
    return jnp.pad(x, ((0, 0), (0, -steps), (0, 0)))[:, -t:]


def _short_conv_chunks(z, d):
    """B, C, x of z [B, T, 3d] in float32, each sliced in z's dtype and
    cast after: a cast of all of z first is a pass of its own on the chip,
    or XLA folds it into the projection before the op, which then writes
    z in float32."""
    return [z[..., i * d:(i + 1) * d].astype(jnp.float32) for i in range(3)]


def _short_conv_parts(z, w):
    """(B, C, x, [p moved L - 1 - j positions later for every tap j], c) in
    float32, p = B * x and c[t] = sum_j w[:, j] * p[t - (L - 1) + j] the
    causal depthwise convolution (p zero before the sequence's first
    position; tap L - 1 multiplies the current position). A moved p is
    made from z moved, an operand, so that no float32 [T, d] is written
    in between."""
    d, taps = w.shape
    b, c_gate, x = _short_conv_chunks(z, d)
    moved = [b * x]
    for steps in range(1, taps):
        b_, _, x_ = _short_conv_chunks(_shift(z, steps), d)
        moved.append(b_ * x_)
    moved.reverse()                     # tap j reads p moved L - 1 - j
    w = w.astype(jnp.float32)
    c = sum(w[:, j] * moved[j] for j in range(taps))
    return b, c_gate, x, moved, c


@jax.custom_vjp
def _gated_short_conv(z, w):
    """C * conv(B * x) for z = [B, C, x] [batch, T, 3d] and the filter w
    [d, L]: the taps as shifted multiply-adds along T, products and sum in
    float32, the result rounded once to z's dtype. The backward keeps z
    and w alone and makes p and c again (the barrier keeps XLA, which sees
    forward and backward in one jitted step, from keeping the forward's
    float32 p and c for it instead); dz is one concatenate of dB, dC and
    dx (autodiff of the three slices would pad each to 3d columns and add
    them). Which of the forms XLA makes most of on the chip: PERF.md,
    Findings PR 35."""
    _, c_gate, _, _, c = _short_conv_parts(z, w)
    return (c_gate * c).astype(z.dtype)


def _gated_short_conv_bwd(res, g):
    z, w, g = lax.optimization_barrier(res + (g,))
    d, taps = w.shape
    b, c_gate, x, moved, c = _short_conv_parts(z, w)
    g32, w32 = g.astype(jnp.float32), w.astype(jnp.float32)
    gc = c_gate * g32

    def gc_earlier(steps):
        """C * g moved ``steps`` positions earlier, from z and g moved."""
        if steps == 0:
            return gc
        return (_short_conv_chunks(_shift(z, -steps), d)[1]
                * _shift(g, -steps).astype(jnp.float32))

    dp = sum(w32[:, j] * gc_earlier(taps - 1 - j) for j in range(taps))
    dw = jnp.stack([jnp.sum(gc * moved[j], axis=(0, 1))
                    for j in range(taps)], axis=1)
    dz = jnp.concatenate([dp * x, g32 * c, dp * b], axis=-1)
    return dz.astype(z.dtype), dw.astype(w.dtype)


_gated_short_conv.defvjp(lambda z, w: (_gated_short_conv(z, w), (z, w)),
                         _gated_short_conv_bwd)


@register_op("gated_short_conv")
def gated_short_conv(ctx, ins, attrs):
    """X [B, T, 3d] (the three chunks B, C, x of an input projection, in
    this order), Filter [d, L] -> Out [B, T, d] = C * conv_L(B * x): a
    causal depthwise convolution of L taps a channel over time (each row
    of the batch a sequence of its own, zeros before its first position),
    gated before and after; no activation, no bias. L is the filter's
    second axis. One op type, so that a trace reads its time by type;
    plain ``jax.numpy``, no kernel. X's dtype in, X's dtype out (bf16
    under AMP, where the projection before it gives bf16); the filter
    stays float32. ``short_conv.calls`` / ``short_conv.taps`` (``metrics``
    flag) count the ops lowered into a step and the sum of their L."""
    from paddle_tpu import observability as obs

    z, w = single(ins, "X"), single(ins, "Filter")
    if z.shape[-1] != 3 * w.shape[0]:
        raise ValueError("gated_short_conv: X's last axis %d is not 3 x the "
                         "filter's %d channels" % (z.shape[-1], w.shape[0]))
    if lowered_into_a_step(ctx, "gated_short_conv"):
        obs.inc("short_conv.calls")
        obs.inc("short_conv.taps", int(w.shape[1]))
    return {"Out": [_gated_short_conv(z, w)]}


def rope_inv_freq(head_dim, attrs):
    """(inverse frequencies [head_dim / 2] float64, the factor on cos and
    sin) of a rotary embedding: ``rope_type`` ``default`` is
    theta^(-2i/d); ``yarn`` (Peng et al., arXiv:2309.00071) blends each
    frequency with its ``factor``-fold interpolation by a linear ramp
    between the dimensions that turn ``beta_fast`` and ``beta_slow`` times
    over the original context."""
    theta = float(attrs.get("rope_theta", 10000.0))
    half = head_dim // 2
    inv = theta ** (-2.0 * np.arange(half, dtype=np.float64) / head_dim)
    kind = attrs.get("rope_type", "default")
    if kind == "default":
        return inv, 1.0
    if kind != "yarn":
        raise ValueError("unknown rope_type %r" % (kind,))
    factor = float(attrs["factor"])
    original = float(attrs["original_max_position_embeddings"])

    def dim_of(turns):
        return (head_dim * np.log(original / (2.0 * np.pi * turns))
                / (2.0 * np.log(theta)))

    low = max(np.floor(dim_of(float(attrs.get("beta_fast", 32.0)))), 0.0)
    high = min(np.ceil(dim_of(float(attrs.get("beta_slow", 1.0)))),
               head_dim - 1.0)
    ramp = np.clip((np.arange(half, dtype=np.float64) - low)
                   / max(high - low, 1e-3), 0.0, 1.0)
    scaling = attrs.get("attention_factor")
    if scaling is None:
        scaling = 0.1 * np.log(factor) + 1.0
    return inv * ((1.0 - ramp) + ramp / factor), float(scaling)


def _rotation(t, d, dtype, attrs):
    """rotate(x) = x*cos + (x @ R)*sin for x [..., t, d] of ``dtype``: R
    the [d, d] matrix of 0 / +-1 with x @ R = [-x2, x1], cos and sin the
    [t, d] float32 tables, each half twice. The product only selects, so
    it is exact: bf16 operands accumulate in float32, float32 operands
    ask for HIGHEST so that the chip does not round them to bf16. Its
    cotangent is the rotation by the negative angle, g*cos - (g @ R)*sin,
    so each way is one pass over the tensor (PERF.md, Findings PR 32)."""
    inv, scaling = rope_inv_freq(d, attrs)
    angle = np.arange(t, dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.tile(np.cos(angle) * scaling, 2), jnp.float32)
    sin = jnp.asarray(np.tile(np.sin(angle) * scaling, 2), jnp.float32)
    half = d // 2
    r = np.zeros((d, d), np.float32)
    r[half:, :half] = -np.eye(half)
    r[:half, half:] = np.eye(half)
    r = jnp.asarray(r, dtype)
    precision = lax.Precision.HIGHEST if dtype == jnp.float32 else None

    def turn(x, combine):
        turned = jnp.dot(x, r, precision=precision,
                         preferred_element_type=jnp.float32)
        return combine(fp32_accum(x) * cos, turned * sin).astype(x.dtype)

    @jax.custom_vjp
    def rotate(x):
        return turn(x, jnp.add)

    rotate.defvjp(lambda x: (turn(x, jnp.add), None),
                  lambda _, g: (turn(g, jnp.subtract),))
    return rotate


@register_op("rotary_embedding")
def rotary_embedding(ctx, ins, attrs):
    """Rotate every X [B, H, T, D] by its position 0..T-1 over the whole
    head, halves convention: x*cos + [-x2, x1]*sin. The tables come from
    the attributes (``rope_inv_freq``) in float64 at lowering; the
    rotation is float32, the result X's dtype. ``rope.rotations``
    (``metrics`` flag) counts the tensors rotated in a lowered step."""
    from paddle_tpu import observability as obs

    outs, rotations = [], {}
    for x in ins["X"]:
        key = (x.shape[-2], x.shape[-1], x.dtype)
        if key not in rotations:      # Q and K share one pair of tables
            rotations[key] = _rotation(*key, attrs)
        outs.append(rotations[key](x))
    if lowered_into_a_step(ctx, "rotary_embedding"):
        obs.inc("rope.rotations", len(outs))
    return {"Out": outs}


@register_op("dropout", needs_rng=True)
def dropout(ctx, ins, attrs):
    x = single(ins, "X")
    p = attrs.get("dropout_prob", 0.5)
    is_test = attrs.get("is_test", False) or ctx.is_test
    impl = attrs.get("dropout_implementation", "downgrade_in_infer")
    if is_test:
        if impl == "upscale_in_train":
            return {"Out": [x], "Mask": [jnp.ones_like(x)]}
        return {"Out": [x * (1.0 - p)], "Mask": [jnp.ones_like(x)]}
    from paddle_tpu.ops.common import hash_keep_mask

    keep = hash_keep_mask(ctx.rng(), x.shape, p)
    mask = keep.astype(x.dtype)
    if impl == "upscale_in_train":
        out = jnp.where(keep, x / (1.0 - p), 0.0).astype(x.dtype)
    else:
        out = x * mask
    return {"Out": [out], "Mask": [mask]}


@register_op("lookup_table", no_grad_inputs=("Ids",))
def lookup_table(ctx, ins, attrs):
    from paddle_tpu.ops.common import flatten_lookup_ids, zero_padding_rows

    w = single(ins, "W")
    flat_ids = flatten_lookup_ids(single(ins, "Ids"))
    out = jnp.take(w, flat_ids, axis=0)
    out = zero_padding_rows(flat_ids, out, attrs.get("padding_idx", -1))
    return {"Out": [out]}


@register_no_grad_op("lookup_table_grad")
def lookup_table_grad(ctx, ins, attrs):
    """Explicit table gradient (reference: lookup_table_op.cc grad kernel +
    selected_rows path, framework/selected_rows.h:32). With is_sparse=True
    the gradient is a SelectedRows value (rows = the batch's ids, values =
    the incoming output grads) — no table-sized tensor is ever built; the
    optimizer lowerings consume it with row-wise scatter updates."""
    from paddle_tpu.core.selected_rows import SelectedRows
    from paddle_tpu.ops.common import flatten_lookup_ids, zero_padding_rows

    w = single(ins, "W")
    og = single(ins, "Out@GRAD")
    flat_ids = flatten_lookup_ids(single(ins, "Ids"))
    rows = flat_ids.reshape(-1).astype(jnp.int32)
    vals = og.reshape((rows.shape[0],) + tuple(w.shape[1:])).astype(w.dtype)
    vals = zero_padding_rows(rows, vals, attrs.get("padding_idx", -1))
    if attrs.get("is_sparse", False):
        return {"W@GRAD": [SelectedRows(rows, vals, w.shape[0])]}
    dense = jnp.zeros_like(w).at[rows].add(vals)
    return {"W@GRAD": [dense]}


@register_op("lrn")
def lrn(ctx, ins, attrs):
    x = single(ins, "X")  # NCHW
    n = attrs.get("n", 5)
    k = attrs.get("k", 2.0)
    alpha = attrs.get("alpha", 1e-4)
    beta = attrs.get("beta", 0.75)
    sq = jnp.square(x)
    half = n // 2
    # sum over channel window via padded cumulative trick
    padded = jnp.pad(sq, ((0, 0), (half, half), (0, 0), (0, 0)))
    window = sum(
        padded[:, i : i + x.shape[1], :, :] for i in range(n)
    )
    return {"Out": [x / jnp.power(k + alpha * window, beta)],
            "MidOut": [k + alpha * window]}


@register_op("l2_normalize")
def l2_normalize(ctx, ins, attrs):
    x = single(ins, "X")
    axis = attrs.get("axis", -1)
    eps = attrs.get("epsilon", 1e-10)
    norm = jnp.sqrt(jnp.sum(jnp.square(x), axis=axis, keepdims=True))
    return {"Out": [x / jnp.maximum(norm, eps)], "Norm": [norm]}


@register_op("norm")
def norm(ctx, ins, attrs):
    x = single(ins, "X")
    axis = attrs.get("axis", -1)
    eps = attrs.get("epsilon", 1e-10)
    norm_v = jnp.sqrt(jnp.sum(jnp.square(x), axis=axis, keepdims=True) + eps)
    return {"Out": [x / norm_v], "Norm": [norm_v]}


@register_op("group_norm")
def group_norm(ctx, ins, attrs):
    x = single(ins, "X")  # NCHW
    scale = single(ins, "Scale")
    bias = single(ins, "Bias")
    groups = attrs.get("groups", 1)
    eps = attrs.get("epsilon", 1e-5)
    n, c = x.shape[0], x.shape[1]
    g = x.reshape(n, groups, c // groups, *x.shape[2:])
    axes = tuple(range(2, g.ndim))
    mean = jnp.mean(g, axis=axes, keepdims=True)
    var = jnp.mean(jnp.square(g - mean), axis=axes, keepdims=True)
    y = ((g - mean) * lax.rsqrt(var + eps)).reshape(x.shape)
    pshape = (1, c) + (1,) * (x.ndim - 2)
    if scale is not None:
        y = y * scale.reshape(pshape)
    if bias is not None:
        y = y + bias.reshape(pshape)
    return {"Y": [y], "Mean": [jnp.squeeze(mean)], "Variance": [jnp.squeeze(var)]}


def _interp_src(out_n, in_n, align_corners, align_mode):
    """Source coordinates per output index (reference:
    operators/interpolate_op.h — align_corners uses the (in-1)/(out-1)
    ratio; align_mode 1 is src = ratio*dst, mode 0 the half-pixel
    src = ratio*(dst+0.5)-0.5)."""
    i = jnp.arange(out_n, dtype=jnp.float32)
    if align_corners:
        ratio = (in_n - 1) / float(max(out_n - 1, 1))
        return i * ratio
    ratio = in_n / float(out_n)
    if align_mode == 1:
        return jnp.clip(i * ratio, 0.0, in_n - 1.0)
    return jnp.clip((i + 0.5) * ratio - 0.5, 0.0, in_n - 1.0)


@register_op("bilinear_interp")
def bilinear_interp(ctx, ins, attrs):
    x = single(ins, "X")  # NCHW
    out_h, out_w = attrs.get("out_h"), attrs.get("out_w")
    ac = bool(attrs.get("align_corners", True))
    am = int(attrs.get("align_mode", 1))
    H, W = x.shape[2], x.shape[3]
    sy = _interp_src(out_h, H, ac, am)
    sx = _interp_src(out_w, W, ac, am)
    y0 = jnp.floor(sy).astype(jnp.int32)
    x0 = jnp.floor(sx).astype(jnp.int32)
    y1 = jnp.minimum(y0 + 1, H - 1)
    x1 = jnp.minimum(x0 + 1, W - 1)
    wy = (sy - y0)[None, None, :, None]
    wx = (sx - x0)[None, None, None, :]
    v00 = x[:, :, y0][:, :, :, x0]
    v01 = x[:, :, y0][:, :, :, x1]
    v10 = x[:, :, y1][:, :, :, x0]
    v11 = x[:, :, y1][:, :, :, x1]
    out = (v00 * (1 - wy) * (1 - wx) + v01 * (1 - wy) * wx
           + v10 * wy * (1 - wx) + v11 * wy * wx)
    return {"Out": [out.astype(x.dtype)]}


@register_op("nearest_interp")
def nearest_interp(ctx, ins, attrs):
    x = single(ins, "X")
    out_h, out_w = attrs.get("out_h"), attrs.get("out_w")
    ac = bool(attrs.get("align_corners", True))
    H, W = x.shape[2], x.shape[3]
    if ac:
        iy = jnp.round(jnp.arange(out_h) * (H - 1)
                       / max(out_h - 1, 1)).astype(jnp.int32)
        ix = jnp.round(jnp.arange(out_w) * (W - 1)
                       / max(out_w - 1, 1)).astype(jnp.int32)
    else:
        iy = jnp.floor(jnp.arange(out_h) * (H / out_h)).astype(jnp.int32)
        ix = jnp.floor(jnp.arange(out_w) * (W / out_w)).astype(jnp.int32)
    iy = jnp.clip(iy, 0, H - 1)
    ix = jnp.clip(ix, 0, W - 1)
    return {"Out": [x[:, :, iy][:, :, :, ix]]}


@register_op("prelu")
def prelu(ctx, ins, attrs):
    x = single(ins, "X")
    alpha = single(ins, "Alpha")
    mode = attrs.get("mode", "all")
    if mode == "all":
        a = alpha.reshape(())
    elif mode == "channel":
        a = alpha.reshape((1, -1) + (1,) * (x.ndim - 2))
    else:
        a = alpha.reshape((1,) + x.shape[1:])
    return {"Out": [jnp.where(x > 0, x, a * x)]}


@register_op("maxout")
def maxout(ctx, ins, attrs):
    x = single(ins, "X")  # NCHW
    groups = attrs.get("groups")
    n, c, h, w = x.shape
    out = x.reshape(n, c // groups, groups, h, w).max(axis=2)
    return {"Out": [out]}
