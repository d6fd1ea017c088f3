"""Quantization ops.

Reference: the xiaolil1 fork's headline feature — MKL-DNN INT8 inference
(paddle/fluid/operators/mkldnn/quantize_mkldnn_op.cc,
conv_mkldnn_op.cc:287 ComputeINT8) and the QAT fake-quant ops
(operators/fake_quantize_op.cc). TPU-native: fake-quant trains with a
straight-through estimator (identity vjp falls out of the
x + stop_gradient(q(x) - x) formulation), and the frozen int8 path runs
real int8 MXU contractions via lax.dot/conv with int32 accumulation.
"""

import jax
import jax.numpy as jnp
from jax import lax

from paddle_tpu.core.registry import register_op, register_no_grad_op
from paddle_tpu.ops.common import require_nchw, single


def _qrange(bits):
    return float(2 ** (bits - 1) - 1)


def _ste_quant(x, scale, bits):
    """Simulated quantization with straight-through gradient."""
    qmax = _qrange(bits)
    s = jnp.maximum(scale, 1e-8)
    q = jnp.clip(jnp.round(x / s * qmax), -qmax, qmax) * s / qmax
    return x + lax.stop_gradient(q - x)


@register_op("fake_quantize_abs_max")
def fake_quantize_abs_max(ctx, ins, attrs):
    x = single(ins, "X")
    bits = int(attrs.get("bit_length", 8))
    scale = lax.stop_gradient(jnp.max(jnp.abs(x)))
    out = _ste_quant(x, scale, bits)
    return {"Out": [out], "OutScale": [scale.reshape(1)]}


@register_op(
    "fake_quantize_moving_average_abs_max",
    no_grad_inputs=("InScale",),
    inplace_map={"OutScale": "InScale"},
)
def fake_quantize_moving_average_abs_max(ctx, ins, attrs):
    x = single(ins, "X")
    in_scale = single(ins, "InScale")
    bits = int(attrs.get("bit_length", 8))
    rate = float(attrs.get("moving_rate", 0.9))
    cur = jnp.max(jnp.abs(x)).reshape(1)
    if attrs.get("is_test", False) or ctx.is_test:
        scale = in_scale
    else:
        scale = rate * in_scale + (1.0 - rate) * cur
    scale = lax.stop_gradient(scale)
    out = _ste_quant(x, scale.reshape(()), bits)
    return {"Out": [out], "OutScale": [scale]}


@register_op("fake_dequantize_max_abs")
def fake_dequantize_max_abs(ctx, ins, attrs):
    x = single(ins, "X")
    scale = single(ins, "Scale")
    qmax = float(attrs.get("max_range", _qrange(8)))
    return {"Out": [x * scale.reshape(()) / qmax]}


# -- frozen INT8 inference path --------------------------------------------

def _native_int8():
    """Whether quantized_* ops contract in native int8 (int32 accumulate)
    or in exact fp32 emulation. Per-call flag read happens at TRACE time
    (the choice is baked into the compiled executable, keyed by the
    engine cache). On the CPU backend XLA's int8 GEMM/conv codegen is
    5-50x slower than fp32, while the emulation is bit-exact — int8
    products are <= 127^2 and the per-dot partial sums of any sane
    contraction stay far inside the f32 24-bit mantissa — so 'auto'
    emulates on CPU and goes native (MXU) everywhere else."""
    from paddle_tpu import flags

    mode = str(flags.get_flag("int8_native")).strip().lower()
    if mode in ("", "auto"):
        return jax.default_backend() != "cpu"
    return mode not in ("0", "false")


def _scale_param(attrs, key, default=1.0):
    """Scalar or per-channel scale attr -> float | f32 vector."""
    v = attrs.get(key, default)
    if isinstance(v, (list, tuple)):
        return jnp.asarray(v, jnp.float32)
    return float(v)


@register_no_grad_op("quantize")
def quantize(ctx, ins, attrs):
    """float -> int8 (reference: quantize_mkldnn_op.cc)."""
    x = single(ins, "Input")
    scale = float(attrs.get("Scale", 1.0))
    q = jnp.clip(jnp.round(x * scale), -127, 127).astype(jnp.int8)
    return {"Output": [q]}


@register_no_grad_op("dequantize")
def dequantize(ctx, ins, attrs):
    x = single(ins, "Input")
    scale = float(attrs.get("Scale", 1.0))
    return {"Output": [x.astype(jnp.float32) / scale]}


@register_no_grad_op("quantized_matmul")
def quantized_matmul(ctx, ins, attrs):
    """int8 × int8 → int32 accumulate → rescale to float (the MXU-native
    int8 GEMM the fork's ComputeINT8 conv does on AVX512). Honors the
    `mul` op's flattening attrs so frozen fc layers keep their shape
    contract. ``scale_y`` may be a per-output-column list (per-channel
    weight quantization); the rescale broadcasts over the last dim."""
    from paddle_tpu.ops.common import flatten_to_2d

    x = single(ins, "X")  # int8 activations (pre-quantized)
    y = single(ins, "Y")  # int8 [K, N] frozen weights
    sx = float(attrs.get("scale_x", 1.0))
    sy = _scale_param(attrs, "scale_y")  # scalar or [N] per-channel
    x_cols = int(attrs.get("x_num_col_dims", 1))
    lead_shape = x.shape[:x_cols]
    x2 = flatten_to_2d(x, x_cols)
    if _native_int8():
        acc = lax.dot(x2.astype(jnp.int8), y.astype(jnp.int8),
                      preferred_element_type=jnp.int32)
        out = acc.astype(jnp.float32)
    else:
        out = lax.dot(x2.astype(jnp.float32), y.astype(jnp.float32))
    out = out / (sx * sy)  # sy broadcasts over the trailing N dim
    out = out.reshape(tuple(lead_shape) + (y.shape[-1],))
    return {"Out": [out]}


@register_no_grad_op("quantized_conv2d")
def quantized_conv2d(ctx, ins, attrs):
    require_nchw(ctx, attrs)
    x = single(ins, "Input")   # int8 NCHW
    w = single(ins, "Filter")  # int8 OIHW
    sx = float(attrs.get("scale_x", 1.0))
    sw = _scale_param(attrs, "scale_w")  # scalar or [O] per-channel
    strides = tuple(attrs.get("strides", [1, 1]))
    paddings = attrs.get("paddings", [0, 0])
    dilations = tuple(attrs.get("dilations", [1, 1]))
    groups = int(attrs.get("groups", 1))
    pad = [(paddings[0], paddings[0]), (paddings[1], paddings[1])]
    dn = lax.conv_dimension_numbers(
        x.shape, w.shape, ("NCHW", "OIHW", "NCHW"))
    if _native_int8():
        acc = lax.conv_general_dilated(
            x.astype(jnp.int8), w.astype(jnp.int8),
            window_strides=strides, padding=pad, rhs_dilation=dilations,
            dimension_numbers=dn, feature_group_count=groups,
            preferred_element_type=jnp.int32)
        out = acc.astype(jnp.float32)
    else:
        out = lax.conv_general_dilated(
            x.astype(jnp.float32), w.astype(jnp.float32),
            window_strides=strides, padding=pad, rhs_dilation=dilations,
            dimension_numbers=dn, feature_group_count=groups)
    if isinstance(sw, jnp.ndarray):
        sw = sw.reshape((1, -1, 1, 1))  # per-O scale over the channels
    return {"Output": [out / (sx * sw)]}
