"""Shared helpers for op lowerings."""

import jax.numpy as jnp

from paddle_tpu.core.registry import amp_enabled


def fp32_accum(x):
    """The AMP numerics policy for accumulation-sensitive internals
    (norm statistics, softmax/log-sum-exp, losses, large mean-pools):
    low-precision floats (bf16, f16) upcast to fp32 for the internal
    compute; callers cast the result back to the activation dtype so no
    extra HBM traffic crosses op boundaries."""
    if x.dtype in (jnp.bfloat16, jnp.float16):
        return x.astype(jnp.float32)
    return x


def amp_cast(*xs):
    """Under AMP, cast float32 operands to bfloat16 (compute dtype); pair
    with preferred_element_type=float32 so accumulation stays fp32."""
    if not amp_enabled():
        return xs if len(xs) > 1 else xs[0]
    out = tuple(
        x.astype(jnp.bfloat16)
        if x is not None and hasattr(x, "dtype") and x.dtype == jnp.float32
        else x
        for x in xs
    )
    return out if len(out) > 1 else out[0]


def bcast_y_to_x(x, y, axis):
    """Fluid elementwise broadcast: align Y's dims to X starting at ``axis``
    (reference: paddle/fluid/operators/elementwise/elementwise_op_function.h,
    the trim-trailing-ones + mid-broadcast rule)."""
    if x.shape == y.shape:
        return y
    if y.ndim > x.ndim:
        # e.g. scalar X vs [1] Y — plain numpy broadcasting is well-defined
        return y
    if axis == -1:
        axis = x.ndim - y.ndim
    # Trim trailing 1s of y (reference does this before computing n/post)
    y_shape = list(y.shape)
    while y_shape and y_shape[-1] == 1 and len(y_shape) > 1:
        if axis + len(y_shape) > x.ndim or x.shape[axis + len(y_shape) - 1] != 1:
            y_shape = y_shape[:-1]
        else:
            break
    y = y.reshape(y_shape) if tuple(y_shape) != y.shape else y
    new_shape = [1] * x.ndim
    for i, d in enumerate(y.shape):
        new_shape[axis + i] = d
    return y.reshape(new_shape)


def flatten_to_2d(x, num_col_dims):
    """Reference ``mul`` op semantics: flatten leading ``num_col_dims`` dims
    into rows, rest into cols (paddle/fluid/operators/mul_op.cc)."""
    rows = 1
    for d in x.shape[:num_col_dims]:
        rows *= d
    cols = 1
    for d in x.shape[num_col_dims:]:
        cols *= d
    return x.reshape(rows, cols)


def single(ins, slot, default=None):
    vals = ins.get(slot, [])
    return vals[0] if vals else default


def lowered_into_a_step(ctx, op_type):
    """Not the shape inference when the Program is built (no engine), nor
    the forward's replay inside its grad op: where a lowering counts what
    a step holds."""
    return ctx.op.type == op_type and ctx.executor is not None


def require_nchw(ctx, attrs):
    """The conv / pool lowerings compute NCHW with OIHW filters. A loaded
    program may carry ``data_format`` "AnyLayout" (the reference's
    default); "NHWC" is refused, not computed as NCHW on NHWC data."""
    fmt = attrs.get("data_format", "NCHW")
    if fmt not in ("NCHW", "AnyLayout"):
        raise ValueError(
            "%s: data_format %r is not supported; the lowering is NCHW "
            "(transpose the input, or leave the attribute out)"
            % (ctx.op.type, fmt))


def flatten_lookup_ids(ids):
    """lookup_table id normalization: a trailing dim of 1 is squeezed
    (reference: lookup_table_op.cc treats ids as a column of indices)."""
    if ids.ndim >= 2 and ids.shape[-1] == 1:
        return jnp.squeeze(ids, axis=-1)
    return ids


def zero_padding_rows(flat_ids, x, padding_idx):
    """Zero the rows of ``x`` (one per id in ``flat_ids``, leading dims
    aligned) whose id equals padding_idx; the padding row contributes
    neither output nor gradient (reference: lookup_table_op.h)."""
    if padding_idx is None or padding_idx < 0:
        return x
    return jnp.where((flat_ids == padding_idx)[..., None], 0.0, x)


def hash_mix_bits(h):
    """2-round xorshift-multiply finalizer: the shared statistical core of
    every counter-based dropout mask (the generic dropout op, the XLA
    attention fallback, and the Pallas flash kernels all call this one
    implementation so their statistics can never silently diverge)."""
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(0xC2B2AE35)
    h = h ^ (h >> 16)
    return h


def keep_threshold(rate):
    """24-bit integer threshold for `mixed_bits >> 8 >= threshold` keep
    tests (no int->float conversion in hot loops)."""
    return jnp.uint32(int(float(rate) * (1 << 24)))


def hash_keep_mask(key, shape, rate):
    """Counter-based dropout keep-mask: a 2-round xorshift-multiply hash of
    the element coordinate, seeded per op instance from ``key`` (one scalar
    threefry draw). ~8 VPU int-ops per element vs ~100+ for a threefry mask
    of the same size — dropout masks are pure bandwidth, they don't need a
    cryptographic stream (the reference's curand Philox kernels make the
    same trade, dropout_op.cu). Deterministic given the key, so the generic
    vjp grad path regenerates the identical mask."""
    import jax
    import numpy as np

    seed = jax.random.bits(key, dtype=jnp.uint32)  # scalar; cheap
    rows = int(np.prod(shape[:-1], dtype=np.int64)) if len(shape) > 1 else 1
    cols = shape[-1] if shape else 1
    r = jax.lax.broadcasted_iota(jnp.uint32, (rows, cols), 0)
    c = jax.lax.broadcasted_iota(jnp.uint32, (rows, cols), 1)
    h = hash_mix_bits((r * jnp.uint32(cols) + c)
                      ^ (seed * jnp.uint32(0x9E3779B9)))
    return ((h >> 8) >= keep_threshold(rate)).reshape(shape)
