"""The Mamba-2 mixer's three ops (Dao and Gu, "Transformers are SSMs",
arXiv:2405.21060), in plain ``jax.numpy`` / ``lax``, and the scan and the
gated norm by two Pallas kernels each of their own on the TPU (which form
runs: the end of this docstring):

    ssm_conv1d      silu(bias + a causal depthwise convolution over time) of
                    the mixer's x, B and C channels
    ssd_scan        the selective state-space scan in its chunked form, with
                    a backward of its own
    gated_rms_norm  RMSNorm in groups of the channels of y * silu(z), times
                    a scale, with a backward of its own on the TPU

One op type each, so that a trace reads their time by type. AMP is each
lowering's own, as for every op here: activations come in and go out in the
input's dtype (bf16 under ``enable_bf16``), the filter, the bias, the decays,
their sums, the states and every accumulation are float32.

**The scan** (``ssd_scan``), for x [B, T, H, P], Δ = softplus(dt + dt_bias)
[B, T, H] (taken in float32 from the projection's dt whatever its dtype, as
Mamba-2's own kernel does), B and C [B, T, G, N], head h reading group
h // (H / G):

    S_t = exp(Δ_t A) S_{t-1} + Δ_t x_t ⊗ B_t      (S [P, N] a head, S_0 = 0)
    y_t = S_t · C_t + D x_t                        A = -exp(A_log)

computed by chunks of Q positions (the sequence padded to a multiple of Q
with Δ = 0, which carries the state through unchanged): with s_i the sum
of Δ A over a chunk's positions up to i,

    y_i = sum_{j <= i} exp(s_i - s_j) (C_i · B_j) Δ_j x_j      within
        + exp(s_i) S_prev · C_i                               across
        + D x_i
    S_next = exp(s_Q) S_prev + sum_j exp(s_Q - s_j) Δ_j x_j ⊗ B_j

the quadratic part and the chunk states as products on the MXU, the chunk
states carried by a ``lax.scan`` over chunks. No ``exp`` has a positive
argument: ``s_i - s_j`` is masked to -inf above the diagonal before the
``exp``. The backward is the same algebra transposed; it keeps the op's
inputs and the states entering each chunk ([B, chunks, H, P, N] float32)
and makes the rest again.

**Which form runs.** Where ``kernels/ssd_scan.by_kernel`` sees the TPU,
bf16 activations, chunks and states of whole 128 lanes and a group's
heads filling whole lane tiles with whole heads, the scan and its
backward are that module's two Pallas kernels: the same algebra and
rounding points, each chunk's ``[Q, Q]`` decays and products and the
carried state kept in VMEM. Everywhere else (the CPU, float32 programs,
odd sizes) the plain form above runs; it shares no code with the
kernels and is their oracle in the tests. No flag chooses.

Likewise the gated norm: where ``kernels/gated_norm.by_kernel`` sees the
TPU, groups of whole 128 lanes and rows in whole tiles, it and its
backward are that module's two Pallas kernels over row tiles, whose
backward keeps X, Z and each (row, group)'s float32 rstd; everywhere else
(the CPU, the rehearsal's groups of 32) the XLA form runs, and its
gradient is ``jax.vjp`` of it.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax

from paddle_tpu.core.registry import register_op
from paddle_tpu.kernels import gated_norm as norm_kernel
from paddle_tpu.kernels import ssd_scan as ssd_kernel
from paddle_tpu.ops.common import fp32_accum, lowered_into_a_step, single
from paddle_tpu.ops.nn_ops import _shift

F32 = jnp.float32


# -- ssm_conv1d ----------------------------------------------------------------

def _conv_pre(x, w, b):
    """bias + sum_j w[:, j] * x[t - (K - 1) + j] in float32 for x [B, T, C]
    and w [C, K] (x zero before the sequence's first position; tap K - 1
    multiplies the current position)."""
    taps = w.shape[1]
    w = w.astype(F32)
    return b.astype(F32) + sum(w[:, j] * _shift(x, taps - 1 - j).astype(F32)
                               for j in range(taps))


@jax.custom_vjp
def _ssm_conv1d(x, w, b):
    """silu of the convolution, rounded once to x's dtype. The backward
    keeps x, w and b alone and makes the pre-activation again behind a
    barrier, as ``gated_short_conv``'s does (nn_ops.py)."""
    return jax.nn.silu(_conv_pre(x, w, b)).astype(x.dtype)


def _ssm_conv1d_bwd(res, g):
    x, w, b, g = lax.optimization_barrier(res + (g,))
    taps = w.shape[1]
    pre = _conv_pre(x, w, b)
    sig = jax.nn.sigmoid(pre)
    dpre = g.astype(F32) * sig * (1.0 + pre * (1.0 - sig))
    w32 = w.astype(F32)
    dx = sum(w32[:, j] * _shift(dpre, j + 1 - taps) for j in range(taps))
    dw = jnp.stack([jnp.sum(dpre * _shift(x, taps - 1 - j).astype(F32),
                            axis=(0, 1)) for j in range(taps)], axis=1)
    return (dx.astype(x.dtype), dw.astype(w.dtype),
            jnp.sum(dpre, axis=(0, 1)).astype(b.dtype))


_ssm_conv1d.defvjp(lambda x, w, b: (_ssm_conv1d(x, w, b), (x, w, b)),
                   _ssm_conv1d_bwd)


@register_op("ssm_conv1d")
def ssm_conv1d(ctx, ins, attrs):
    """X [B, T, C], Filter [C, K], Bias [C] -> Out [B, T, C] =
    silu(Bias + conv_K(X)): a causal depthwise convolution of K taps a
    channel over time, each row of the batch a sequence of its own. X's
    dtype in and out; filter and bias float32."""
    x, w, b = single(ins, "X"), single(ins, "Filter"), single(ins, "Bias")
    if x.shape[-1] != w.shape[0]:
        raise ValueError("ssm_conv1d: X has %d channels, the filter %d"
                         % (x.shape[-1], w.shape[0]))
    return {"Out": [_ssm_conv1d(x, w, b)]}


# -- ssd_scan ------------------------------------------------------------------

def _by_chunks(v, chunk, split=None):
    """v [B, T, ...] padded with zeros to whole chunks along T and viewed
    as [B, L, Q, ...], its third axis split into ``split`` where given."""
    pad = (-v.shape[1]) % chunk
    v = jnp.pad(v, [(0, 0), (0, pad)] + [(0, 0)] * (v.ndim - 2))
    return v.reshape((v.shape[0], -1, chunk) + (split or v.shape[2:3])
                     + v.shape[3:])


def _chunked(x, dt, a_log, b, c, chunk):
    """The chunk-major views: x [B, L, Q, G, R, P] and b, c [B, L, Q, G, N]
    in their dtype, Δ [B, L, Q, G, R] and s, its running sum times A over
    each chunk, in float32, A [G, R]; heads split as (group, head within
    it)."""
    heads = (b.shape[2], x.shape[2] // b.shape[2])
    a = -jnp.exp(a_log.astype(F32)).reshape(heads)
    dts = _by_chunks(dt.astype(F32), chunk, heads)
    return (_by_chunks(x, chunk, heads), dts, _by_chunks(b, chunk),
            _by_chunks(c, chunk), a, jnp.cumsum(dts * a, axis=2))


def _decay(s):
    """exp(s_i - s_j) for j <= i, 0 above: [B, L, G, R, Q, Q] from s
    [B, L, Q, G, R], the mask before the ``exp``."""
    st = jnp.moveaxis(s, 2, -1)
    q = st.shape[-1]
    below = jnp.arange(q)[:, None] >= jnp.arange(q)[None, :]
    return jnp.exp(jnp.where(below, st[..., :, None] - st[..., None, :],
                             -jnp.inf))


def _einsum(spec, a, b):
    return jnp.einsum(spec, a, b, preferred_element_type=F32)


def _parts(x, dt, a_log, b, c, chunk):
    """What forward and backward both use: the views, the decays
    exp(s_i - s_j) and the within-chunk matrix M = (C_i · B_j)
    exp(s_i - s_j), both float32 [B, L, G, R, Q, Q], u = Δ x in float32,
    the weights exp(s_Q - s_j) of the chunk's end and exp(s_Q)
    [B, L, G, R]."""
    xs, dts, bs, cs, a, s = _chunked(x, dt, a_log, b, c, chunk)
    decay = _decay(s)
    m = _einsum("bligN,bljgN->blgij", cs, bs)[:, :, :, None] * decay
    u = dts[..., None] * xs.astype(F32)
    s_end = s[:, :, -1]
    return (xs, dts, bs, cs, a, s, decay, m, u,
            jnp.exp(s_end[:, :, None] - s), jnp.exp(s_end))


def _carry(decay_end, each, reverse=False):
    """The chunks' states in order: forward S_k = exp(s_Q, k) S_{k-1} +
    each_k, giving every chunk the state S_{k-1} it starts from (S_{-1} =
    0); ``reverse`` the adjoint G_{k-1} = each_k + exp(s_Q, k) G_k, giving
    every chunk the G_k of the state it ends in (G of the last = 0)."""
    def step(state, inp):
        dec, one = inp
        return one + dec[..., None, None] * state, state

    _, out = lax.scan(step, jnp.zeros_like(each[:, 0]),
                      (jnp.moveaxis(decay_end, 1, 0),
                       jnp.moveaxis(each, 1, 0)), reverse=reverse)
    return jnp.moveaxis(out, 0, 1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _ssd_scan(x, dt, a_log, b, c, d, chunk):
    return _ssd_fwd(x, dt, a_log, b, c, d, chunk)[0]


def _ssd_fwd(x, dt, a_log, b, c, d, chunk):
    cd = x.dtype
    xs, dts, bs, cs, a, s, _, m, u, w_end, decay_end = _parts(
        x, dt, a_log, b, c, chunk)
    y = _einsum("blgrij,bljgrp->bligrp", m.astype(cd), u.astype(cd))
    sigma = _einsum("bljgrp,bljgN->blgrpN",
                    (w_end[..., None] * u).astype(cd), bs)
    states = _carry(decay_end, sigma)
    y = y + jnp.exp(s)[..., None] * _einsum(
        "bligN,blgrpN->bligrp", cs, states.astype(cd))
    y = y + d.astype(F32).reshape(a.shape)[..., None] * xs.astype(F32)
    y = y.reshape(x.shape[:1] + (-1,) + x.shape[2:])[:, :x.shape[1]]
    return y.astype(cd), (x, dt, a_log, b, c, d, states)


def _ssd_bwd(chunk, res, gy):
    from paddle_tpu import observability as obs

    obs.inc("ssm.scan_xla")
    x, dt, a_log, b, c, d, states, gy = lax.optimization_barrier(
        res + (gy,))
    cd, (rows, t, h) = x.dtype, x.shape[:3]
    xs, dts, bs, cs, a, s, decay, m, u, w_end, decay_end = _parts(
        x, dt, a_log, b, c, chunk)
    gys = _by_chunks(gy.astype(cd), chunk, xs.shape[3:5])
    gy32, exp_s = gys.astype(F32), jnp.exp(s)
    egy = (exp_s[..., None] * gy32).astype(cd)
    # the adjoint of the state each chunk ends in, carried back
    ends = _carry(decay_end, _einsum("bligrp,bligN->blgrpN", egy, cs),
                  reverse=True)
    # S_next = exp(s_Q) S_prev + sum_j w_end_j u_j ⊗ B_j
    ds_end = decay_end * jnp.sum(ends * states, axis=(-2, -1))
    gb = _einsum("blgrpN,bljgN->bljgrp", ends.astype(cd), bs)
    du = w_end[..., None] * gb
    db = _einsum("bljgrp,blgrpN->bljgN", (w_end[..., None] * u).astype(cd),
                 ends.astype(cd))
    by_end = jnp.sum(gb * u, -1) * w_end
    ds = -by_end
    ds_end = ds_end + jnp.sum(by_end, axis=2)
    # across chunks: exp(s_i) S_prev · C_i
    ds = ds + exp_s * jnp.sum(gy32 * _einsum(
        "bligN,blgrpN->bligrp", cs, states.astype(cd)), -1)
    dc = _einsum("bligrp,blgrpN->bligN", egy, states.astype(cd))
    # within chunks: M u
    dm = _einsum("bligrp,bljgrp->blgrij", gys, u.astype(cd))
    du = du + _einsum("blgrij,bligrp->bljgrp", m.astype(cd), gys)
    dcb = jnp.sum(dm * decay, axis=3).astype(cd)
    e = dm * m
    ds = ds + jnp.moveaxis(jnp.sum(e, -1) - jnp.sum(e, -2), -1, 2)
    dc = dc + _einsum("blgij,bljgN->bligN", dcb, bs)
    db = db + _einsum("blgij,bligN->bljgN", dcb, cs)
    ds = ds.at[:, :, -1].add(ds_end)
    # s is the running sum of Δ A: its transpose is the sum from the end
    da = jnp.flip(jnp.cumsum(jnp.flip(ds, 2), 2), 2)
    xs32 = xs.astype(F32)
    d32 = d.astype(F32).reshape(a.shape)
    dx = dts[..., None] * du + d32[..., None] * gy32
    ddt = jnp.sum(du * xs32, -1) + da * a
    da_log = (jnp.sum(da * dts, axis=(0, 1, 2)) * a).reshape(h)
    dd = jnp.sum(gy32 * xs32, axis=(0, 1, 2, 5)).reshape(h)

    def back(v, like):
        return v.reshape((rows, -1) + like.shape[2:])[:, :t].astype(
            like.dtype)

    return (back(dx, x), back(ddt, dt), da_log.astype(a_log.dtype),
            back(db, b), back(dc, c), dd.astype(d.dtype))


_ssd_scan.defvjp(_ssd_fwd, _ssd_bwd)


@register_op("ssd_scan")
def ssd_scan(ctx, ins, attrs):
    """X [B, T, H, P], Dt [B, T, H] (before its bias), DtBias [H], ALog
    [H], B and C [B, T, G, N], D [H] -> Out [B, T, H, P]: the selective
    state-space scan of every row from a zero state, by chunks of
    ``chunk_size`` positions (the module's docstring). Δ = softplus(Dt +
    DtBias) in float32, whatever Dt's dtype. X's dtype in and out; B and C
    are taken in X's dtype. The two Pallas kernels of
    ``kernels/ssd_scan.py`` where ``by_kernel``, else the plain form.
    ``ssm.layers`` / ``ssm.scan_chunks`` (``metrics`` flag) count the ops
    lowered into a step and the chunks a row of each; ``ssm.scan_kernel``
    / ``ssm.scan_xla`` the call sites in each form, the forward's counted
    by this lowering, the backward's where it is traced."""
    from paddle_tpu import observability as obs

    x = single(ins, "X")
    dt = jax.nn.softplus(single(ins, "Dt").astype(F32)
                         + single(ins, "DtBias").astype(F32))
    b, c = single(ins, "B").astype(x.dtype), single(ins, "C").astype(x.dtype)
    heads, groups = x.shape[2], b.shape[2]
    chunk = int(attrs.get("chunk_size", 128))
    if heads % groups or b.shape != c.shape or dt.shape != x.shape[:3]:
        raise ValueError("ssd_scan: X %s, Dt %s, B %s, C %s: the heads must "
                         "fall into B's and C's groups evenly"
                         % (x.shape, dt.shape, b.shape, c.shape))
    by_kernel = ssd_kernel.by_kernel(x, b, chunk)
    if lowered_into_a_step(ctx, "ssd_scan"):
        obs.inc("ssm.layers")
        obs.inc("ssm.scan_chunks", -(-x.shape[1] // chunk))
        obs.inc("ssm.scan_kernel" if by_kernel else "ssm.scan_xla")
    scan = ssd_kernel.scan if by_kernel else _ssd_scan
    return {"Out": [scan(x, dt, single(ins, "ALog"), b, c, single(ins, "D"),
                         chunk)]}


# -- gated_rms_norm ------------------------------------------------------------

def _gated_rms_norm_xla(x, z, scale, groups, eps):
    """The XLA form: y = x · silu(z) in float32, each group scaled by
    rsqrt(mean(y²) + eps), times the scale, in X's dtype; the gradient is
    ``jax.vjp`` of it."""
    y = fp32_accum(x) * jax.nn.silu(fp32_accum(z))
    grouped = y.reshape(y.shape[:-1] + (groups, -1))
    grouped = grouped * lax.rsqrt(jnp.mean(jnp.square(grouped), -1,
                                           keepdims=True) + eps)
    return (grouped.reshape(y.shape) * scale).astype(x.dtype)


@register_op("gated_rms_norm")
def gated_rms_norm(ctx, ins, attrs):
    """X, Z [..., I], Scale [I] -> Y = RMS(X * silu(Z)) * Scale, the RMS
    over each of ``groups`` equal groups of the last axis (the gate before
    the norm), in float32, returned in X's dtype. Where
    ``kernels/gated_norm.by_kernel`` sees the TPU, groups of whole 128
    lanes and rows in whole tiles, the norm and its backward are that
    module's two Pallas kernels over row tiles, the backward keeping X, Z
    and each group's float32 rstd alone; everywhere else (the CPU, odd
    sizes) the XLA form, whose gradient is ``jax.vjp`` of it. No flag
    chooses. ``ssm.gated_norm_kernel`` / ``ssm.gated_norm_xla``
    (``metrics`` flag) count the ops lowered into a step in each form."""
    from paddle_tpu import observability as obs

    x, z, scale = single(ins, "X"), single(ins, "Z"), single(ins, "Scale")
    groups, eps = int(attrs["groups"]), attrs.get("epsilon", 1e-6)
    by_kernel = norm_kernel.by_kernel(x, z, scale, groups)
    if lowered_into_a_step(ctx, "gated_rms_norm"):
        obs.inc("ssm.gated_norm_kernel" if by_kernel
                else "ssm.gated_norm_xla")
    norm = norm_kernel.norm if by_kernel else _gated_rms_norm_xla
    return {"Y": [norm(x, z, scale, groups, eps)]}
