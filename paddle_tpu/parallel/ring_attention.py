"""Ring attention: sequence-parallel exact attention over an ICI ring.

Long-context story for the framework (SURVEY.md §5 notes the reference's
2018 LoDTensor approach has no sequence parallelism; this is the first-class
TPU-native replacement). Q/K/V are sharded along the sequence axis over the
``sp`` mesh axis; each step every device contracts its local Q block against
the K/V block currently in hand, merges with a numerically-stable online
softmax (flash-attention accumulation), then passes K/V to its ring
neighbor with ``lax.ppermute`` — exact attention with O(T/n) memory per
device and comm overlapped across steps.

The local contraction is the Pallas flash kernel whenever it can lower
(TPU backend, tileable block) — ``flash_attention_lse`` takes the ring
step's global (q_off, k_off) positions for causal masking and returns the
per-row logsumexp, and per-step partial outputs merge across steps with
the standard logaddexp rescaling, so the multi-chip long-context path
runs each step at single-chip kernel speed instead of materializing
[t, t] score blocks in XLA. The plain einsum body remains the fallback
for odd shapes / non-TPU backends.

Differentiable end-to-end: the ring is a ``lax.scan`` and ppermute has a
transpose rule, so BPTT through the ring needs no custom vjp; the flash
step's lse cotangent folds into the backward kernels' delta.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

_NEG = -1e30


def reference_attention(q, k, v, causal=False, scale=None):
    """Plain attention oracle, [B, H, T, D]."""
    d = q.shape[-1]
    scale = scale if scale is not None else d ** -0.5
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if causal:
        T = q.shape[2]
        mask = jnp.tril(jnp.ones((T, T), bool))
        s = jnp.where(mask[None, None], s, _NEG)
    w = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", w, v)


def _ring_body(q_blk, k_blk, v_blk, axis_name, n_shards, causal, scale):
    """Per-device body under shard_map. Blocks are [B, H, t, D] locals.

    The online-softmax carry (o/m/l) accumulates in float32 regardless of
    input dtype — with bf16 inputs a bf16 running max/denominator loses
    the flash-kernel's accuracy and ``_NEG`` rounds to -inf; the output is
    cast back at the end (same discipline as kernels/flash_attention.py).
    """
    in_dtype = q_blk.dtype
    idx = lax.axis_index(axis_name)
    t = q_blk.shape[2]
    q_pos = idx * t + jnp.arange(t)  # global positions of local queries

    o0 = jnp.zeros(q_blk.shape, jnp.float32)
    m0 = jnp.full(q_blk.shape[:3], _NEG, jnp.float32)   # running max
    l0 = jnp.zeros(q_blk.shape[:3], jnp.float32)        # running denom
    perm = [(j, (j + 1) % n_shards) for j in range(n_shards)]

    def step(carry, i):
        o, m, l, k_cur, v_cur = carry
        src = (idx - i) % n_shards  # whose K/V block we hold this step
        s = jnp.einsum("bhqd,bhkd->bhqk", q_blk.astype(jnp.float32),
                       k_cur.astype(jnp.float32)) * scale
        if causal:
            k_pos = src * t + jnp.arange(t)
            keep = q_pos[:, None] >= k_pos[None, :]
            s = jnp.where(keep[None, None], s, _NEG)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        corr = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l = l * corr + jnp.sum(p, axis=-1)
        o = o * corr[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p, v_cur.astype(jnp.float32))
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        return (o, m_new, l, k_nxt, v_nxt), None

    (o, m, l, _, _), _ = lax.scan(
        step, (o0, m0, l0, k_blk, v_blk), jnp.arange(n_shards))
    out = o / jnp.maximum(l, 1e-30)[..., None]
    return out.astype(in_dtype)


def _ring_body_flash(q_blk, k_blk, v_blk, axis_name, n_shards, causal,
                     scale, block, interpret):
    """Flash-kernel ring body: each step contracts the local Q block
    against the in-hand K/V block with the Pallas kernel at the step's
    global (q_off, k_off) positions, then merges the normalized partial
    output via its logsumexp:

        lse' = logaddexp(lse, lse_i)
        o'   = o * exp(lse - lse') + o_i * exp(lse_i - lse')

    A fully-causally-masked step publishes lse_i ~= -1e30 and drops out of
    the merge with weight exp(-1e30 - lse') = 0. The merge runs in fp32
    and is plain XLA, so scan-transpose BPTT differentiates it and each
    step's flash vjp runs the backward kernels (dk/dv cotangents ride the
    ppermute transpose back around the ring)."""
    from paddle_tpu.kernels.flash_attention import flash_attention_lse

    in_dtype = q_blk.dtype
    idx = lax.axis_index(axis_name)
    t = q_blk.shape[2]
    B, H = q_blk.shape[0], q_blk.shape[1]

    o0 = jnp.zeros(q_blk.shape, jnp.float32)
    lse0 = jnp.full((B, H, t), _NEG, jnp.float32)
    perm = [(j, (j + 1) % n_shards) for j in range(n_shards)]

    def step(carry, i):
        o, lse, k_cur, v_cur = carry
        src = (idx - i) % n_shards  # whose K/V block we hold this step
        offsets = jnp.stack([idx * t, src * t]).astype(jnp.int32)
        o_i, lse_i = flash_attention_lse(
            q_blk, k_cur, v_cur, None, offsets, 0, causal, scale, 0.0,
            block, block, interpret)
        lse_new = jnp.logaddexp(lse, lse_i)
        o = (o * jnp.exp(lse - lse_new)[..., None]
             + o_i.astype(jnp.float32) * jnp.exp(lse_i - lse_new)[..., None])
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        return (o, lse_new, k_nxt, v_nxt), None

    (o, _, _, _), _ = lax.scan(
        step, (o0, lse0, k_blk, v_blk), jnp.arange(n_shards))
    return o.astype(in_dtype)


def ring_attention(q, k, v, mesh=None, axis_name="sp", causal=False,
                   scale=None, batch_axis=None, use_flash=None,
                   interpret=False):
    """Exact attention with the sequence axis sharded over ``axis_name``.

    q, k, v: [B, H, T, D]; T must divide by the sp axis size. Usable inside
    jit (shard_map traces into the surrounding computation).

    ``use_flash``: None (auto — Pallas kernel on TPU for tileable local
    blocks of at least PADDLE_TPU_FLASH_MIN_SEQ keys, einsum fallback
    elsewhere), True (force the kernel; pass ``interpret=True`` off-TPU),
    or False (force the einsum body)."""
    from paddle_tpu.parallel.mesh import get_default_mesh

    mesh = mesh or get_default_mesh()
    n = mesh.shape[axis_name]
    if q.shape[2] % n:
        raise ValueError(
            "seq len %d not divisible by %s=%d" % (q.shape[2], axis_name, n))
    d = q.shape[-1]
    scale = scale if scale is not None else d ** -0.5
    t = q.shape[2] // n

    if use_flash is None:
        from paddle_tpu.kernels.flash_attention import flash_dispatch_ok

        use_flash = flash_dispatch_ok(t, t)
    if use_flash:
        from paddle_tpu.kernels.flash_attention import pick_block

        body = functools.partial(
            _ring_body_flash, axis_name=axis_name, n_shards=n,
            causal=causal, scale=scale, block=pick_block(t, q.dtype),
            interpret=interpret)
    else:
        body = functools.partial(
            _ring_body, axis_name=axis_name, n_shards=n, causal=causal,
            scale=scale)

    spec = P(batch_axis, None, axis_name, None)
    fn = shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                   out_specs=spec, check_vma=False)
    return fn(q, k, v)
