"""Flash attention as Pallas TPU kernels, forward AND backward.

Forward streams K/V blocks from VMEM against a resident Q block with
online-softmax accumulation and emits the per-row logsumexp — O(T) memory,
MXU-shaped contractions (the kernel the reference implements as
math/softmax.cu + matmuls, fused here instead; fused-op strategy per
paddle/fluid/operators/fused/).

Backward re-derives the softmax from the saved logsumexp instead of
materializing the [Tq, Tk] probability matrix: a cheap XLA delta
precompute (rowsum(dO*O)), then ONE kernel with a K/V block resident and
Q streamed that derives s, p, dp and ds once per tile and feeds dQ, dK
and dV from them (5 tile matmuls, 1 exp pass); dQ accumulates over the K
blocks in a full-length [Tq, D] f32 VMEM scratch. Where that accumulator
does not fit (``_bwd_fused_fits``, reckoned from the static shapes: past
8192 positions in bf16 at head size 64) the FlashAttention-2 pair runs in
its place, a dQ kernel (Q block resident, K/V streamed) and the same
K/V-resident kernel without its dQ part (7 matmuls, 2 exp passes, VMEM
bounded by the blocks alone). The plain-XLA recompute path remains the
fallback (PADDLE_TPU_FLASH_BWD=xla, or shapes the kernels cannot tile).

Mosaic layout notes (what made round-2's kernels fail to lower on the
real chip): every block's last two dims must be (8, 128)-tileable or span
the full array dim. The logsumexp/delta residuals are therefore carried
rank-3 as ``[B*H, Tq, _LSE_LANES=1]`` — the trailing unit lane axis spans
its full array dim (legal the same way the D=64 head dim is), never as
rank-2 ``(1, block_q)`` blocks whose sublane dim is neither 8-divisible
nor full. jax's own kernel instead replicates the scalar across 128
lanes; both lower, the unit lane costs 128x less HBM.

Masking is TPU-first: key-padding masks are passed as per-sequence
*lengths* living in SMEM (scalar memory), not as [B, H, T, T] additive
tensors — the kernel compares against a key-position iota. Causal masking
is a static flag, and so is ``window`` (causal only: query ``t`` sees keys
``t-window+1 .. t``): the K-block range of a Q tile is clamped below by the
window as it is above by causality, in the loop bounds and in the fetch
index, so a window layer visits fewer tiles, not the same tiles with more
masking. K/V may have fewer heads than Q (grouped-query attention): Q head
``h`` reads K/V head ``h // (Hq // Hkv)`` through the K/V index maps; the
backward makes dK/dV per Q head and ``_flash_backward`` sums each group. Attention dropout runs *inside* the kernel using a
counter-based hash RNG (murmur3 finalizer over the global (batch, q, k)
coordinate), so the forward and every backward kernel regenerate the
identical mask from (seed, coords) with no [Tq, Tk] mask ever stored.

``fused_attention`` is the dispatch point: the Pallas kernel on TPU (or in
interpreter mode for tests), the plain-XLA composition elsewhere.
"""

import functools

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -1e30
# Lane width for the logsumexp/delta residuals, carried as rank-3
# [B*H, Tq, _LSE_LANES] so every block spans full array dims on the last
# axis (Mosaic-legal, like the D=64 head dim). 1 verifies on hardware and
# keeps the residuals O(B*H*T); jax's own kernel replicates to 128 lanes
# (MIN_BLOCK_SIZE), which also lowers but costs 128x the HBM.
_LSE_LANES = 1


def _smem_spec():
    return pl.BlockSpec(memory_space=pltpu.SMEM)


def _keep_mask(seed, b, q_pos, k_pos, t_k, rate):
    """Deterministic dropout keep-mask from the *global* (b, q, k)
    coordinate: murmur3 finalizer bits -> uniform [0,1) -> >= rate.
    Counter-based, so every backward kernel reproduces the forward's
    mask exactly regardless of its iteration order.

    (Round-5 measured the in-kernel dropout at ~25% of whole-kernel time
    and tried a strip-hoisted 1-multiply variant of this hash: the
    overhead did NOT move — the cost is the unavoidable extra
    compare/select/scale vector ops on the [bq, bk] tile, not the hash
    arithmetic — so the stronger full-avalanche form stays.)"""
    from paddle_tpu.ops.common import hash_mix_bits, keep_threshold

    idx = (q_pos * t_k + k_pos).astype(jnp.uint32)
    h = hash_mix_bits(idx ^ (seed.astype(jnp.uint32)
                             + jnp.uint32(0x9E3779B9)
                             * (b + 1).astype(jnp.uint32)))
    return (h >> 8) >= keep_threshold(rate)


def _nk_limit(nk, causal_hi, length, block_k, masked, causal):
    """Number of K blocks that can contribute: min over the causal frontier
    and the valid-key frontier (both dynamic-friendly fori_loop bounds).

    ``causal_hi`` may be 0 or negative when the whole Q tile precedes the
    K range (a ring-attention step holding a future K/V block) — the loop
    then runs zero iterations and the row publishes lse ~= -1e30, which
    the cross-step logaddexp merge treats as "no contribution". The
    masked limit is >= 1 by construction (lengths are clamped upstream)."""
    nk_eff = nk
    if causal:
        nk_eff = jnp.clip(causal_hi, 0, nk)
    if masked:
        nk_eff = jnp.minimum(nk_eff, (length + block_k - 1) // block_k)
    return nk_eff


def _causal_blocks(q_off, k_off, j, block_q, block_k):
    """Dynamic count of K blocks at or before the causal frontier of Q
    block ``j``, with Q/K living at global offsets ``q_off``/``k_off``
    (SMEM scalars — the ring-attention caller passes shard*T). Floor
    division handles the fully-masked (negative) case."""
    return (q_off - k_off + (j + 1) * block_q - 1) // block_k + 1


def _window_first_block(q_off, k_off, j, block_q, block_k, window):
    """First K block that any row of Q block ``j`` can see under
    ``window``: the block of the first row's oldest visible key."""
    return jnp.maximum(q_off - k_off + j * block_q - (window - 1),
                       0) // block_k


def _window_last_q_block(s, block_q, block_k, window):
    """Last Q block with a row that still sees K block ``s`` under
    ``window`` (offsets 0): the block of the query ``window - 1`` past the
    block's last key."""
    return ((s + 1) * block_k - 1 + window - 1) // block_q


def _score_mask(sij, q_pos, k_pos, q_off, k_off, length, causal, window,
                masked):
    """The scores with every key a query may not see set to ``_NEG``."""
    if causal:
        sij = jnp.where(q_pos + q_off >= k_pos + k_off, sij, _NEG)
    if window is not None:
        sij = jnp.where((q_pos + q_off) - (k_pos + k_off) < window, sij,
                        _NEG)
    if masked:
        sij = jnp.where(k_pos < length, sij, _NEG)
    return sij


def _attn_kernel(len_ref, seed_ref, off_ref, q_ref, k_ref, v_ref, o_ref,
                 lse_ref, acc_s, m_s, l_s, *, block_q, block_k, causal,
                 scale, rate, masked, t_k, window=None):
    """Online-softmax forward with K/V STREAMED over the innermost grid
    axis (grid = (B*H, Tq/block_q, Tk/block_k)) and the (acc, m, l)
    carry in VMEM scratch — VMEM bounded by the block sizes, not Tk
    (the resident-K/V form capped context at ~8k: seq-16384 overran the
    16MB scoped limit in this kernel by 768KB)."""
    b = pl.program_id(0)
    j = pl.program_id(1)
    s = pl.program_id(2)
    ns = pl.num_programs(2)
    length = len_ref[b]
    seed = seed_ref[0]
    q_off, k_off = off_ref[0], off_ref[1]

    @pl.when(s == 0)
    def _init():
        acc_s[...] = jnp.zeros_like(acc_s)
        m_s[...] = jnp.full_like(m_s, _NEG)
        l_s[...] = jnp.zeros_like(l_s)

    causal_hi = _causal_blocks(q_off, k_off, j, block_q, block_k)
    nk_eff = _nk_limit(ns, causal_hi, length, block_k, masked, causal)
    live = s < nk_eff
    if window is not None:
        live = jnp.logical_and(live, s >= _window_first_block(
            q_off, k_off, j, block_q, block_k, window))

    @pl.when(live)
    def _step():
        q = q_ref[0]                           # [block_q, D], input dtype
        k_blk = k_ref[0]                       # [block_k, D]
        v_blk = v_ref[0]
        q_pos = j * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        sij = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        k_pos = s * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        sij = _score_mask(sij, q_pos, k_pos, q_off, k_off, length, causal,
                          window, masked)
        m = m_s[...]
        m_new = jnp.maximum(m, jnp.max(sij, axis=-1, keepdims=True))
        corr = jnp.exp(m - m_new)
        p = jnp.exp(sij - m_new)
        l_s[...] = l_s[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        m_s[...] = m_new
        if rate > 0.0:
            keep = _keep_mask(seed, b, q_pos, k_pos, t_k, rate)
            p_acc = jnp.where(keep, p, 0.0) * (1.0 / (1.0 - rate))
        else:
            p_acc = p
        acc_s[...] = acc_s[...] * corr + jax.lax.dot_general(
            p_acc.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(s == ns - 1)
    def _emit():
        acc, m, l = acc_s[...], m_s[...], l_s[...]
        # a row with EVERY key masked keeps m at _NEG, making p = exp(0)
        # = 1 garbage — zero it so the row publishes out = 0,
        # lse ~= -1e30 (the "no contribution" value the ring merge
        # expects). Without this guard only block-aligned offsets would
        # be safe.
        l = jnp.where(m > 0.5 * _NEG, l, 0.0)
        acc = jnp.where(m > 0.5 * _NEG, acc, 0.0)
        o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
        # logsumexp per row, the softmax residual the backward kernels
        # re-derive p from (FlashAttention-2's L); replicated across the
        # lane dim so the block stays (8, 128)-tileable
        lse = m + jnp.log(jnp.maximum(l, 1e-30))  # [block_q, 1]
        lse_ref[0] = jnp.broadcast_to(lse, (block_q, _LSE_LANES))


def _stream_kvmap(block_q, block_k, causal, offsets, window=None, group=1):
    """Index map for K/V blocks streamed over the innermost grid axis of
    a (b, q-block, k-block) grid. For causal runs without (traced) ring
    offsets the fetch index clamps to the causal frontier, and under a
    ``window`` to the window's first block as well, so skipped steps
    re-fetch the block a live step needs (consecutive equal indices
    elide the copy); ring-step offsets keep the identity map — wasted
    fetches on skipped steps, never wrong. ``group`` Q heads share one
    K/V head: program ``b`` reads K/V row ``b // group``."""
    def head(b):
        return b // group if group > 1 else b

    if causal and offsets is None:
        def kvmap(b, j, s):
            blk = jnp.minimum(s, ((j + 1) * block_q - 1) // block_k)
            if window is not None:
                blk = jnp.maximum(blk, _window_first_block(
                    0, 0, j, block_q, block_k, window))
            return (head(b), blk, 0)
    else:
        def kvmap(b, j, s):
            return (head(b), s, 0)
    return kvmap


def _kv_group(q, k, window, causal):
    """Q heads per K/V head, from the shapes; counts the lowered calls
    of the grouped and windowed forms (``flash.gqa_calls``,
    ``flash.window_calls``)."""
    from paddle_tpu import observability as obs

    if window is not None and not causal:
        raise ValueError("a window needs causal attention")
    hq, hkv = q.shape[1], k.shape[1]
    if hq % hkv:
        raise ValueError("Q heads (%d) are no multiple of the K/V heads "
                         "(%d)" % (hq, hkv))
    if hq != hkv:
        obs.inc("flash.gqa_calls")
    if window is not None:
        obs.inc("flash.window_calls")
    return hq // hkv


def _window_kw(window):
    """The kernels' ``window`` keyword, left out where there is none so
    that a call without a window is lowered as it always was."""
    return {} if window is None else {"window": int(window)}


def _offsets_arr(offsets):
    """[q_off, k_off] int32 SMEM scalars — the Q/K global base positions
    (ring-attention shard offsets); [0, 0] for ordinary full attention."""
    if offsets is None:
        return jnp.zeros((2,), jnp.int32)
    return jnp.asarray(offsets, jnp.int32).reshape(2)


def _flash_forward(q, k, v, seq_lens, offsets, seed, causal, scale, rate,
                   block_q, block_k, interpret, window=None):
    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    group = _kv_group(q, k, window, causal)
    qr = q.reshape(B * H, Tq, D)
    kr = k.reshape(B * H // group, Tk, D)
    vr = v.reshape(B * H // group, Tk, D)
    block_q = min(block_q, Tq)
    block_k = min(block_k, Tk)
    grid = (B * H, Tq // block_q)

    masked = seq_lens is not None
    if masked:
        lens = jnp.repeat(jnp.maximum(seq_lens.astype(jnp.int32), 1), H)
    else:
        lens = jnp.full((B * H,), Tk, jnp.int32)
    seed_arr = jnp.asarray(seed, jnp.int32).reshape(1)

    _kvmap = _stream_kvmap(block_q, block_k, causal, offsets, window, group)
    kernel = functools.partial(
        _attn_kernel, block_q=block_q, block_k=block_k, causal=causal,
        scale=scale, rate=rate, masked=masked, t_k=Tk,
        **_window_kw(window))
    out, lse = pl.pallas_call(
        kernel,
        out_shape=[
            jax.ShapeDtypeStruct(qr.shape, q.dtype),
            jax.ShapeDtypeStruct((B * H, Tq, _LSE_LANES), jnp.float32),
        ],
        grid=grid + (Tk // block_k,),
        in_specs=[
            _smem_spec(),
            _smem_spec(),
            _smem_spec(),
            pl.BlockSpec((1, block_q, D), lambda b, j, s: (b, j, 0)),
            pl.BlockSpec((1, block_k, D), _kvmap),
            pl.BlockSpec((1, block_k, D), _kvmap),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, j, s: (b, j, 0)),
            pl.BlockSpec((1, block_q, _LSE_LANES),
                         lambda b, j, s: (b, j, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32),
                        pltpu.VMEM((block_q, 1), jnp.float32),
                        pltpu.VMEM((block_q, 1), jnp.float32)],
        interpret=interpret,
    )(lens, seed_arr, _offsets_arr(offsets), qr, kr, vr)
    return out.reshape(B, H, Tq, D), lse


def _bwd_dq_kernel(len_ref, seed_ref, off_ref, q_ref, k_ref, v_ref, do_ref,
                   lse_ref, delta_ref, dq_ref, dq_acc, *, block_q, block_k,
                   causal, scale, rate, masked, t_k, window=None):
    """dQ with K/V streamed over the innermost grid axis and the dq
    accumulator in VMEM scratch (same restructure as the forward — the
    resident-K/V form's VMEM grew with Tk)."""
    b = pl.program_id(0)
    j = pl.program_id(1)
    s = pl.program_id(2)
    ns = pl.num_programs(2)
    length = len_ref[b]
    seed = seed_ref[0]
    q_off, k_off = off_ref[0], off_ref[1]

    @pl.when(s == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    causal_hi = _causal_blocks(q_off, k_off, j, block_q, block_k)
    nk_eff = _nk_limit(ns, causal_hi, length, block_k, masked, causal)
    live = s < nk_eff
    if window is not None:
        live = jnp.logical_and(live, s >= _window_first_block(
            q_off, k_off, j, block_q, block_k, window))

    @pl.when(live)
    def _step():
        q = q_ref[0]                          # [block_q, D]
        do = do_ref[0]                        # [block_q, D]
        lse = lse_ref[0][:, :1]               # [block_q, 1]
        delta = delta_ref[0][:, :1]           # [block_q, 1]
        k_blk = k_ref[0]                      # [block_k, D]
        v_blk = v_ref[0]
        q_pos = j * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        sij = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        k_pos = s * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        sij = _score_mask(sij, q_pos, k_pos, q_off, k_off, length, causal,
                          window, masked)
        # fully-masked rows carry lse ~= -1e30; exp(sij - lse) would
        # overflow to inf there — such rows contribute no gradient
        p = jnp.where(lse > 0.5 * _NEG, jnp.exp(sij - lse), 0.0)
        dp = jax.lax.dot_general(
            do, v_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if rate > 0.0:
            keep = _keep_mask(seed, b, q_pos, k_pos, t_k, rate)
            dp = jnp.where(keep, dp, 0.0) * (1.0 / (1.0 - rate))
        ds = p * (dp - delta) * scale
        dq_acc[...] += jax.lax.dot_general(
            ds.astype(k_blk.dtype), k_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(s == ns - 1)
    def _emit():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


def _bwd_fused_kernel(len_ref, seed_ref, off_ref, q_ref, k_ref, v_ref,
                      do_ref, lse_ref, delta_ref, *outs_and_scratch, with_dq,
                      block_q, block_k, causal, scale, rate, masked,
                      window=None):
    """The backward with a K/V tile resident and the Q dimension STREAMED
    over the innermost grid axis (grid = (B*H, Tk/block_k, Tq/block_q)),
    f32 accumulation in VMEM scratch — the earlier form held full-length
    Q/dO/lse/delta resident per program, so its VMEM footprint grew
    linearly with Tq and capped trainable context at ~2-4k tokens
    (seq-4096+dropout exceeded the 16MB scoped limit by 672KB; seq-8192
    by 8.75MB). TPU grids iterate sequentially, so the accumulator
    pattern (zero at j==0, emit at j==nq-1) is the standard one — cf. the
    public pallas flash kernel's block_q_major streaming
    (jax.experimental.pallas.ops.tpu).

    ``with_dq=False`` is the dK/dV kernel of the two-kernel form. With
    ``with_dq=True`` the same pass over the scores also feeds dQ: s, p, dp
    and ds are derived once per tile (5 matmuls and 1 exp pass where the
    pair runs 7 and 2), and each tile's ``ds @ k`` is added into rows
    ``j*block_q...`` of a full-length ``[Tq, D]`` f32 accumulator, which
    is written out once per (batch, head) at the last (k block, q block)
    step — the dQ output block spans Tq and its index moves with ``b``
    alone, so it is flushed once and not per K block. That accumulator is
    why this form fits only up to a length (``_bwd_fused_fits``)."""
    if with_dq:
        dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc = outs_and_scratch
    else:
        dk_ref, dv_ref, dk_acc, dv_acc = outs_and_scratch
    b = pl.program_id(0)
    s_idx = pl.program_id(1)
    j = pl.program_id(2)
    ns = pl.num_programs(1)
    nq = pl.num_programs(2)
    t_k = dk_ref.shape[1] * ns
    length = len_ref[b]
    seed = seed_ref[0]
    q_off, k_off = off_ref[0], off_ref[1]

    @pl.when(j == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    if with_dq:
        @pl.when(jnp.logical_and(s_idx == 0, j == 0))
        def _init_dq():
            dq_acc[...] = jnp.zeros_like(dq_acc)

    def compute():
        k_blk = k_ref[0]                       # [block_k, D]
        v_blk = v_ref[0]                       # [block_k, D]
        q = q_ref[0]                           # [block_q, D]
        do = do_ref[0]                         # [block_q, D]
        lse = lse_ref[0][:, :1]                # [block_q, 1]
        delta = delta_ref[0][:, :1]            # [block_q, 1]
        sij = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        q_pos = j * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        k_pos = s_idx * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        sij = _score_mask(sij, q_pos, k_pos, q_off, k_off, length, causal,
                          window, masked)
        # guard fully-masked rows (lse ~= -1e30) as in the dQ kernel
        p = jnp.where(lse > 0.5 * _NEG, jnp.exp(sij - lse),
                      0.0)                     # [block_q, block_k]
        if rate > 0.0:
            keep = _keep_mask(seed, b, q_pos, k_pos, t_k, rate)
            inv = 1.0 / (1.0 - rate)
            p_drop = jnp.where(keep, p, 0.0) * inv
        else:
            keep = None
            p_drop = p
        dv_acc[...] += jax.lax.dot_general(
            p_drop.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do, v_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if keep is not None:
            dp = jnp.where(keep, dp, 0.0) * inv
        ds = (p * (dp - delta) * scale).astype(q.dtype)
        dk_acc[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        if with_dq:
            rows = pl.ds(pl.multiple_of(j * block_q, block_q), block_q)
            dq_acc[rows, :] += jax.lax.dot_general(
                ds, k_blk, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    if causal:
        # q blocks whose last global row is before this k block's first
        # see none of it — same frontier as the old fori j0, now a
        # skipped grid step; under a window so do the q blocks whose
        # first row is ``window`` or more past this k block's last key
        live = ((j + 1) * block_q - 1 + q_off
                >= s_idx * block_k + k_off)
        if window is not None:
            live = jnp.logical_and(
                live, j * block_q + q_off
                - ((s_idx + 1) * block_k - 1 + k_off) < window)
        pl.when(live)(compute)
    else:
        compute()

    @pl.when(j == nq - 1)
    def _emit():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)

    if with_dq:
        @pl.when(jnp.logical_and(s_idx == ns - 1, j == nq - 1))
        def _emit_dq():
            dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


# What the fused backward may ask of VMEM, handed to Mosaic as the
# kernel's limit (the default scoped limit is 16 MiB of the chip's 128).
_FUSED_BWD_VMEM_LIMIT = 32 * 2 ** 20


def _bwd_fused_fits(tq, d, dtype, block_q, block_k, rate=0.0):
    """Whether the one-kernel backward applies, reckoned from the static
    shapes: the VMEM that grows with the sequence (the f32 dQ accumulator
    and its double-buffered output block), the streamed and resident
    blocks and the tile's intermediates, minor dims padded to 128 lanes,
    within seven eighths of the kernel's limit. Longer sequences run the
    two kernels, whose VMEM does not grow with the length."""
    item = jnp.dtype(dtype).itemsize
    lanes = -(-d // 128) * 128
    dq = tq * lanes * (4 + 2 * item)
    streamed = 2 * block_q * (2 * lanes * item + 2 * 128 * 4)
    resident = block_k * lanes * (2 * 4 * item + 2 * 4)
    # what Mosaic keeps of a tile: the scores in f32, p and ds in the
    # MXU's operand dtype, the dropout mask as a word (compiled for a
    # v5e, bf16 and f32, 256..1024 squared, 2048..16384 positions: the
    # least limit that compiles is 0 to 6 MiB under this sum)
    tile = block_q * block_k * (4 + 2 * item + (4 if rate > 0.0 else 0))
    return (dq + streamed + resident + tile
            <= _FUSED_BWD_VMEM_LIMIT * 7 // 8)


def _flash_backward(q, k, v, out, lse, g, g_lse, seq_lens, offsets, seed,
                    causal, scale, rate, block_q, block_k, interpret,
                    window=None):
    """dQ, dK, dV from the saved (out, lse). One kernel where its
    full-length dQ accumulator fits VMEM (``_bwd_fused_fits``, from the
    static shapes; blocks from ``pick_bwd_blocks``), the dQ and dK/dV
    kernels at the caller's blocks beyond. The counters
    ``flash.bwd_fused`` / ``flash.bwd_split`` count the lowered calls of
    each form."""
    from paddle_tpu import observability as obs

    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    group = _kv_group(q, k, window, causal)
    qr = q.reshape(B * H, Tq, D)
    kr = k.reshape(B * H // group, Tk, D)
    vr = v.reshape(B * H // group, Tk, D)
    do = g.reshape(B * H, Tq, D)
    bq, bk = min(block_q, Tq), min(block_k, Tk)
    fused_blocks = pick_bwd_blocks(Tq, Tk, q.dtype, (bq, bk))
    fused = _bwd_fused_fits(Tq, D, q.dtype, *fused_blocks, rate)
    obs.inc("flash.bwd_fused" if fused else "flash.bwd_split")

    masked = seq_lens is not None
    if masked:
        lens = jnp.repeat(jnp.maximum(seq_lens.astype(jnp.int32), 1), H)
    else:
        lens = jnp.full((B * H,), Tk, jnp.int32)
    seed_arr = jnp.asarray(seed, jnp.int32).reshape(1)
    off_arr = _offsets_arr(offsets)

    # delta = rowsum(dO * O): cheap elementwise, XLA fuses it; replicated
    # across the lane dim like lse so its blocks stay Mosaic-tileable.
    # A cotangent on the published logsumexp (the ring-attention merge
    # differentiates through lse) folds in exactly: d s from g_lse is
    # p * g_lse, and ds = p * (dp - delta + g_lse) — so delta -= g_lse.
    delta = jnp.sum(
        do.astype(jnp.float32) * out.reshape(B * H, Tq, D).astype(
            jnp.float32), axis=-1)
    if g_lse is not None:
        delta = delta - g_lse.reshape(B * H, Tq).astype(jnp.float32)
    delta = jnp.broadcast_to(delta[..., None], (B * H, Tq, _LSE_LANES))
    args = (lens, seed_arr, off_arr, qr, kr, vr, do, lse, delta)
    static = dict(causal=causal, scale=scale, rate=rate, masked=masked,
                  **_window_kw(window))

    if fused:
        bq, bk = fused_blocks
    else:
        _kvmap = _stream_kvmap(bq, bk, causal, offsets, window, group)

        def qspec(lanes):
            return pl.BlockSpec((1, bq, lanes), lambda b, j, s: (b, j, 0))

        dq = pl.pallas_call(
            functools.partial(_bwd_dq_kernel, block_q=bq, block_k=bk,
                              t_k=Tk, **static),
            out_shape=jax.ShapeDtypeStruct(qr.shape, q.dtype),
            grid=(B * H, Tq // bq, Tk // bk),
            in_specs=[
                _smem_spec(),
                _smem_spec(),
                _smem_spec(),
                qspec(D),
                pl.BlockSpec((1, bk, D), _kvmap),
                pl.BlockSpec((1, bk, D), _kvmap),
                qspec(D),
                qspec(_LSE_LANES),
                qspec(_LSE_LANES),
            ],
            out_specs=qspec(D),
            scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
            interpret=interpret,
        )(*args)

    # q/do/lse/delta stream over the innermost grid axis (VMEM bounded by
    # the block size, not Tq — what makes seq >= 4096 compile). Causal
    # runs skip the sub-frontier steps in-kernel; when the offsets are
    # static zeros (every non-ring call) the fetch index also clamps to
    # the frontier so skipped steps re-fetch the block the first live
    # step needs (consecutive equal indices elide the copy). Ring-step
    # (traced) offsets keep the identity map — fetches for skipped steps
    # are wasted bandwidth but never wrong.
    if causal and offsets is None:
        nq = Tq // bq

        def _qmap(b, s, j):
            # lower-clamp to the causal frontier, upper-clamp to the last
            # real Q block (Tk > Tq puts whole k blocks past every q —
            # the body is skipped there, but the fetch must stay in range)
            # and, under a window, to the last Q block that sees K block s
            last = nq - 1
            if window is not None:
                last = jnp.minimum(
                    last, _window_last_q_block(s, bq, bk, window))
            return (b, jnp.minimum(jnp.maximum(j, (s * bk) // bq), last),
                    0)
    else:
        def _qmap(b, s, j):
            return (b, j, 0)
    kspec = pl.BlockSpec((1, bk, D), lambda b, s, j: (b, s, 0))
    # grouped heads: every Q head reads its group's K/V tile and writes a
    # dK/dV of its own (one kernel body for both cases); the group's sum
    # is taken below, in XLA
    kin = kspec if group == 1 else pl.BlockSpec(
        (1, bk, D), lambda b, s, j: (b // group, s, 0))
    kv_shapes = [jax.ShapeDtypeStruct((B * H, Tk, D), k.dtype),
                 jax.ShapeDtypeStruct((B * H, Tk, D), v.dtype)]
    kv_scratch = [pltpu.VMEM((bk, D), jnp.float32),
                  pltpu.VMEM((bk, D), jnp.float32)]
    call = functools.partial(
        pl.pallas_call,
        functools.partial(_bwd_fused_kernel, with_dq=fused, block_q=bq,
                          block_k=bk, **static),
        grid=(B * H, Tk // bk, Tq // bq),
        in_specs=[
            _smem_spec(),
            _smem_spec(),
            _smem_spec(),
            pl.BlockSpec((1, bq, D), _qmap),
            kin,
            kin,
            pl.BlockSpec((1, bq, D), _qmap),
            pl.BlockSpec((1, bq, _LSE_LANES), _qmap),
            pl.BlockSpec((1, bq, _LSE_LANES), _qmap),
        ],
        interpret=interpret)
    if fused:
        dq, dk, dv = call(
            out_shape=[jax.ShapeDtypeStruct(qr.shape, q.dtype)] + kv_shapes,
            out_specs=[pl.BlockSpec((1, Tq, D), lambda b, s, j: (b, 0, 0)),
                       kspec, kspec],
            scratch_shapes=[pltpu.VMEM((Tq, D), jnp.float32)] + kv_scratch,
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=_FUSED_BWD_VMEM_LIMIT),
        )(*args)
    else:
        dk, dv = call(out_shape=kv_shapes, out_specs=[kspec, kspec],
                      scratch_shapes=kv_scratch)(*args)

    if group > 1:
        dk, dv = (x.reshape(B, H // group, group, Tk, D).astype(
            jnp.float32).sum(axis=2).astype(x.dtype) for x in (dk, dv))
    return (dq.reshape(B, H, Tq, D), dk.reshape(B, H // group, Tk, D),
            dv.reshape(B, H // group, Tk, D))


def _repeat_kv(x, heads):
    """K or V with each head repeated for the Q heads of its group."""
    group = heads // x.shape[1]
    return x if group == 1 else jnp.repeat(x, group, axis=1)


def _xla_scores(q, k, causal, scale, seq_lens, window=None):
    """Masked, scaled [B, H, Tq, Tk] scores of the unfused composition."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   _repeat_kv(k, q.shape[1]).astype(jnp.float32)) * scale
    Tq, Tk = q.shape[2], k.shape[2]
    if causal:
        mask = jnp.tril(jnp.ones((Tq, Tk), bool))
        if window is not None:
            mask = jnp.logical_and(mask, ~jnp.tril(mask, -int(window)))
        s = jnp.where(mask[None, None], s, _NEG)
    elif window is not None:
        raise ValueError("a window needs causal attention")
    if seq_lens is not None:
        k_pos = jnp.arange(Tk)[None, None, None, :]
        valid = k_pos < jnp.maximum(seq_lens.astype(jnp.int32), 1).reshape(
            -1, 1, 1, 1)
        s = jnp.where(valid, s, _NEG)
    return s


def _xla_attention_lse(q, k, v, causal, scale, seq_lens=None, rate=0.0,
                       rng_key=None, window=None):
    """(out, lse) in plain XLA — the differentiable fallback matching
    ``flash_attention_lse``'s two outputs (the PADDLE_TPU_FLASH_BWD
    escape hatch and the op lowering's non-TPU branch, which must bind
    the program's Lse output). With dropout it draws its own jax.random
    mask — statistically, not bitwise, equivalent to the kernel's hash
    RNG; the lse is of the pre-dropout softmax, as in the kernel."""
    s = _xla_scores(q, k, causal, scale, seq_lens, window)
    lse = jax.scipy.special.logsumexp(s, axis=-1)
    w = jnp.exp(s - lse[..., None])
    if rate > 0.0:
        from paddle_tpu.ops.common import hash_keep_mask

        if rng_key is None:
            rng_key = jax.random.PRNGKey(0)
        keep = hash_keep_mask(rng_key, w.shape, rate)
        w = jnp.where(keep, w / (1.0 - rate), 0.0)
    out = jnp.einsum("bhqk,bhkd->bhqd", w,
                     _repeat_kv(v, q.shape[1]).astype(jnp.float32))
    return out.astype(q.dtype), lse


def _xla_attention(q, k, v, causal, scale, seq_lens=None, rate=0.0,
                   rng_key=None, window=None):
    """Unfused reference composition (and the off-TPU fallback)."""
    return _xla_attention_lse(q, k, v, causal, scale, seq_lens, rate,
                              rng_key, window)[0]


def _check_tileable(q, k, block_q, block_k):
    Tq, Tk = q.shape[2], k.shape[2]
    bq, bk = min(block_q, Tq), min(block_k, Tk)
    if Tq % bq or Tk % bk:
        raise ValueError(
            "flash_attention needs Tq/Tk divisible by the (clamped) block "
            "sizes, got Tq=%d Tk=%d blocks=(%d, %d); use fused_attention "
            "for automatic XLA fallback on odd shapes" % (Tq, Tk, bq, bk))


_BLOCK_TABLE_CACHE = None


def _block_table():
    """Sweep table, cached only on a SUCCESSFUL load — a transient read
    failure (e.g. the file mid-rewrite by the sweep's incremental dump)
    must not pin the heuristic fallback for the process lifetime."""
    global _BLOCK_TABLE_CACHE
    if _BLOCK_TABLE_CACHE is None:
        import json
        import os

        path = os.path.join(os.path.dirname(__file__),
                            "flash_block_table.json")
        try:
            with open(path) as f:
                _BLOCK_TABLE_CACHE = json.load(f)
        except (OSError, ValueError):  # pragma: no cover
            return {}
    return _BLOCK_TABLE_CACHE


def _table_row(t, dtype):
    """Nearest swept row for (dtype, seq): an int (one block for every
    kernel) or a dict {"fwd": int, "bwd": [block_q, block_k]} where the
    fused backward was swept apart from the forward (it keeps a K/V tile
    and the whole dQ resident, the forward a Q tile)."""
    table = _block_table().get(
        jnp.dtype(dtype).name if dtype is not None else "bfloat16")
    if not table:
        return None
    return table[min(table, key=lambda s: abs(int(s) - t))]


def pick_block(t, dtype=None):
    """Forward-kernel block choice, driven by the committed sweep table
    (flash_block_table.json, produced on real hardware by
    tools/flash_block_sweep.py with an interleaved median-of-reps
    protocol — the jit kernel-benchmark discipline of the reference's
    operators/jit/README.en.md). Lookup is by (dtype, nearest swept seq);
    the winning block is clamped to one that tiles ``t``. Heuristic
    fallback (256 when it tiles) if the table is absent. Shared by the
    fused_attention dispatch and chip_smoke.py's kernel check."""
    row = _table_row(t, dtype)
    if row is not None:
        if isinstance(row, dict):
            row = row.get("fwd", 256)
        for blk in (int(row), 256, 128):
            if t % blk == 0 and t >= blk:
                return blk
    return 256 if t % 256 == 0 and t >= 256 else 128


def pick_bwd_blocks(tq, tk, dtype, default):
    """(block_q, block_k) of the fused backward kernel: the table's
    ``bwd`` pair (tools/flash_block_sweep.py --bwd, on the chip) when
    ``default``, the caller's blocks, are the table's own forward choice
    and the pair tiles the shapes; ``default`` otherwise, so an explicit
    block choice (e.g. to bound VMEM) and off-table shapes are never
    overridden. The two-kernel form runs ``default``."""
    row = _table_row(tk, dtype)
    pair = row.get("bwd") if isinstance(row, dict) else None
    if (pair and default == (min(pick_block(tq, dtype), tq),
                             min(pick_block(tk, dtype), tk))
            and tq % int(pair[0]) == 0 and tk % int(pair[1]) == 0):
        return int(pair[0]), int(pair[1])
    return default


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(6, 7, 8, 9, 10, 11, 12))
def flash_attention_lse(q, k, v, seq_lens=None, offsets=None, seed=0,
                        causal=False, scale=None, rate=0.0, block_q=128,
                        block_k=128, interpret=False, window=None):
    """[B, H, T, D] attention via the Pallas kernels, returning
    ``(out, lse)`` where ``lse`` is the per-row logsumexp of the scaled
    (and masked) scores, [B, H, Tq] float32.

    This is the ring-attention building block: ``offsets`` ([2] int32,
    traced — [q_off, k_off]) places the Q and K blocks at global sequence
    positions so causal masking works across ring steps, and the exposed
    lse lets the caller merge per-step partial outputs with the standard
    logaddexp rescaling. Offsets need not be block-aligned: any row whose
    every key lands ahead of the causal frontier publishes out = 0 with
    lse ~= -1e30 (the kernels guard the fully-masked-row case), which the
    merge maps to weight 0. The lse cotangent is folded into the backward
    kernels' delta (see ``_flash_backward``), so differentiating through
    the merge costs no extra kernel.

    ``seq_lens`` ([B] int) masks keys at positions >= len (padding mask);
    lengths are clamped to >= 1, so a fully-empty sequence attends to key
    position 0 rather than producing NaNs — callers with genuinely empty
    rows must mask the corresponding outputs/loss themselves. ``rate`` is
    in-kernel attention-weight dropout reproduced exactly in the backward
    kernels from ``seed``. Tq/Tk must divide by the (clamped) block sizes
    (ValueError otherwise — ``fused_attention`` handles the fallback).
    ``window`` (static, causal only) keeps to each query its last
    ``window`` keys; K and V may come with fewer heads than Q, each
    shared by a group of consecutive Q heads.
    """
    out, lse = _fa_fwd(q, k, v, seq_lens, offsets, seed, causal, scale,
                       rate, block_q, block_k, interpret, window)[0]
    return out, lse


def flash_attention(q, k, v, seq_lens=None, seed=0, causal=False, scale=None,
                    rate=0.0, block_q=128, block_k=128, interpret=False,
                    window=None):
    """[B, H, T, D] attention via the Pallas kernels (output only — see
    ``flash_attention_lse`` for semantics; this keeps the historical
    signature used by the op lowerings and the benchmarks)."""
    out, _ = flash_attention_lse(q, k, v, seq_lens, None, seed, causal,
                                 scale, rate, block_q, block_k, interpret,
                                 window)
    return out


def _use_xla_bwd():
    from paddle_tpu import flags as _flags

    return _flags.get_flag("flash_bwd") == "xla"


def _fa_fwd(q, k, v, seq_lens, offsets, seed, causal, scale, rate, block_q,
            block_k, interpret, window=None):
    _check_tileable(q, k, block_q, block_k)
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    out, lse = _flash_forward(q, k, v, seq_lens, offsets, seed, causal,
                              scale, rate, block_q, block_k, interpret,
                              window)
    B, H, Tq = q.shape[0], q.shape[1], q.shape[2]
    lse_pub = lse[..., 0].reshape(B, H, Tq)
    return (out, lse_pub), (q, k, v, out, lse, seq_lens, offsets, seed)


def _fa_bwd_core(q, k, v, out, lse_k, g_out, g_lse, seq_lens, offsets,
                 seed, causal, scale, rate, block_q, block_k, interpret,
                 window=None):
    """Shared backward preamble for both custom_vjps: the
    PADDLE_TPU_FLASH_BWD=xla escape hatch (with its dropout/offset
    guards) and the _flash_backward dispatch. ``lse_k`` is the kernel-layout
    [B*H, Tq, _LSE_LANES] residual; ``g_lse`` the public [B, H, Tq]
    cotangent (or None)."""
    scale_ = scale if scale is not None else q.shape[-1] ** -0.5
    Tq = q.shape[2]
    if _use_xla_bwd():
        if rate > 0.0:
            raise RuntimeError(
                "PADDLE_TPU_FLASH_BWD=xla cannot be combined with in-kernel "
                "attention dropout: XLA cannot reproduce the kernel's hash "
                "mask. Unset the flag or set dropout_rate=0.")
        if offsets is not None:
            raise RuntimeError(
                "PADDLE_TPU_FLASH_BWD=xla cannot differentiate the "
                "offset (ring-step) form; unset the flag.")
        # escape hatch: recompute attention in XLA (O(T^2) intermediates)
        # for chips where the backward kernels fail to lower. Differentiate
        # the (out, lse) pair so a caller's lse cotangent is not dropped.
        B, H, _ = g_lse.shape if g_lse is not None else (q.shape[0],
                                                        q.shape[1], Tq)
        gl = (g_lse if g_lse is not None
              else jnp.zeros((B, H, Tq), jnp.float32))
        _, vjp = jax.vjp(
            lambda q_, k_, v_: _xla_attention_lse(
                q_, k_, v_, causal, scale_, seq_lens, window=window),
            q, k, v)
        return vjp((g_out, gl))
    return _flash_backward(q, k, v, out, lse_k, g_out, g_lse, seq_lens,
                           offsets, seed, causal, scale_, rate, block_q,
                           block_k, interpret, window)


def _fa_bwd(causal, scale, rate, block_q, block_k, interpret, window, res,
            g):
    q, k, v, out, lse, seq_lens, offsets, seed = res
    g_out, g_lse = g
    dq, dk, dv = _fa_bwd_core(q, k, v, out, lse, g_out, g_lse, seq_lens,
                              offsets, seed, causal, scale, rate, block_q,
                              block_k, interpret, window)
    return dq, dk, dv, None, None, None


flash_attention_lse.defvjp(_fa_fwd, _fa_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10, 11))
def flash_attention_raw_lse(q, k, v, seq_lens, seed, causal, scale, rate,
                            block_q, block_k, interpret, window=None):
    """``flash_attention_lse`` with the logsumexp kept in the kernel's
    native [B, H, Tq, _LSE_LANES] tiling (the form the fused_attention op
    saves so the backward read is relayout-free). Carrying its own
    custom_vjp makes the op LOWERING differentiable by jax autodiff —
    the remat lowering (engine/lowering.py lower_block_remat) gradients
    the composed forward instead of running the registered grad op, so
    the pallas_call must not be left to jax's default jvp."""
    out, lse = _flash_forward(q, k, v, seq_lens, None, seed, causal,
                              scale, rate, block_q, block_k, interpret,
                              window)
    B, H, Tq = q.shape[0], q.shape[1], q.shape[2]
    return out, lse.reshape(B, H, Tq, -1)


def _fa_raw_fwd(q, k, v, seq_lens, seed, causal, scale, rate, block_q,
                block_k, interpret, window=None):
    out, lse = _flash_forward(q, k, v, seq_lens, None, seed, causal,
                              scale, rate, block_q, block_k, interpret,
                              window)
    B, H, Tq = q.shape[0], q.shape[1], q.shape[2]
    lse_raw = lse.reshape(B, H, Tq, -1)
    return (out, lse_raw), (q, k, v, out, lse_raw, seq_lens, seed)


def _fa_raw_bwd(causal, scale, rate, block_q, block_k, interpret, window,
                res, g):
    q, k, v, out, lse_raw, seq_lens, seed = res
    g_out, g_lse_raw = g
    B, H, Tq, _ = q.shape
    # raw lse replicates the row value across lanes, so the public
    # cotangent is the lane sum (zeros when nothing consumed the lse)
    g_lse = None if g_lse_raw is None else g_lse_raw.sum(axis=-1)
    lse_k = lse_raw.reshape(B * H, Tq, -1)
    dq, dk, dv = _fa_bwd_core(q, k, v, out, lse_k, g_out, g_lse, seq_lens,
                              None, seed, causal, scale, rate, block_q,
                              block_k, interpret, window)
    return dq, dk, dv, None, None


flash_attention_raw_lse.defvjp(_fa_raw_fwd, _fa_raw_bwd)


def _on_tpu():
    try:
        return jax.default_backend() == "tpu"
    except Exception:  # pragma: no cover
        return False


def _flash_min_seq():
    try:
        from paddle_tpu import flags as _flags

        return int(_flags.get_flag("flash_min_seq"))
    except ValueError:  # pragma: no cover
        return 256


def flash_dispatch_ok(tq, tk):
    """Whether the Pallas kernels apply to a (Tq, Tk) attention: real
    TPU backend, tileable blocks, and at least
    PADDLE_TPU_FLASH_MIN_SEQ keys (the measured crossover — see
    ``fused_attention``). The single dispatch predicate shared by
    ``fused_attention`` and the ring-attention body so the two paths can
    never diverge."""
    tileable = tq % min(128, tq) == 0 and tk % min(128, tk) == 0
    return _on_tpu() and tileable and tk >= _flash_min_seq()


# --- SPMD (shard_map) wrapping ---------------------------------------------
# When a block is being traced for a mesh (engine/executor.py sets the
# parallel.mesh.spmd_lowering context), the attention dispatch and the
# direct flash backward wrap themselves in shard_map over the mesh's
# data-parallel and tensor axes — attention is independent per
# (batch, head), so splitting those dims is exact, each shard runs the
# Pallas kernels at local shape, and XLA never tries to partition a
# pallas_call it cannot see into. Same construction as
# parallel/ring_attention.py's sp-axis ring (which remains the sequence
# axis story; these wraps leave the sequence dim whole).

def _shard_map(body, mesh, in_specs, out_specs):
    """shard_map with the varying-manual-axes check off: a pallas_call's
    out_shape carries no ``vma``, so tracing the kernels under
    ``check_vma=True`` raises."""
    return shard_map(body, mesh=mesh, in_specs=in_specs,
                     out_specs=out_specs, check_vma=False)


def _spmd_attention_axes(B, H):
    """(mesh, batch_axes, head_axis) for the active SPMD lowering
    context, or None when no wrap applies: no context, 1-way axes, or
    indivisible batch/head dims (each falls back to the unwrapped
    single-device trace — a 1-device mesh is bit-identical by
    construction)."""
    from paddle_tpu.parallel.mesh import current_spmd

    spmd = current_spmd()
    if spmd is None:
        return None
    mesh, data_axes = spmd
    batch_axes = tuple(a for a in data_axes if a in mesh.axis_names)
    bsz = 1
    for a in batch_axes:
        bsz *= mesh.shape[a]
    if not (bsz > 1 and B % bsz == 0):
        batch_axes = ()
    head_axis = None
    if ("tp" in mesh.axis_names and "tp" not in batch_axes
            and mesh.shape["tp"] > 1 and H % mesh.shape["tp"] == 0):
        head_axis = "tp"
    if not batch_axes and head_axis is None:
        return None
    return mesh, batch_axes, head_axis


def _batch_spec_entry(batch_axes):
    if not batch_axes:
        return None
    return batch_axes if len(batch_axes) > 1 else batch_axes[0]


def _shard_seed(seed, mesh, batch_axes, head_axis):
    """Per-shard dropout seed: fold the linear shard index in so shards
    draw decorrelated masks (the kernel's hash RNG indexes by LOCAL
    (b, q, k) coordinates, which repeat across shards). Deterministic in
    (seed, shard), and identical in the forward and backward wraps, so
    the backward kernels still regenerate the forward's exact mask."""
    idx = jnp.int32(0)
    for a in tuple(batch_axes) + ((head_axis,) if head_axis else ()):
        idx = idx * jnp.int32(mesh.shape[a]) + jax.lax.axis_index(
            a).astype(jnp.int32)
    return jnp.asarray(seed, jnp.int32) + idx * jnp.int32(1000003)


def _dispatch_local(q, k, v, causal, scale, seq_lens, dropout_rate, seed,
                    force_pallas, raw_lse, window=None):
    """Single-device (or per-shard) dispatch core of
    ``dispatch_attention_lse``."""
    Tq, Tk = q.shape[2], k.shape[2]
    B, H = q.shape[0], q.shape[1]
    bq, bk = pick_block(Tq, q.dtype), pick_block(Tk, q.dtype)
    scale_ = scale if scale is not None else q.shape[-1] ** -0.5
    use_pallas = (force_pallas if force_pallas is not None
                  else flash_dispatch_ok(Tq, Tk))
    if use_pallas:
        if raw_lse:
            _check_tileable(q, k, bq, bk)
            return flash_attention_raw_lse(
                q, k, v, seq_lens, seed, causal, scale_,
                dropout_rate, bq, bk, not _on_tpu(), window)
        return flash_attention_lse(q, k, v, seq_lens, None, seed, causal,
                                   scale_, dropout_rate, bq, bk,
                                   not _on_tpu(), window)
    key = jax.random.PRNGKey(seed) if dropout_rate > 0.0 else None
    out, lse = _xla_attention_lse(q, k, v, causal, scale_, seq_lens,
                                  dropout_rate, key, window)
    if raw_lse:
        lse = jnp.broadcast_to(lse[..., None], (B, H, Tq, _LSE_LANES))
    return out, lse


def dispatch_attention_lse(q, k, v, causal=False, scale=None, seq_lens=None,
                           dropout_rate=0.0, seed=0, force_pallas=None,
                           raw_lse=False, window=None):
    """THE shared (out, lse) attention dispatch: the Pallas kernels when
    ``flash_dispatch_ok`` (block table + interpret flag resolved here, in
    exactly one place), the XLA composition otherwise. ``fused_attention``,
    the fused_attention op lowering, and the registered grad op's
    recompute fallback all route through this function, so the forward a
    gradient differentiates can never silently diverge from the forward
    that produced the saved Out.

    Under an active SPMD lowering context (the engine tracing a block
    for a mesh) the whole dispatch additionally wraps itself in
    ``shard_map`` over the mesh's data axes (batch dim) and ``tp`` axis
    (head dim) — exact per-(batch, head) decomposition, so sharded
    models get the flash kernels per shard instead of an XLA-partitioned
    approximation of the custom call.

    ``raw_lse=True`` returns the logsumexp in the kernel's native tiling
    carried as ``[B, H, Tq, _LSE_LANES]`` float32 (a major-dim-only
    reshape of the kernel's [B*H, Tq, LANES] — layout-preserving, and
    the leading dim keeps the build-time batch sentinel intact) instead
    of the public ``[B, H, Tq]``. The fused_attention op saves it this
    way so the backward kernels read it with zero relayout (the
    [B,H,T] <-> [B*H,T,1] round trip doesn't commute with TPU tiling;
    the round-5 seq-2048 trace showed 12 x ~0.08 ms/step of lse layout
    copies). Only meaningful on the forward-only (op) path — the
    custom_vjp keeps the public form."""
    # the head axis splits K/V too, so it is their head count that has to
    # divide (Q's is a multiple of it)
    spmd = _spmd_attention_axes(q.shape[0], k.shape[1])
    if spmd is None:
        return _dispatch_local(q, k, v, causal, scale, seq_lens,
                               dropout_rate, seed, force_pallas, raw_lse,
                               window)
    mesh, batch_axes, head_axis = spmd
    from jax.sharding import PartitionSpec as P

    bspec = _batch_spec_entry(batch_axes)
    qspec = P(bspec, head_axis, None, None)
    out_specs = (qspec,
                 P(bspec, head_axis, None, None) if raw_lse
                 else P(bspec, head_axis, None))
    seed_in = jnp.asarray(seed, jnp.int32)

    def body(q_, k_, v_, seed_, lens_):
        if dropout_rate > 0.0:
            seed_ = _shard_seed(seed_, mesh, batch_axes, head_axis)
        return _dispatch_local(q_, k_, v_, causal, scale, lens_,
                               dropout_rate, seed_, force_pallas, raw_lse,
                               window)

    if seq_lens is not None:
        fn = _shard_map(
            body, mesh=mesh,
            in_specs=(qspec, qspec, qspec, P(), P(bspec)),
            out_specs=out_specs)
        return fn(q, k, v, seed_in, seq_lens)
    fn = _shard_map(
        lambda q_, k_, v_, s_: body(q_, k_, v_, s_, None), mesh=mesh,
        in_specs=(qspec, qspec, qspec, P()),
        out_specs=out_specs)
    return fn(q, k, v, seed_in)


def flash_backward_spmd(q, k, v, out, lse_k, g, seq_lens, seed, causal,
                        scale, rate, block_q, block_k, interpret,
                        window=None):
    """``_flash_backward`` for the registered grad op, shard_mapped over
    the active mesh's data/tp axes when an SPMD lowering context is up
    (per-(batch, head) independence makes the wrap exact — the same
    decomposition the forward dispatch used, so the saved Out/Lse shards
    line up); plain direct call otherwise. ``lse_k`` arrives in the
    kernel's [B*H, Tq, LANES] layout; the wrap splits its leading dim as
    [B, H, Tq, LANES] (metadata-only) to shard batch and heads, and
    re-flattens per shard."""
    B, H, Tq, _D = q.shape
    spmd = _spmd_attention_axes(B, k.shape[1])
    if spmd is None:
        return _flash_backward(q, k, v, out, lse_k, g, None, seq_lens,
                               None, seed, causal, scale, rate, block_q,
                               block_k, interpret, window)
    mesh, batch_axes, head_axis = spmd
    from jax.sharding import PartitionSpec as P

    bspec = _batch_spec_entry(batch_axes)
    qspec = P(bspec, head_axis, None, None)
    lse4 = lse_k.reshape(B, H, Tq, -1)
    seed_in = jnp.asarray(seed, jnp.int32)

    def body(q_, k_, v_, out_, lse4_, g_, seed_, lens_):
        if rate > 0.0:
            seed_ = _shard_seed(seed_, mesh, batch_axes, head_axis)
        Bl, Hl = q_.shape[0], q_.shape[1]
        return _flash_backward(
            q_, k_, v_, out_, lse4_.reshape(Bl * Hl, Tq, -1), g_, None,
            lens_, None, seed_, causal, scale, rate, block_q, block_k,
            interpret, window)

    out_specs = (qspec, qspec, qspec)
    if seq_lens is not None:
        fn = _shard_map(
            body, mesh=mesh,
            in_specs=(qspec, qspec, qspec, qspec, qspec, qspec, P(),
                      P(bspec)),
            out_specs=out_specs)
        return fn(q, k, v, out, lse4, g, seed_in, seq_lens)
    fn = _shard_map(
        lambda q_, k_, v_, o_, l_, g_, s_: body(q_, k_, v_, o_, l_, g_,
                                                s_, None),
        mesh=mesh,
        in_specs=(qspec, qspec, qspec, qspec, qspec, qspec, P()),
        out_specs=out_specs)
    return fn(q, k, v, out, lse4, g, seed_in)


def fused_attention(q, k, v, causal=False, scale=None, seq_lens=None,
                    dropout_rate=0.0, seed=0, force_pallas=None, window=None):
    """Dispatch point for whole-attention fusion: the Pallas flash kernels
    on TPU for sequences of at least PADDLE_TPU_FLASH_MIN_SEQ (default
    256) keys, the plain-XLA composition elsewhere (short sequences, odd
    shapes, non-TPU backends).

    The threshold is measured, not aesthetic: at short T the [T, T] score
    matrix is tiny, XLA's batched matmul+softmax fusion wins, and flash's
    per-program overhead costs ~15% end-to-end on BERT seq-128; from
    ~256-512 keys up the O(T^2) materialization starts losing to the
    streaming kernel (1.1-1.3x at seq 2048) and flash's O(T) memory is
    what makes long-context training fit at all. ``seq_lens`` lengths are
    clamped to >= 1 (see flash_attention). ``force_pallas=True`` runs the
    kernel in interpreter mode off-TPU (tests)."""
    return dispatch_attention_lse(q, k, v, causal, scale, seq_lens,
                                  dropout_rate, seed, force_pallas,
                                  window=window)[0]
