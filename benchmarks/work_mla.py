"""Operations and bytes of one causal attention whose q·k width differs
from its values' width, as latent attention trains it through the flash
kernels: Q and K ``dqk`` wide (the non-rotary and the rotary part of a
head together), V, the output and its cotangent ``dv`` wide, as many K/V
heads as Q heads (the shared rotary key is made for every head before the
kernels see it). Conventions as ``work.py``: a multiply-add is two
operations, nothing recomputed counts, every argument and result moves
once. At ``dqk == dv`` this is ``work_moe.masked_attention_least_seconds``
with as many K/V heads as Q heads.

Checked examples: ``benchmarks/tests/test_joyai_readers.py``.
"""

from benchmarks import work_moe


def mla_attention_flops(rows, heads, seq, dqk, dv):
    """(forward, backward) over the keys a causal query sees: forward
    S = Q K^T (``dqk``) and O = P V (``dv``); backward dV = P^T dO and
    dP = dO V^T (``dv``), dQ = dS K and dK = dS^T Q (``dqk``). The scores
    the backward makes again do not count."""
    pairs = rows * heads * work_moe.keys_seen(seq, None)
    return 2 * pairs * (dqk + dv), 2 * pairs * (2 * dqk + 2 * dv)


def mla_attention_bytes(rows, heads, seq, dqk, dv, itemsize=2):
    """Q, K and their gradients at ``dqk``; V, O, dO and dV at ``dv``;
    once each."""
    return rows * heads * seq * (4 * dqk + 4 * dv) * itemsize


def mla_attention_least_seconds(rows, heads, seq, dqk, dv, peak):
    """The least time the chip could take for one such attention, forward
    and backward: the larger of its operations at the MXU's peak and its
    bytes at the memory's."""
    fwd, bwd = mla_attention_flops(rows, heads, seq, dqk, dv)
    return max((fwd + bwd) / peak["bf16_flops_per_s"],
               mla_attention_bytes(rows, heads, seq, dqk, dv)
               / peak["hbm_bytes_per_s"])
