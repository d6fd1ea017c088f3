"""Operations and bytes of what a windowed, grouped-query decoder with a
mixture-of-experts layer adds to ``work.py``'s arithmetic: attention in
which a query sees only some keys, and the grouped matmuls of the experts
a chip holds. Same conventions: a multiply-add is two operations, nothing
recomputed counts, every operand and result moves once.

Checked examples: ``benchmarks/tests/test_work_moe.py``.
"""


def keys_seen(seq, window=None):
    """Keys that the queries 0..seq-1 of one causal sequence see in all:
    query ``t`` sees ``min(t + 1, window)``."""
    if window is None or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def masked_attention_flops(rows, q_heads, seq, head_dim, window=None):
    """(forward, backward) of one causal attention over the keys a query
    really sees: QK^T and PV forward (4 per key and head dimension); dV,
    dP, dQ, dK backward (8)."""
    unit = rows * q_heads * keys_seen(seq, window) * head_dim
    return 4 * unit, 8 * unit


def grouped_attention_bytes(rows, q_heads, kv_heads, seq, head_dim,
                            itemsize=2):
    """Q, O, dO, dQ at the query heads; K, V, dK, dV at the key/value
    heads; once each."""
    return 4 * rows * (q_heads + kv_heads) * seq * head_dim * itemsize


def masked_attention_least_seconds(rows, q_heads, kv_heads, seq, head_dim,
                                   window, peak):
    fwd, bwd = masked_attention_flops(rows, q_heads, seq, head_dim, window)
    return max((fwd + bwd) / peak["bf16_flops_per_s"],
               grouped_attention_bytes(rows, q_heads, kv_heads, seq,
                                       head_dim) / peak["hbm_bytes_per_s"])


def pairs_held(tokens, per_token, held, experts):
    """Token-expert pairs that fall on the experts held, in expectation:
    the router's weights are random and barely move in a run."""
    return tokens * per_token * held // experts


def grouped_matmul_flops(pairs, d, width):
    """(forward, backward) of one gated expert MLP over ``pairs`` rows:
    gate, up and down forward (3 matmuls of 2 * pairs * d * width); each
    one's two products backward."""
    unit = 2 * pairs * d * width
    return 3 * unit, 6 * unit


def grouped_matmul_bytes(pairs, d, width, experts=1, itemsize=2):
    """Forward and backward: the nine matmuls' operands and results, once
    each: a [pairs, d] and a [pairs, width] side and the ``experts``
    weights (or their gradients) of [d, width] every time."""
    return 9 * (pairs * (d + width) + experts * d * width) * itemsize


def grouped_matmul_least_seconds(pairs, d, width, experts, peak):
    fwd, bwd = grouped_matmul_flops(pairs, d, width)
    return max((fwd + bwd) / peak["bf16_flops_per_s"],
               grouped_matmul_bytes(pairs, d, width, experts)
               / peak["hbm_bytes_per_s"])
