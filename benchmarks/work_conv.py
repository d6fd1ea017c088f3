"""Operations and bytes of a gated short convolution, the token mixer of a
conv layer: ``Out = C * conv_L(B * x)`` for an input ``[tokens, 3d]`` (the
chunks B, C, x) and a filter ``[d, L]``, a causal depthwise convolution of
L taps a channel over time. Conventions as ``work.py``: a multiply-add is
two operations, nothing recomputed counts, every operand and result moves
once. The count is of the op's arguments and results, whatever implements
it (XLA fusions today), so that a kernel is read by the same yardstick.

Checked examples: ``benchmarks/tests/test_lfm2_readers.py``.
"""


def short_conv_flops(tokens, d, taps):
    """(forward, backward). Forward a token and channel: B * x, ``taps``
    multiply-adds, the gate C (2 taps + 2). Backward: C * g, the taps'
    transpose for dp and their products for dw (2 taps each), and dB, dx
    and dC (4 taps + 4); p and c made again do not count."""
    unit = tokens * d
    return (2 * taps + 2) * unit, (4 * taps + 4) * unit


def short_conv_bytes(tokens, d, taps, itemsize=2):
    """Forward reads the input [tokens, 3d] and writes [tokens, d];
    backward reads the input and the cotangent [tokens, d] and writes the
    input's gradient [tokens, 3d]: 11 tokens d elements of ``itemsize``
    (the program's activation width). Beside them the float32 filter:
    read forward, read and its gradient written backward."""
    return 11 * tokens * d * itemsize + 3 * d * taps * 4


def short_conv_least_seconds(tokens, d, taps, peak, itemsize=2):
    """The least time the chip could take for one conv layer's operator,
    forward and backward: bound by bytes at any size (8 to 16 operations
    an element against 2 to 4 bytes)."""
    fwd, bwd = short_conv_flops(tokens, d, taps)
    return max((fwd + bwd) / peak["bf16_flops_per_s"],
               short_conv_bytes(tokens, d, taps, itemsize)
               / peak["hbm_bytes_per_s"])
