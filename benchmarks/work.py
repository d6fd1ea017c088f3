"""Operations and bytes that the mathematics of a step needs, from the
configuration's shapes: what ``step_mfu_pct`` and ``flash_roofline_pct``
divide. Nothing recomputed counts (a backward kernel that rebuilds the
scores, a rematerialised layer), and neither does anything that is not a
matmul or a convolution. A multiply-add is two operations.

Here: the chips' peaks and the attention arithmetic that models share. A
whole step's count belongs to its model and lives with the plain
reference, ``reference/<name>.py`` ``step_flops(cfg, rows)``, so that a
new model brings its count in a file of its own.

Checked examples: ``benchmarks/tests/test_work.py``.
"""

import json
import os

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")


def peaks(device_kind):
    """The chip's published peaks; a device that is not in the table is an
    error, not a default."""
    with open(PEAKS_FILE) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError("no peaks for device kind %r in %s"
                       % (device_kind, PEAKS_FILE))
    return table[device_kind]


# -- attention ----------------------------------------------------------------

def attention_flops(rows, heads, seq, head_dim):
    """(forward, backward) of one attention: QK^T and PV forward
    (4*B*H*T^2*d); dV, dP, dQ, dK backward (8*B*H*T^2*d)."""
    unit = rows * heads * seq * seq * head_dim
    return 4 * unit, 8 * unit


def attention_bytes(rows, heads, seq, head_dim, itemsize=2):
    """Q, K, V, O forward; dO, dQ, dK, dV backward; once each."""
    return 8 * rows * heads * seq * head_dim * itemsize


def attention_least_seconds(rows, heads, seq, head_dim, peak):
    """The least time the chip could take for one attention, forward and
    backward, and which bound binds."""
    fwd, bwd = attention_flops(rows, heads, seq, head_dim)
    by_compute = (fwd + bwd) / peak["bf16_flops_per_s"]
    by_bytes = (attention_bytes(rows, heads, seq, head_dim)
                / peak["hbm_bytes_per_s"])
    return max(by_compute, by_bytes), \
        "compute" if by_compute >= by_bytes else "bytes"
