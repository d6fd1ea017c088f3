"""The comparison that decides ``correct`` for a training cell.

Both sides give the same readings (``reference/common.py`` ``follow`` for
the reference, the driver for the timed program): the loss of each of the
first steps, the norm of every leaf's first gradient as the optimizer got
it, and the norm of every leaf's change after those steps. These numbers
come out, and a cell's workload file gives a limit to each one it holds
(``null``: read and printed, not held; PERF.md section 2 says which and why):

    loss_gap            worst step: |loss - reference| / |reference|
    loss1_gap           the same of the first step alone (the forward)
    grad_gap            worst leaf: | ||g|| - ||g_ref|| | / max(||g_ref||, median)
    change_gap          worst leaf: the same of the change after the steps
    grad_gap_median     the median leaf's gap instead of the worst's
    change_gap_median   likewise

A gap is between the two norms, not the norm of a difference, and is
measured against the reference's norm of that leaf or of the median leaf,
whichever is larger (some gradients are all but zero). Leaves whose
reference gradient is under a thousandth of the median leaf's move under
Adam by round-off alone and are left out of the change's gaps.
"""

import math
import statistics

NEGLIGIBLE_GRADIENT = 1e-3


def _leaf_gaps(got, want, skip=()):
    """-> (worst gap, its leaf, median gap) over the leaves of ``want``."""
    floor = statistics.median(want.values())
    gaps = []
    for name, ref in want.items():
        if name in skip:
            continue
        value = got.get(name)
        if value is None or not math.isfinite(value):
            return float("inf"), name, float("inf")
        gaps.append((abs(value - ref) / max(ref, floor, 1e-30), name))
    worst, where = max(gaps)
    return worst, where, statistics.median(g for g, _ in gaps)


def numbers(program, reference):
    """-> [(name, value, worst leaf or step)] of the numbers above."""
    loss_gaps = [abs(a - b) / max(abs(b), 1e-30) if math.isfinite(a)
                 else float("inf")
                 for a, b in zip(program["losses"], reference["losses"])]
    loss_gap = max(loss_gaps)
    step = "step%d" % (loss_gaps.index(loss_gap) + 1)
    grads = reference["grad_norms"]
    floor = statistics.median(grads.values())
    still = {n for n, g in grads.items() if g < NEGLIGIBLE_GRADIENT * floor}
    grad_gap, grad_leaf, grad_median = _leaf_gaps(
        program["grad_norms"], grads)
    change_gap, change_leaf, change_median = _leaf_gaps(
        program["change_norms"], reference["change_norms"], skip=still)
    return [("loss_gap", loss_gap, step),
            ("loss1_gap", loss_gaps[0], "step1"),
            ("grad_gap", grad_gap, grad_leaf),
            ("change_gap", change_gap, change_leaf),
            ("grad_gap_median", grad_median, "median leaf"),
            ("change_gap_median", change_median, "median leaf")]


def judge(program, reference, limits):
    """-> (correct, {name: {"value", "limit", "at"}}). A number whose
    limit is ``null`` or absent is read and printed, and not held."""
    compared, correct = {}, True
    for name, value, where in numbers(program, reference):
        limit = limits.get(name)
        compared[name] = {"value": value, "limit": limit, "at": where}
        if limit is None:
            continue
        if not (value <= limit):
            correct = False
    return correct, compared
