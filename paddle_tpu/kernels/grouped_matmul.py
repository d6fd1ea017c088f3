"""Grouped matmul over the experts a chip holds: ``[R, k] x [G, k, n] ->
[R, n]`` where the rows of ``lhs`` are sorted by group and ``group_sizes``
says how many rows each group has. The rows past ``sum(group_sizes)`` (a
dropless buffer is sized for the worst routing, and most of it is unused)
are never read and what the result holds there is unspecified: callers
mask by the row count, never multiply by zero. How the rows get into that
buffer and out of it again is the sibling module's, ``row_permute.py``
(whole tiles on the MXU where these kernels run, XLA's gathers elsewhere).

On the TPU these are the megablox Pallas kernels (``jax.experimental
.pallas.ops.tpu.megablox``): their grids cover only the row tiles that
groups occupy, so the unused part of the buffer costs nothing. The
backward is two more of them (``gmm`` against the transposed weights for
the rows' gradient, ``tgmm`` for the weights'), each at a tiling of its
own (``_tilings``): the contraction is kept whole where a weight block then
fits VMEM, so that consecutive row tiles of one expert find its weights
resident instead of fetching them again. Elsewhere, and for rows the
kernels cannot tile, it is ``jax.lax.ragged_dot``, which XLA differentiates
itself. Which of the two the chip runs, and the tilings, were read on the
chip at the benchmark's shapes (PERF.md, Findings PR 29), not left to a
flag.
"""

import functools

import jax
import jax.numpy as jnp

# rows of a tile: a group's first and last tile are partly another group's
# and are visited once for each, so narrow tiles waste less at about a
# thousand rows a group; read on a v5e (PERF.md, Findings PR 29)
_TILE_ROWS = 256
# what a kernel's blocks may take of VMEM (double-buffered operands and
# result, float32 accumulator) under Mosaic's default scoped limit of 16 MiB
_VMEM_BUDGET = 13 * 2 ** 20


def _on_tpu():
    try:
        return jax.default_backend() == "tpu"
    except Exception:  # pragma: no cover
        return False


def _megablox():
    """The kernels' module (the package exports a function under the same
    name, with one tiling for all three kernels)."""
    import importlib

    return importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm")


def _lanes(x):
    return -(-x // 128) * 128


def _widths(n):
    """Tiles of ``n`` columns: ``n`` itself rounded up to 128 lanes, and
    the multiples of 128 that divide it, widest first."""
    whole = _lanes(n)
    return [whole] + [w for w in range(whole - 128, 0, -128) if n % w == 0]


def _tilings(rows, k, n, item=2):
    """(forward, rows' gradient, weights' gradient) tilings of an
    ``[rows, k] x [G, k, n]`` product, each (rows, contraction, columns) of
    its own kernel's view."""
    tm = min(_TILE_ROWS, rows)

    def product(kk, nn):
        # [tm, kk] x [kk, tn]: the contraction whole, the columns split
        kk = _lanes(kk)
        tn = next((w for w in _widths(nn) if (
            2 * tm * kk + 2 * kk * w + 2 * tm * w) * item + 4 * tm * w
            <= _VMEM_BUDGET), 128)
        return tm, kk, tn

    def transposed(kk, nn):
        # [kk, rows] x [rows, nn] -> [G, kk, nn]: the largest [tk, tn]
        # block of the result that fits stays while the group's rows
        # stream through
        fit = [(tk, tn) for tk in _widths(kk) for tn in _widths(nn) if (
            2 * tk * tm + 2 * tm * tn + 2 * tk * tn) * item + 4 * tk * tn
            <= _VMEM_BUDGET]
        tk, tn = max(fit, key=lambda t: t[0] * t[1]) if fit else (128, 128)
        return tm, tk, tn

    return product(k, n), product(n, k), transposed(k, n)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _pallas_gmm(lhs, rhs, group_sizes, tilings, interpret):
    megablox = _megablox()
    return megablox.gmm(lhs, rhs, group_sizes, lhs.dtype, tilings[0],
                        interpret=interpret)


def _pallas_gmm_fwd(lhs, rhs, group_sizes, tilings, interpret):
    return (_pallas_gmm(lhs, rhs, group_sizes, tilings, interpret),
            (lhs, rhs, group_sizes))


def _pallas_gmm_bwd(tilings, interpret, res, g):
    megablox = _megablox()
    lhs, rhs, group_sizes = res
    d_lhs = megablox.gmm(g, rhs, group_sizes, lhs.dtype, tilings[1],
                         transpose_rhs=True, interpret=interpret)
    d_rhs = megablox.tgmm(lhs.swapaxes(0, 1), g, group_sizes, rhs.dtype,
                          tilings[2], num_actual_groups=rhs.shape[0],
                          interpret=interpret)
    return d_lhs, d_rhs, None


_pallas_gmm.defvjp(_pallas_gmm_fwd, _pallas_gmm_bwd)


def grouped_matmul(lhs, rhs, group_sizes, interpret=False):
    """``lhs`` [R, k] (rows sorted by group), ``rhs`` [G, k, n],
    ``group_sizes`` [G] int32 -> [R, n] in ``lhs``'s dtype, float32
    accumulation. ``interpret`` runs the Pallas kernels in interpret mode
    off the TPU (tests)."""
    rows = lhs.shape[0]
    rhs, group_sizes = rhs.astype(lhs.dtype), group_sizes.astype(jnp.int32)
    tm = min(_TILE_ROWS, rows)
    if (interpret or _on_tpu()) and rows % tm == 0 and tm % 8 == 0:
        return _pallas_gmm(
            lhs, rhs, group_sizes,
            _tilings(rows, rhs.shape[1], rhs.shape[2], lhs.dtype.itemsize),
            interpret and not _on_tpu())
    return jax.lax.ragged_dot(
        lhs, rhs, group_sizes,
        preferred_element_type=jnp.float32).astype(lhs.dtype)
