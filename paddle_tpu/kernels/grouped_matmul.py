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
groups occupy, so the unused part of the buffer costs nothing. Four
products, each at a tiling of its own (``_tilings``): ``grouped_matmul``
forward; ``grouped_matmul_t`` (``gmm`` against the transposed weights) for
the rows' gradient of one product; ``grouped_matmul_pair_t`` for the rows'
gradient of two products of the same rows (the expert MLP's gate and up),
the one kernel whose body is this module's: megablox's grid, group
metadata and store mask, but two left tiles and two weight blocks a
visit, both products added in one float32 accumulator and the result tile
written once, where autodiff would round each product to the rows' dtype
and add them in a pass over ALL buffer rows; and
``grouped_weight_gradient`` (``tgmm``). The contraction is kept whole
where a weight block then fits VMEM, so that consecutive row tiles of one
expert find its weights resident instead of fetching them again.
``grouped_matmul`` alone carries a backward (``gmm`` + ``tgmm``, for
whoever differentiates a single product); the expert MLP composes the four
under its own (``ops/moe_ops.py``). Elsewhere, and for rows the kernels
cannot tile, every product is ``jax.lax.ragged_dot`` (``ragged_dot_general``
for the weights' gradient). Which of the two the chip runs, and the
tilings, were read on the chip at the benchmark's shapes (PERF.md,
Findings PR 29 and PR 34), not left to a flag.
"""

import collections
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


_Tilings = collections.namedtuple(
    "_Tilings", "forward rows_gradient weights_gradient pair_rows_gradient")


def _tilings(rows, k, n, item=2):
    """The four kernels' tilings of an ``[rows, k] x [G, k, n]`` product,
    each (rows, contraction, columns) of its own kernel's view."""
    tm = min(_TILE_ROWS, rows)

    def product(kk, nn, pairs=1):
        # [tm, kk] x [kk, tn], ``pairs`` of them into one [tm, tn]: the
        # contraction whole, the columns split
        kk = _lanes(kk)
        tn = next((w for w in _widths(nn) if (
            pairs * (2 * tm * kk + 2 * kk * w) + 2 * tm * w) * item
            + 4 * tm * w <= _VMEM_BUDGET), 128)
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

    return _Tilings(product(k, n), product(n, k), transposed(k, n),
                    product(n, k, 2))


def _by_kernel(rows, interpret):
    """Whether the Pallas kernels take a buffer of ``rows`` rows here (then
    interpreted exactly where this is not the TPU): decided by what the
    call site can see, not by a flag."""
    tm = min(_TILE_ROWS, rows)
    return (interpret or _on_tpu()) and rows % tm == 0 and tm % 8 == 0


def pair_by_kernel(rows, k, n, interpret=False):
    """Whether ``grouped_matmul_pair_t`` on ``[rows, n]`` gradients and
    ``[G, k, n]`` weights is the one kernel: whole tiles of rows and whole
    lanes both ways, where the kernels run at all."""
    return _by_kernel(rows, interpret) and k % 128 == 0 and n % 128 == 0


def _gmm_t(g, rhs, group_sizes, tiling, interpret):
    return _megablox().gmm(g, rhs, group_sizes, g.dtype, tiling,
                           transpose_rhs=True, interpret=interpret)


def _tgmm(lhs, g, group_sizes, groups, tiling, interpret):
    return _megablox().tgmm(lhs.swapaxes(0, 1), g, group_sizes, lhs.dtype,
                            tiling, num_actual_groups=groups,
                            interpret=interpret)


def _pair_gmm_t(g_a, g_b, rhs_a, rhs_b, group_sizes, tiling, interpret):
    """``g_a @ rhs_a[i]ᵀ + g_b @ rhs_b[i]ᵀ`` over each group's rows in one
    kernel: ``g_*`` [R, n], ``rhs_*`` [G, k, n] as the forward holds them.
    Grid, group metadata and store mask are megablox ``gmm``'s (column
    tiles x the row tiles that groups occupy, a tile that two groups share
    visited once for each); the body is this module's: two ``[tm, n]``
    left tiles and two ``[tk, n]`` weight blocks a visit, the contraction
    whole, both products added in one float32 accumulator and the
    ``[tm, tk]`` tile stored once, in the operands' dtype."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    megablox = _megablox()
    (rows, n), (groups, k, _) = g_a.shape, rhs_a.shape
    tm, tn, tk = tiling     # (rows, contraction, columns) of this view
    assert tn == n and k % tk == 0, (tiling, g_a.shape, rhs_a.shape)
    metadata, tiles = megablox.make_group_metadata(
        group_sizes=group_sizes, m=rows, tm=tm, start_group=jnp.int32(0),
        num_nonzero_groups=groups, visit_empty_groups=False)

    def kernel(offsets, group_ids, tile_ids, a, b, w_a, w_b, out, acc):
        dims = (((1,), (1,)), ((), ()))
        acc[...] = jax.lax.dot_general(
            a[...], w_a[...], dims, preferred_element_type=jnp.float32)
        acc[...] += jax.lax.dot_general(
            b[...], w_b[...], dims, preferred_element_type=jnp.float32)
        mine = megablox._get_store_mask(
            grid_id=pl.program_id(1),
            group_metadata=(offsets, group_ids, tile_ids), tm=tm, tn=tk)
        out[...] = jax.lax.select(
            mine, acc[...], out[...].astype(jnp.float32)).astype(out.dtype)

    def left(k_i, t, offsets, group_ids, tile_ids):
        return tile_ids[t], 0

    def weights(k_i, t, offsets, group_ids, tile_ids):
        return group_ids[t], k_i, 0

    def result(k_i, t, offsets, group_ids, tile_ids):
        return tile_ids[t], k_i

    item = g_a.dtype.itemsize
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((rows, k), g_a.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            in_specs=[pl.BlockSpec((tm, tn), left)] * 2
            + [pl.BlockSpec((None, tk, tn), weights)] * 2,
            out_specs=pl.BlockSpec((tm, tk), result),
            grid=(k // tk, tiles),
            scratch_shapes=[pltpu.VMEM((tm, tk), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=4 * rows * k * n, transcendentals=0,
            bytes_accessed=(2 * rows * n * (k // tk) + rows * k
                            + 2 * groups * k * n) * item),
        interpret=interpret, name="gmm_pair",
    )(*metadata, g_a, g_b, rhs_a, rhs_b)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _pallas_gmm(lhs, rhs, group_sizes, tilings, interpret):
    megablox = _megablox()
    return megablox.gmm(lhs, rhs, group_sizes, lhs.dtype, tilings.forward,
                        interpret=interpret)


def _pallas_gmm_fwd(lhs, rhs, group_sizes, tilings, interpret):
    return (_pallas_gmm(lhs, rhs, group_sizes, tilings, interpret),
            (lhs, rhs, group_sizes))


def _pallas_gmm_bwd(tilings, interpret, res, g):
    lhs, rhs, group_sizes = res
    return (_gmm_t(g, rhs, group_sizes, tilings.rows_gradient, interpret),
            _tgmm(lhs, g, group_sizes, rhs.shape[0],
                  tilings.weights_gradient, interpret), None)


_pallas_gmm.defvjp(_pallas_gmm_fwd, _pallas_gmm_bwd)


def grouped_matmul(lhs, rhs, group_sizes, interpret=False):
    """``lhs`` [R, k] (rows sorted by group), ``rhs`` [G, k, n],
    ``group_sizes`` [G] int32 -> [R, n] in ``lhs``'s dtype, float32
    accumulation. ``interpret`` runs the Pallas kernels in interpret mode
    off the TPU (tests)."""
    rows = lhs.shape[0]
    rhs, group_sizes = rhs.astype(lhs.dtype), group_sizes.astype(jnp.int32)
    if _by_kernel(rows, interpret):
        return _pallas_gmm(
            lhs, rhs, group_sizes,
            _tilings(rows, rhs.shape[1], rhs.shape[2], lhs.dtype.itemsize),
            not _on_tpu())
    return jax.lax.ragged_dot(
        lhs, rhs, group_sizes,
        preferred_element_type=jnp.float32).astype(lhs.dtype)


def _ragged_t(g, rhs, group_sizes):
    return jax.lax.ragged_dot(g, rhs.swapaxes(1, 2), group_sizes,
                              preferred_element_type=jnp.float32)


def grouped_matmul_t(g, rhs, group_sizes, interpret=False):
    """The rows' gradient of one product: ``g`` [R, n] against ``rhs``
    [G, k, n] transposed -> [R, k] in ``g``'s dtype."""
    rows = g.shape[0]
    rhs, group_sizes = rhs.astype(g.dtype), group_sizes.astype(jnp.int32)
    if _by_kernel(rows, interpret):
        return _gmm_t(g, rhs, group_sizes, _tilings(
            rows, rhs.shape[1], rhs.shape[2],
            g.dtype.itemsize).rows_gradient, not _on_tpu())
    return _ragged_t(g, rhs, group_sizes).astype(g.dtype)


def grouped_matmul_pair_t(g_a, g_b, rhs_a, rhs_b, group_sizes,
                          interpret=False):
    """The rows' gradient of two products of the same rows: ``g_a @
    rhs_aᵀ + g_b @ rhs_bᵀ`` per group, ``g_*`` [R, n], ``rhs_*``
    [G, k, n] -> [R, k] in ``g_a``'s dtype. One kernel that adds both in
    its float32 accumulator where ``pair_by_kernel``; elsewhere two
    ``ragged_dot``s added in float32."""
    rows, (_, k, n) = g_a.shape[0], rhs_a.shape
    rhs_a, rhs_b = rhs_a.astype(g_a.dtype), rhs_b.astype(g_a.dtype)
    group_sizes = group_sizes.astype(jnp.int32)
    if pair_by_kernel(rows, k, n, interpret):
        return _pair_gmm_t(
            g_a, g_b, rhs_a, rhs_b, group_sizes,
            _tilings(rows, k, n, g_a.dtype.itemsize).pair_rows_gradient,
            not _on_tpu())
    return (_ragged_t(g_a, rhs_a, group_sizes)
            + _ragged_t(g_b, rhs_b, group_sizes)).astype(g_a.dtype)


def grouped_weight_gradient(lhs, g, group_sizes, interpret=False):
    """The weights' gradient: ``lhs`` [R, k] transposed against ``g``
    [R, n] over each group's rows -> [G, k, n] in ``lhs``'s dtype, from a
    float32 accumulator (a group without rows gets zeros). Not in float32:
    on the chip that read 0.14-0.19 ms a call slower (a smaller block
    stays, the result is twice the bytes) and Adam, which reads the
    gradient under its update, as much again (PERF.md, Findings PR 34)."""
    rows, groups = lhs.shape[0], group_sizes.shape[0]
    g, group_sizes = g.astype(lhs.dtype), group_sizes.astype(jnp.int32)
    if _by_kernel(rows, interpret):
        return _tgmm(lhs, g, group_sizes, groups, _tilings(
            rows, lhs.shape[1], g.shape[1],
            lhs.dtype.itemsize).weights_gradient, not _on_tpu())
    return jax.lax.ragged_dot_general(
        lhs, g, group_sizes, jax.lax.RaggedDotDimensionNumbers(
            dot_dimension_numbers=(((0,), (0,)), ((), ())),
            lhs_ragged_dimensions=[0], rhs_group_dimensions=[]),
        preferred_element_type=jnp.float32).astype(lhs.dtype)
