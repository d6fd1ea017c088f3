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
groups occupy, so the unused part of the buffer costs nothing. Six kinds
of kernel, each at a tiling of its own (``_tilings``, ``gate_tile_rows``).
Four products: ``grouped_matmul`` forward; ``grouped_matmul_t`` (``gmm``
against the transposed weights) for the rows' gradient of one product;
``grouped_matmul_pair_t`` for the rows' gradient of two products of the
same rows (the expert MLP's gate and up), whose body is this module's:
megablox's grid, group metadata and store mask, but two left tiles and two
weight blocks a visit, both products added in one float32 accumulator and
the result tile written once, where autodiff would round each product to
the rows' dtype and add them in a pass over ALL buffer rows; and
``grouped_weight_gradient`` (``tgmm``). And the elementwise middle of the
expert MLP between them, ``gated`` (``weight * silu(gate) * up``) and its
transpose ``gated_t``, this module's too: the rows are sorted by group and
the dead ones stand at the end, so the live rows are a prefix of the
buffer and the grid is as long as that prefix, ``(cdiv(sum(group_sizes),
tm),)``, a traced length and no group metadata, where XLA's fusions pass
over all rows of a buffer of which an eighth to two fifths are live. The
contraction is kept whole where a weight block then fits VMEM, so that
consecutive row tiles of one expert find its weights resident instead of
fetching them again. ``grouped_matmul`` alone carries a backward (``gmm`` +
``tgmm``, for whoever differentiates a single product); the expert MLP
composes the six under its own (``ops/moe_ops.py``). Elsewhere, and for
rows the kernels cannot tile, every product is ``jax.lax.ragged_dot``
(``ragged_dot_general`` for the weights' gradient) and the gate XLA's
(``silu_gate`` over all rows). Which of the two the chip runs, and the
tilings, were read on the chip at the benchmark's shapes (PERF.md,
Findings PRs 29, 34 and 36), not left to a flag.
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


def gate_tile_rows(rows, width, item=2):
    """Rows of a tile of the elementwise gate between the products: the
    width whole, and the transposed kernel's five ``[rows, width]`` blocks
    (three read, two written), each double-buffered, inside the budget."""
    tm = min(_TILE_ROWS, rows)
    while 2 * 5 * tm * _lanes(width) * item > _VMEM_BUDGET and tm % 16 == 0:
        tm //= 2
    return tm


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


def gate_by_kernel(rows, width, interpret=False):
    """Whether the gate between the products, on ``[rows, width]`` results
    of ``grouped_matmul``, is ``gated`` / ``gated_t``: whole tiles of rows
    and whole lanes, where the kernels run at all."""
    return _by_kernel(rows, interpret) and width % 128 == 0


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


def silu_gate(gate, up, row_weight):
    """The middle of the expert MLP: ``row_weight * silu(gate) * up`` in
    float32, as ``gate``'s dtype; ``gate`` and ``up`` [R, w], ``row_weight``
    [R] float32. Called on whole arrays this is XLA's form, a pass over ALL
    buffer rows, for what the kernels do not take (``gate_by_kernel``); the
    kernels' body is the same function on a tile."""
    return (jax.nn.silu(gate.astype(jnp.float32)) * up.astype(jnp.float32)
            * row_weight[:, None]).astype(gate.dtype)


def _gate_kernel(transposed, tiles, gate, up, weight, *refs):
    """One ``[tm, w]`` tile of ``silu_gate`` or, ``transposed``, of its
    ``jax.vjp``. The tile's weights arrive along the lanes (``[1, tm]`` of
    the ``[1, R]`` view); the weights' gradient, the rows' sums over ``w``,
    goes back the same way."""
    if transposed:
        d_hidden, d_gate, d_up, d_weight = refs
        back = jax.vjp(silu_gate, gate[...], up[...], weight[0])[1]
        d_gate[...], d_up[...], d_weight[0, :] = back(d_hidden[...])
    else:
        refs[0][...] = silu_gate(gate[...], up[...], weight[0])


def live_tiles(group_sizes, tm):
    """Tiles of ``tm`` rows that hold a live row: the rows are sorted by
    group and the dead ones stand at the end, so the live ones are a
    prefix of the buffer."""
    return (jnp.sum(group_sizes, dtype=jnp.int32) + (tm - 1)) // tm


@functools.partial(jax.jit, static_argnums=(5, 6))
def _gate(gate, up, row_weight, d_hidden, group_sizes, tm, interpret):
    """``gated`` (``d_hidden`` None) or ``gated_t`` as one kernel whose
    grid is the buffer's live prefix, ``(live_tiles,)``, a traced length:
    no group metadata, the kernel does not care which expert a row is on.
    Tiles past the prefix are never visited; the partly live last one is
    computed whole. Jitted as megablox's kernels are: the forward's call
    and its replay inside the grad op then lower to one function, and XLA
    merges the two custom calls (a bare ``pallas_call`` carries the trace's
    name, ``gate`` / ``jvp(gate)``, into its body, and ran twice)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows, width = gate.shape
    assert rows % tm == 0, (gate.shape, tm)
    transposed = d_hidden is not None
    wide = pl.BlockSpec((tm, width), lambda i, tiles: (i, 0))
    along = pl.BlockSpec((1, tm), lambda i, tiles: (0, i))
    like = jax.ShapeDtypeStruct(gate.shape, gate.dtype)
    tiles = live_tiles(group_sizes, tm)[None]
    out = pl.pallas_call(
        functools.partial(_gate_kernel, transposed),
        out_shape=(like, like, jax.ShapeDtypeStruct(
            (1, rows), jnp.float32)) if transposed else like,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            in_specs=[wide, wide, along] + ([wide] if transposed else []),
            out_specs=(wide, wide, along) if transposed else wide,
            grid=(tiles[0],)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        cost_estimate=pl.CostEstimate(
            flops=(14 if transposed else 5) * rows * width,
            transcendentals=rows * width,
            bytes_accessed=(5 if transposed else 3) * rows * width
            * gate.dtype.itemsize),
        # the gradients take the places of gate and up, whose last use
        # this is: a tile is read whole before it is written
        input_output_aliases={1: 0, 2: 1} if transposed else {},
        interpret=interpret, name="gate_t" if transposed else "gate",
    )(tiles, gate, up, row_weight[None, :],
      *([d_hidden] if transposed else []))
    if transposed:
        return out[0], out[1], out[2][0]
    return out


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


def _gate_by_kernel(gate, up, row_weight, d_hidden, group_sizes, interpret):
    rows, width = gate.shape
    assert gate_by_kernel(rows, width, interpret), gate.shape
    return _gate(gate, up, row_weight, d_hidden,
                 group_sizes.astype(jnp.int32),
                 gate_tile_rows(rows, width, gate.dtype.itemsize),
                 not _on_tpu())


def gated(gate, up, row_weight, group_sizes, interpret=False):
    """``silu_gate`` on the live row tiles only: ``gate`` and ``up`` [R, w]
    (``grouped_matmul``'s results), ``row_weight`` [R] float32 -> [R, w] in
    ``gate``'s dtype. For call sites where ``gate_by_kernel``; what the
    result holds past the live prefix is unspecified."""
    return _gate_by_kernel(gate, up, row_weight, None, group_sizes,
                           interpret)


def gated_t(gate, up, row_weight, d_hidden, group_sizes, interpret=False):
    """``gated``'s transpose on the live row tiles only: what
    ``jax.vjp(silu_gate, gate, up, row_weight)[1](d_hidden)`` returns on
    them, float32 inside: (``d_gate``, ``d_up``) [R, w] in ``gate``'s dtype
    and ``d_weight`` [R] float32, each row's sum over ``w``."""
    return _gate_by_kernel(gate, up, row_weight, d_hidden, group_sizes,
                           interpret)


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
