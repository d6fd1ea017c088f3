"""The Mamba-2 mixer's gated RMSNorm (``ops/ssm_ops.py``
``gated_rms_norm``) and its backward as two Pallas kernels over row
tiles, for X and Z [M, I] (the mixer's positions as rows), a scale [I] and
G groups of I / G channels:

    u = x · silu(z),   n = u · rstd,   rstd = rsqrt(mean_group(u²) + eps)
    y = n · scale

**Grid.** Both kernels walk the rows in tiles of ``tm``, each visit one
``[tm, I]`` tile of every operand, the width whole, so a group's
statistics are complete inside the visit. A visit walks the G groups'
``[tm, I / G]`` slices, whole lane tiles, in an unrolled loop; every
float32 array lives in VMEM and dies with its group.

- ``_fwd_kernel`` reads x and z in their dtypes and writes y in x's, and
  rstd, the backward's only residual beside x and z: float32 ``[G', M]``
  (G rounded up to a sublane tile), the rows along the lanes, so that
  the array is 4·G'·M bytes; a tile's ``[tm, 1]`` columns are put side
  by side on the lanes and transposed once.
- ``_bwd_kernel`` reads x, z, y's cotangent dy and rstd, forms u, n and
  dn = dy · scale again, and writes

      du = rstd · (dn − n · mean_group(dn · n))
      dx = du · silu(z),   dz = du · x · σ(z) (1 + z (1 − σ(z)))

  in x's and z's dtypes, and the tile's float32 partial sums of dy · n
  over its rows (eight of them, one a sublane), which XLA adds up into
  the scale's gradient.

**Rounding points** are the XLA form's: x and z are taken to float32, the
statistics, u, n and every gradient are float32, and only y, dx and dz
are rounded, once, to the inputs' dtypes.

The XLA form (``ssm_ops._gated_rms_norm_xla``) is the oracle
(``tests/test_gated_norm_kernel.py``) and runs where these do not
(``by_kernel``). Both calls are jitted, as ``ssd_scan``'s are: the forward
op's call and its replay inside the grad op (``jax.vjp`` of the lowering)
then lower to one function, and XLA merges the two custom calls.
"""
import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
_LANES = 128
_SUBLANES = 8
# what a call may ask of VMEM, handed to Mosaic as the kernel's limit (the
# default scoped limit is 16 MiB of the chip's 128), and what the tile's
# double-buffered blocks and a group's float32 temporaries may take of it
_VMEM_LIMIT = 48 * 2 ** 20
_VMEM_BUDGET = 32 * 2 ** 20
_TILE_ROWS = (512, 256, 128)


def _on_tpu():
    try:
        return jax.default_backend() == "tpu"
    except Exception:  # pragma: no cover
        return False


def _vmem_bytes(tm, width, groups, itemsizes):
    """A visit's double-buffered ``[tm, I]`` blocks of ``itemsizes`` and
    eight float32 ``[tm, I / G]`` temporaries of one group."""
    return 2 * tm * width * sum(itemsizes) + 8 * 4 * tm * (width // groups)


def tile_rows(rows, width, groups, itemsizes):
    """The largest row tile of ``_TILE_ROWS`` that divides ``rows`` and
    whose blocks fit the budget, or None."""
    for tm in _TILE_ROWS:
        if (rows % tm == 0
                and _vmem_bytes(tm, width, groups, itemsizes) <= _VMEM_BUDGET):
            return tm
    return None


def _backward_itemsizes(x, z):
    """x, z, dy, dx and dz: the backward's blocks, the larger kernel."""
    return (x.dtype.itemsize, z.dtype.itemsize) * 2 + (x.dtype.itemsize,)


def tiles(shape, groups, itemsizes):
    """Whether the kernels tile X and Z of ``shape`` in ``groups``: groups
    of whole 128-lane tiles, at most 128 of them (rstd's columns share a
    lane tile), and rows that divide into whole tiles that fit VMEM."""
    width = shape[-1]
    rows = 1
    for n in shape[:-1]:
        rows *= n
    return (0 < groups <= _LANES and width % groups == 0
            and (width // groups) % _LANES == 0
            and tile_rows(rows, width, groups, itemsizes) is not None)


def by_kernel(x, z, scale, groups):
    """Whether a ``gated_rms_norm`` call site on X, Z [..., I] and Scale
    [I] takes the kernels: the TPU, X and Z of one shape and sizes that
    ``tiles``. Everything else keeps the XLA form: the CPU, groups off the
    lane tiles (the rehearsal's 32 wide), rows in no whole tile. Decided
    by what the call site sees, no flag."""
    return (_on_tpu() and x.shape == z.shape
            and scale.shape == x.shape[-1:]
            and tiles(x.shape, groups, _backward_itemsizes(x, z)))


def _gated(x, z):
    """u = x · silu(z) in float32, as the XLA form makes it."""
    return x.astype(F32) * jax.nn.silu(z.astype(F32))


def _groups(width, groups):
    size = width // groups
    return [pl.ds(g * size, size) for g in range(groups)]


def _fwd_kernel(x_ref, z_ref, s_ref, y_ref, r_ref, *, groups, eps):
    tm, width = x_ref.shape
    lane = lax.broadcasted_iota(jnp.int32, (1, _LANES), 1)
    cols = jnp.zeros((tm, _LANES), F32)
    for g, at in enumerate(_groups(width, groups)):
        u = _gated(x_ref[:, at], z_ref[:, at])
        rstd = lax.rsqrt(jnp.mean(u * u, axis=1, keepdims=True) + eps)
        y_ref[:, at] = (u * rstd * s_ref[:, at]).astype(y_ref.dtype)
        cols = jnp.where(lane == g, rstd, cols)
    r_ref[...] = cols.T[:r_ref.shape[0]]


def _bwd_kernel(x_ref, z_ref, s_ref, r_ref, dy_ref, dx_ref, dz_ref, ds_ref,
                *, groups):
    tm, width = x_ref.shape
    lane = lax.broadcasted_iota(jnp.int32, (1, _LANES), 1)
    rows = r_ref[...]
    cols = jnp.concatenate(
        [rows, jnp.zeros((_LANES - rows.shape[0], tm), F32)], axis=0).T
    for g, at in enumerate(_groups(width, groups)):
        rstd = jnp.sum(jnp.where(lane == g, cols, 0.0), axis=1,
                       keepdims=True)
        x, z = x_ref[:, at].astype(F32), z_ref[:, at].astype(F32)
        sig = jax.nn.sigmoid(z)
        silu = z * sig
        u = x * silu
        n = u * rstd
        dy = dy_ref[:, at].astype(F32)
        dn = dy * s_ref[:, at]
        du = rstd * (dn - n * jnp.mean(dn * n, axis=1, keepdims=True))
        dx_ref[:, at] = (du * silu).astype(dx_ref.dtype)
        dz_ref[:, at] = (du * x * (sig * (1.0 + z * (1.0 - sig)))).astype(
            dz_ref.dtype)
        ds_ref[:, at] = jnp.sum((dy * n).reshape(
            tm // _SUBLANES, _SUBLANES, -1), axis=0)


def _params():
    return pltpu.CompilerParams(dimension_semantics=("parallel",),
                                vmem_limit_bytes=_VMEM_LIMIT)


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6))
def _forward(x, z, scale, groups, eps, tm, interpret):
    """(y [M, I] in x's dtype, rstd [G', M] float32) for x, z [M, I]."""
    rows, width = x.shape
    wide = pl.BlockSpec((tm, width), lambda i: (i, 0))
    padded = -(-groups // _SUBLANES) * _SUBLANES     # rstd's rows
    elems = rows * width
    return pl.pallas_call(
        functools.partial(_fwd_kernel, groups=groups, eps=eps),
        out_shape=(jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((padded, rows), F32)),
        grid=(rows // tm,),
        in_specs=[wide, wide, pl.BlockSpec((1, width), lambda i: (0, 0))],
        out_specs=(wide, pl.BlockSpec((padded, tm), lambda i: (0, i))),
        compiler_params=_params(),
        cost_estimate=pl.CostEstimate(
            flops=10 * elems, transcendentals=elems,
            bytes_accessed=elems * (2 * x.dtype.itemsize + z.dtype.itemsize)
            + 4 * (width + padded * rows)),
        interpret=interpret, name="gated_norm_fwd",
    )(x, z, scale.astype(F32).reshape(1, width))


@functools.partial(jax.jit, static_argnums=(5, 6, 7))
def _backward(x, z, scale, rstd, dy, groups, tm, interpret):
    """(dx, dz, dscale) for x, z, dy [M, I], the forward's rstd and the
    scale [I]: dx and dz in x's and z's dtypes, dscale in the scale's."""
    rows, width = x.shape
    wide = pl.BlockSpec((tm, width), lambda i: (i, 0))
    elems = rows * width
    partials = rows // tm * _SUBLANES
    dx, dz, ds = pl.pallas_call(
        functools.partial(_bwd_kernel, groups=groups),
        out_shape=(jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(z.shape, z.dtype),
                   jax.ShapeDtypeStruct((partials, width), F32)),
        grid=(rows // tm,),
        in_specs=[wide, wide, pl.BlockSpec((1, width), lambda i: (0, 0)),
                  pl.BlockSpec((rstd.shape[0], tm), lambda i: (0, i)), wide],
        out_specs=(wide, wide,
                   pl.BlockSpec((_SUBLANES, width), lambda i: (i, 0))),
        compiler_params=_params(),
        cost_estimate=pl.CostEstimate(
            flops=25 * elems, transcendentals=elems,
            bytes_accessed=elems * (3 * x.dtype.itemsize
                                    + 2 * z.dtype.itemsize)
            + 4 * (width + rstd.size + partials * width)),
        interpret=interpret, name="gated_norm_bwd",
    )(x, z, scale.astype(F32).reshape(1, width), rstd, dy.astype(x.dtype))
    return dx, dz, jnp.sum(ds, axis=0).astype(scale.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _norm(x, z, scale, groups, eps, tm, interpret):
    # the same call as the residuals' below, so that XLA merges the two
    return _forward(x, z, scale, groups, eps, tm, interpret)[0]


def _norm_fwd(x, z, scale, groups, eps, tm, interpret):
    y, rstd = _forward(x, z, scale, groups, eps, tm, interpret)
    return y, (x, z, scale, rstd)


def _norm_bwd(groups, eps, tm, interpret, res, dy):
    return _backward(*res, dy, groups, tm, interpret)


_norm.defvjp(_norm_fwd, _norm_bwd)


def norm(x, z, scale, groups, eps, interpret=False):
    """``ssm_ops._gated_rms_norm_xla`` by the kernels, for a call site
    where ``by_kernel``: X, Z [..., I], Scale [I] -> Y [..., I] in X's
    dtype, with the backward its kernel's. ``interpret`` runs them in
    Pallas's interpret mode off the TPU (tests)."""
    shape, width = x.shape, x.shape[-1]
    x2, z2 = x.reshape(-1, width), z.reshape(-1, width)
    tm = tile_rows(x2.shape[0], width, groups, _backward_itemsizes(x, z))
    assert tm and tiles(shape, groups, _backward_itemsizes(x, z)), (
        shape, groups)
    return _norm(x2, z2, scale, groups, float(eps), tm,
                 interpret).reshape(shape)
