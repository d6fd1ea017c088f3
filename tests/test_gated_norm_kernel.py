"""The Mamba-2 gated norm's two Pallas kernels (``kernels/gated_norm.py``)
against the XLA form (``ops/ssm_ops.py`` ``_gated_rms_norm_xla``) and a
float32 numpy reference, in Pallas's interpret mode on the CPU at small
sizes, and the rule that picks between them."""

import functools
from types import SimpleNamespace

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu import observability as obs
from paddle_tpu.kernels import gated_norm as kernel
from paddle_tpu.ops import ssm_ops

EPS = 1e-5


def _args(rng, shape, dtype):
    """X, Z, Scale and Y's cotangent; Z spread wide enough that silu's
    negative half and its saturation both show."""
    return (jnp.asarray(rng.randn(*shape), dtype),
            jnp.asarray(2.0 * rng.randn(*shape), dtype),
            jnp.asarray(1.0 + 0.3 * rng.randn(shape[-1]), jnp.float32),
            jnp.asarray(rng.randn(*shape), dtype))


def _numpy(x, z, scale, g, groups):
    """Y and the gradients of X, Z and Scale in float64 from the
    equations (the module's docstring), written out by hand."""
    x, z, scale, g = (np.asarray(v, np.float64) for v in (x, z, scale, g))
    sig = 1.0 / (1.0 + np.exp(-z))
    u = (x * z * sig).reshape(x.shape[:-1] + (groups, -1))
    rstd = 1.0 / np.sqrt(np.mean(u * u, -1, keepdims=True) + EPS)
    n = u * rstd
    dn = (g * scale).reshape(n.shape)
    du = (rstd * (dn - n * np.mean(dn * n, -1, keepdims=True))).reshape(
        x.shape)
    n = n.reshape(x.shape)
    return (n * scale, du * z * sig, du * x * sig * (1.0 + z * (1.0 - sig)),
            np.sum(g * n, axis=tuple(range(x.ndim - 1))))


def _both(norm, args):
    y, back = jax.vjp(norm, *args[:3])
    return [y] + list(back(args[3]))


NAMES = ("y", "dx", "dz", "dscale")

# (X's shape, groups): groups of 128 and 512 lanes, 2 and 8 of them; the
# rows in one tile of 128 or three
CASES = {
    "float32-2x128": (jnp.float32, (2, 64, 256), 2),
    "float32-8x128": (jnp.float32, (384, 1024), 8),
    "float32-2x512": (jnp.float32, (128, 1024), 2),
    "bfloat16-8x128": (jnp.bfloat16, (3, 128, 1024), 8),
    "bfloat16-8x512": (jnp.bfloat16, (256, 4096), 8),
}


@pytest.mark.parametrize("case", list(CASES))
def test_the_kernels_follow_the_xla_form_and_the_equations(case):
    """Y and the gradients of X, Z and Scale from the kernels against the
    XLA form's and against float64 numpy: in float32 within float32's
    rounding of both; in bf16 the output is the XLA form's bit for bit
    (the same float32 arithmetic, rounded once), the gradients within one
    rounding of it, and no further from the equations than it is."""
    dtype, shape, groups = CASES[case]
    args = _args(np.random.RandomState(len(case)), shape, dtype)
    got = _both(functools.partial(kernel.norm, groups=groups, eps=EPS,
                                  interpret=True), args)
    xla = _both(lambda x, z, s: ssm_ops._gated_rms_norm_xla(
        x, z, s, groups, EPS), args)
    exact = _numpy(*args, groups)
    for name, a, b, f in zip(NAMES, got, xla, exact):
        assert (a.shape, a.dtype) == (b.shape, b.dtype), name
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        scale = np.abs(f).max()
        if dtype == jnp.float32:
            assert np.abs(a - b).max() <= 1e-6 * scale, name
            assert np.abs(a - f).max() <= 2e-6 * scale, name
        else:
            if name == "y":
                np.testing.assert_array_equal(a, b)
            assert np.abs(a - b).max() <= 8e-3 * scale, name
            assert np.abs(a - f).max() <= (1.2 * np.abs(b - f).max()
                                           + 1e-6 * scale), name


def test_a_group_is_normalised_alone():
    """A change of one channel of X in one row moves that row's group of
    Y and nothing else, and scaling the group by a power of two moves
    nothing at all (the RMS takes the scale out, exactly at eps 0): the
    statistics are a (row, group)'s own."""
    x, z, scale, _ = _args(np.random.RandomState(5), (256, 512), jnp.float32)
    norm = functools.partial(kernel.norm, groups=4, eps=0.0, interpret=True)
    y = np.asarray(norm(x, z, scale))
    np.testing.assert_array_equal(
        np.asarray(norm(x.at[7, 128:256].multiply(16.0), z, scale)), y)
    moved = np.array(norm(x.at[7, 130].add(1.0), z, scale))
    assert np.abs(moved[7, 128:256] - y[7, 128:256]).min() > 0
    moved[7, 128:256] = y[7, 128:256]
    np.testing.assert_array_equal(moved, y)


def _lower(x, z, scale, groups):
    """The ``gated_rms_norm`` op's lowering as a step's lowering calls it,
    with ``ssm.gated_norm_kernel`` / ``ssm.gated_norm_xla`` read round
    it."""
    ctx = SimpleNamespace(op=SimpleNamespace(type="gated_rms_norm"),
                          executor=object())
    names = ("ssm.gated_norm_kernel", "ssm.gated_norm_xla")
    before = [obs.counter_value(n) for n in names]
    out = ssm_ops.gated_rms_norm(ctx, {"X": [x], "Z": [z], "Scale": [scale]},
                                 {"groups": groups, "epsilon": EPS})["Y"][0]
    return out, tuple(obs.counter_value(n) - w for n, w in zip(names, before))


# (X's shape, groups, Z's shape where it differs)
SHAPES = {
    "tiles": ((2, 64, 1024), 8, None),
    "one-group": ((128, 256), 1, None),
    "groups-of-64": ((2, 64, 512), 8, None),
    "groups-of-32": ((2, 64, 64), 2, None),
    "rows-off-the-tile": ((3, 50, 1024), 8, None),
    "z-of-another-shape": ((128, 256), 2, (128, 1, 256)),
}


@pytest.mark.parametrize("shape", list(SHAPES))
def test_by_kernel_takes_the_kernels_where_the_rule_says(monkeypatch, shape):
    """The kernels on the TPU where the last axis splits into groups of
    whole 128-lane tiles and the rows into whole tiles, and nowhere else:
    on the CPU, or for groups off the lanes (the rehearsal's 32 wide),
    rows in no whole tile or a Z of another shape on the TPU, the op runs
    the XLA form, equation for equation, and counts it as
    ``ssm.gated_norm_xla``."""
    x_shape, groups, z_shape = SHAPES[shape]
    x, z, scale, _ = _args(np.random.RandomState(3), x_shape, jnp.bfloat16)
    if z_shape:
        z = z.reshape(z_shape)
    assert not kernel.by_kernel(x, z, scale, groups)    # the CPU
    obs.set_enabled(True)
    if not z_shape:
        out, counted = _lower(x, z, scale, groups)
        np.testing.assert_array_equal(out, ssm_ops._gated_rms_norm_xla(
            x, z, scale, groups, EPS))
        assert counted == (0, 1)
    monkeypatch.setattr(kernel, "_on_tpu", lambda: True)
    takes = shape in ("tiles", "one-group")
    assert kernel.by_kernel(x, z, scale, groups) == takes
    if not takes:
        if not z_shape:
            assert _lower(x, z, scale, groups)[1] == (0, 1)
        return
    # the TPU's path, its kernels interpreted here
    monkeypatch.setattr(kernel, "norm", functools.partial(
        kernel.norm, interpret=True))
    out, counted = _lower(x, z, scale, groups)
    assert counted == (1, 0)
    np.testing.assert_array_equal(out, kernel.norm(x, z, scale, groups, EPS))
    np.testing.assert_array_equal(out, ssm_ops._gated_rms_norm_xla(
        x, z, scale, groups, EPS))


def test_the_row_tile_is_the_largest_that_fits():
    """The largest of 512, 256 and 128 rows that divides the rows and
    whose double-buffered blocks fit the budget: 256 for the cell's
    [8192, 4096] in bf16 (the backward's five blocks of 512 rows would
    take 42 MB), 512 at a quarter of the width, 128 where the rows or
    float32 ask it, none where the rows divide into no tile of 128."""
    bf16 = (2,) * 5
    assert kernel.tile_rows(8192, 4096, 8, bf16) == 256
    assert kernel.tile_rows(8192, 1024, 8, bf16) == 512
    assert kernel.tile_rows(384, 4096, 8, bf16) == 128
    assert kernel.tile_rows(8192, 4096, 8, (4,) * 5) == 128
    assert kernel.tile_rows(8192 + 64, 4096, 8, bf16) is None
