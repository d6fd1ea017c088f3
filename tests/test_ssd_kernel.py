"""The Mamba-2 scan's two Pallas kernels (``kernels/ssd_scan.py``) against
the plain form (``ops/ssm_ops.py`` ``_ssd_scan``), in Pallas's interpret
mode on the CPU at small sizes, and the rule that picks between them."""

import functools
from types import SimpleNamespace

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu import observability as obs
from paddle_tpu.kernels import ssd_scan as kernel
from paddle_tpu.ops import ssm_ops


def _args(rng, rows, t, heads, p, groups, dtype, n=128):
    """x, Δ, A_log, B, C, D and y's cotangent, with decays over a chunk
    from about 1 to about e^-20, so that the carried state matters."""
    def normal(*shape, scale=1.0):
        return jnp.asarray(scale * rng.randn(*shape), dtype)

    return (normal(rows, t, heads, p),
            jnp.asarray(np.log1p(np.exp(rng.randn(rows, t, heads) - 3.0)),
                        jnp.float32),
            jnp.asarray(np.log(rng.uniform(0.5, 4.0, heads)), jnp.float32),
            normal(rows, t, groups, n, scale=0.3),
            normal(rows, t, groups, n, scale=0.3),
            jnp.asarray(rng.randn(heads), jnp.float32),
            normal(rows, t, heads, p))


def _both(scan, args):
    y, back = jax.vjp(scan, *args[:-1])
    return [y] + list(back(args[-1]))


NAMES = ("y", "x", "dt", "A_log", "B", "C", "D")

# (rows, positions, heads, head size, groups): chunks of 128, so 384
# positions are three whole chunks and 300 two and a part; R = H / G
# heads a group, 128 / P of them on a lane tile
CASES = {
    "float32-whole-R2": (jnp.float32, (2, 384, 4, 64, 2)),
    "float32-padded-R1": (jnp.float32, (1, 300, 2, 128, 2)),
    "float32-padded-R8-two-tiles": (jnp.float32, (1, 300, 8, 32, 1)),
    "bfloat16-whole-R1": (jnp.bfloat16, (1, 384, 2, 128, 2)),
    "bfloat16-padded-R4": (jnp.bfloat16, (2, 300, 8, 64, 2)),
}


@pytest.mark.parametrize("case", list(CASES))
def test_the_kernels_follow_the_plain_form(case):
    """y and the gradients of x, Δ, A_log, B, C and D from the kernels
    against the plain form's: within float32's rounding in float32; in
    bf16 within one rounding of the result, and no further from the
    float32 plain form than the bf16 plain form is."""
    dtype, (rows, t, heads, p, groups) = CASES[case]
    args = _args(np.random.RandomState(len(case)), rows, t, heads, p,
                 groups, dtype)
    with jax.default_matmul_precision("highest"):
        got = _both(functools.partial(kernel.scan, chunk=128,
                                      interpret=True), args)
        plain = _both(lambda *a: ssm_ops._ssd_scan(*a, 128), args)
        wide = _both(lambda *a: ssm_ops._ssd_scan(*a, 128), [
            a.astype(jnp.float32) for a in args])
    for name, a, b, f in zip(NAMES, got, plain, wide):
        assert (a.shape, a.dtype) == (b.shape, b.dtype), name
        a, b, f = (np.asarray(v, np.float32) for v in (a, b, f))
        scale = np.abs(f).max()
        if dtype == jnp.float32:
            assert np.abs(a - b).max() <= 2e-5 * scale, name
        else:
            assert np.abs(a - b).max() <= 1e-2 * scale, name
            assert np.abs(a - f).max() <= (1.5 * np.abs(b - f).max()
                                           + 1e-3 * scale), name


def test_the_state_crosses_chunks_and_rows_start_from_zero():
    """A change at position 10 of the first row moves y from there to the
    third chunk and leaves the second row, and every earlier position,
    alone: the state is carried from chunk to chunk and reset at a row."""
    rows, t, heads, p, groups = 2, 384, 2, 64, 1
    x, dt, a_log, b, c, d, _ = _args(np.random.RandomState(2), rows, t,
                                     heads, p, groups, jnp.float32)
    dt = dt * 0.01      # slow decays: what enters at 10 is still there
    scan = functools.partial(kernel.scan, chunk=128, interpret=True)
    y = scan(x, dt, a_log, b, c, d)
    moved = scan(x.at[0, 10].add(1.0), dt, a_log, b, c, d)
    np.testing.assert_array_equal(moved[:, :10], y[:, :10])
    np.testing.assert_array_equal(moved[1], y[1])
    assert np.abs(np.asarray(moved[0, 256:] - y[0, 256:])).max() > 1e-4


def _lower(x, dt, b, c, chunk=128):
    """The ``ssd_scan`` op's lowering as a step's lowering calls it, with
    ``ssm.scan_kernel`` / ``ssm.scan_xla`` read around it."""
    heads = x.shape[2]
    ctx = SimpleNamespace(op=SimpleNamespace(type="ssd_scan"),
                          executor=object())
    names = ("ssm.scan_kernel", "ssm.scan_xla")
    before = [obs.counter_value(n) for n in names]
    ins = {"X": [x], "Dt": [dt], "DtBias": [jnp.zeros((heads,))],
           "ALog": [jnp.zeros((heads,))], "B": [b], "C": [c],
           "D": [jnp.ones((heads,))]}
    out = ssm_ops.ssd_scan(ctx, ins, {"chunk_size": chunk})["Out"][0]
    return out, tuple(obs.counter_value(n) - w for n, w in zip(names, before))


@pytest.mark.parametrize("shape", [
    "tiles", "float32", "chunk-64", "state-64", "head-48", "group-of-64"])
def test_by_kernel_refuses_the_cpu_and_what_does_not_tile(monkeypatch,
                                                          shape):
    """The predicate takes the kernels on the TPU for bf16 activations
    whose chunk, state and group of heads fill whole lanes with whole
    heads on each 128 lanes, and nowhere else: on the CPU, or for float32
    or odd sizes on the TPU, the op runs the plain form, equation for
    equation, and counts it as ``ssm.scan_xla``."""
    dtype, chunk, n, p, heads, groups = {
        "tiles": (jnp.bfloat16, 128, 128, 64, 4, 2),
        "float32": (jnp.float32, 128, 128, 64, 4, 2),
        "chunk-64": (jnp.bfloat16, 64, 128, 64, 4, 2),
        "state-64": (jnp.bfloat16, 128, 64, 64, 4, 2),
        "head-48": (jnp.bfloat16, 128, 128, 48, 8, 2),
        "group-of-64": (jnp.bfloat16, 128, 128, 32, 4, 2)}[shape]
    x, dt, a_log, b, c, d, _ = _args(np.random.RandomState(3), 1, 150,
                                     heads, p, groups, dtype, n)
    assert not kernel.by_kernel(x, b, chunk)    # the CPU
    obs.set_enabled(True)
    out, counted = _lower(x, dt, b, c, chunk)
    delta = jax.nn.softplus(dt)
    np.testing.assert_array_equal(out, ssm_ops._ssd_scan(
        x, delta, jnp.zeros((heads,)), b, c, jnp.ones((heads,)), chunk))
    assert counted == (0, 1)
    monkeypatch.setattr(kernel, "_on_tpu", lambda: True)
    assert kernel.by_kernel(x, b, chunk) == (shape == "tiles")
    if shape != "tiles":
        out, counted = _lower(x, dt, b, c, chunk)
        assert counted == (0, 1)
        return
    # the TPU's path, its kernels interpreted here
    monkeypatch.setattr(kernel, "scan", functools.partial(
        kernel.scan, interpret=True))
    out, counted = _lower(x, dt, b, c, chunk)
    assert counted == (1, 0)
    np.testing.assert_array_equal(out, kernel.scan(
        x, delta, jnp.zeros((heads,)), b, c, jnp.ones((heads,)), chunk))
