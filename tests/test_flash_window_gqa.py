"""The flash kernels' window and grouped-query forms, in interpret mode on
the CPU against the XLA composition: forward, dQ, dK and dV, at a window
that is no multiple of the block, a window at least as long as the
sequence, both backward forms; and the kernels of the benchmark's 2048 cell
lowered as they were before either form existed."""

import hashlib
import importlib
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

_fa = importlib.import_module("paddle_tpu.kernels.flash_attention")


def _rand(shape, seed):
    return jnp.asarray(
        np.random.RandomState(seed).randn(*shape).astype(np.float32))


@pytest.fixture(params=["fused", "split"])
def form(request, monkeypatch):
    """Each form of ``_flash_backward``, steered from the test as
    tests/test_flash_attention.py steers it; the counters say afterwards
    that only the form asked for was lowered, with the grouped and the
    windowed forms counted beside it."""
    from paddle_tpu import observability as obs

    if request.param == "split":
        monkeypatch.setattr(_fa, "_bwd_fused_fits", lambda *a: False)
    obs.set_enabled(True)
    yield request.param
    other = {"fused": "split", "split": "fused"}[request.param]
    assert obs.counter_value("flash.bwd_" + request.param) > 0
    assert obs.counter_value("flash.bwd_" + other) == 0


# (Tq, block, window, Q heads, K/V heads): a window that crosses block
# edges at no multiple of the block; one at least as long as the sequence;
# one narrower than a block; grouped heads with and without a window
CASES = [
    (256, 64, 100, 4, 2),
    (256, 64, 300, 4, 1),
    (256, 128, 40, 2, 2),
    (512, 128, None, 8, 2),
    (1024, 256, 300, 2, 1),
]


@pytest.mark.parametrize("T,block,window,hq,hkv", CASES)
def test_window_and_grouped_heads_match_xla(T, block, window, hq, hkv, form):
    from paddle_tpu import observability as obs

    B, D = 2, 16
    q = _rand((B, hq, T, D), 0)
    k, v = _rand((B, hkv, T, D), 1), _rand((B, hkv, T, D), 2)
    g = _rand((B, hq, T, D), 3)

    def flash(q_, k_, v_):
        return _fa.flash_attention(q_, k_, v_, None, 0, True, None, 0.0,
                                   block, block, True, window)

    def ref(q_, k_, v_):
        return _fa._xla_attention(q_, k_, v_, True, D ** -0.5,
                                  window=window)

    out, vjp_f = jax.vjp(flash, q, k, v)
    want, vjp_r = jax.vjp(ref, q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=2e-4)
    for got, exp, name in zip(vjp_f(g), vjp_r(g), ("dq", "dk", "dv")):
        assert got.shape == exp.shape, name
        np.testing.assert_allclose(np.asarray(got), np.asarray(exp),
                                   atol=5e-4, rtol=5e-3, err_msg=name)
    assert (obs.counter_value("flash.gqa_calls") > 0) == (hq != hkv)
    assert (obs.counter_value("flash.window_calls") > 0) == (
        window is not None)


def test_window_with_lengths_and_dropout_agrees_across_forms(monkeypatch):
    """Window, grouped heads, key lengths and in-kernel dropout together:
    the one-kernel backward against the dQ and dK/dV pair on the same
    saved (out, lse)."""
    B, hq, hkv, T, D = 2, 4, 2, 128, 16
    q = _rand((B, hq, T, D), 0)
    k, v = _rand((B, hkv, T, D), 1), _rand((B, hkv, T, D), 2)
    g = _rand((B, hq, T, D), 3)
    lens = jnp.array([128, 77], jnp.int32)
    out, lse = _fa.flash_attention_lse(q, k, v, lens, None, 5, True, None,
                                       0.1, 32, 32, True, 50)

    def backward():
        return _fa._flash_backward(
            q, k, v, out, lse.reshape(B * hq, T, 1), g, None, lens, None, 5,
            True, D ** -0.5, 0.1, 32, 32, True, 50)

    fused = backward()
    monkeypatch.setattr(_fa, "_bwd_fused_fits", lambda *a: False)
    for got, want, name in zip(fused, backward(), ("dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-6, rtol=1e-6, err_msg=name)


def test_a_window_visits_fewer_tiles():
    """The K-block range of a Q tile is clamped at both ends: at 4096
    positions and window 1024 a causal layer's 10 tiles of 1024 become 7,
    its 36 of 512 become 21."""
    def tiles(block, window):
        n = 4096 // block
        live = 0
        for j in range(n):
            hi = int(_fa._causal_blocks(0, 0, j, block, block))
            lo = 0 if window is None else int(
                _fa._window_first_block(0, 0, j, block, block, window))
            live += min(hi, n) - lo
        return live

    assert (tiles(1024, None), tiles(1024, 1024)) == (10, 7)
    assert (tiles(512, None), tiles(512, 1024)) == (36, 21)
    # and the backward's Q-block range of a K tile agrees with it
    for block in (512, 1024):
        n = 4096 // block
        seen = sum(
            min(int(_fa._window_last_q_block(s, block, block, 1024)), n - 1)
            - (s * block) // block + 1 for s in range(n))
        assert seen == tiles(block, 1024)


def test_a_window_needs_causal_attention():
    q = _rand((1, 2, 64, 16), 0)
    with pytest.raises(ValueError, match="causal"):
        _fa.flash_attention(q, q, q, None, 0, False, None, 0.0, 32, 32,
                            True, 16)
    three = jnp.zeros((1, 3, 64, 16))
    with pytest.raises(ValueError, match="multiple"):
        _fa.flash_attention(q, three, three, None, 0, True, None, 0.0, 32,
                            32, True)


def _jaxpr_digest(fn, *args):
    text = str(jax.make_jaxpr(fn)(*args))
    text = re.sub(r" at 0x[0-9a-f]+", "", text)
    text = re.sub(r"\S+\.py:\d+", "F", text)
    return hashlib.sha256(text.encode()).hexdigest()[:16]


def test_the_2048_cells_kernels_are_lowered_as_before():
    """With no window and equal head counts the kernels of the benchmark's
    BERT cell ([8, 12, 2048, 64] bf16, key lengths, the table's blocks)
    trace to the jaxprs they had at the parent commit (PR 27, 4b51861):
    digests of the jaxpr text, source positions struck, taken there."""
    B, H, T, D = 8, 12, 2048, 64
    act = jax.ShapeDtypeStruct((B, H, T, D), jnp.bfloat16)
    lens = jax.ShapeDtypeStruct((B,), jnp.int32)
    lse = jax.ShapeDtypeStruct((B * H, T, 1), jnp.float32)
    blk = _fa.pick_block(T, jnp.bfloat16)
    assert _jaxpr_digest(
        lambda q, k, v, l: _fa.flash_attention_raw_lse(
            q, k, v, l, 0, False, 0.125, 0.0, blk, blk, False),
        act, act, act, lens) == "1dcc433302ed39c0"
    assert _jaxpr_digest(
        lambda q, k, v, o, ls, g, l: _fa._flash_backward(
            q, k, v, o, ls, g, None, l, None, 0, False, 0.125, 0.0, blk,
            blk, False),
        act, act, act, act, lse, act, lens) == "9dd209212f38feca"
    assert _jaxpr_digest(
        lambda q, k, v, o, ls, g: _fa._flash_backward(
            q, k, v, o, ls, g, None, None, None, 0, True, 0.125, 0.1, 512,
            512, False),
        act, act, act, act, lse, act) == "9849fbe87cf230ad"
