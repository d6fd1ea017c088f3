"""The scalars of the expert layer's routing: ``moe_router``'s k chosen
scores (a compare over the E outputs) and ``moe_dispatch``'s weights in
row order (a payload of the sort), against the forms they replaced: a
gather of tokens x k single elements each way, and the gathers'
transposes. The lowerings themselves are called, outside any engine."""

import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from paddle_tpu.ops import moe_ops

TOKENS, D = 96, 32


def _ctx(op_type):
    """What a lowering asks of its context when no engine runs it."""
    return types.SimpleNamespace(op=types.SimpleNamespace(type=op_type),
                                 executor=None)


def _router(x, w, bias, attrs):
    ins = {"X": [x], "Weight": [w]}
    if bias is not None:
        ins["Bias"] = [bias]
    outs = moe_ops.moe_router(_ctx("moe_router"), ins, attrs)
    return outs["TopkWeight"][0], outs["TopkIds"][0]


def _router_by_gather(x, w, bias, attrs):
    """The router as it stood before PR 38: ``top_k``'s own values where no
    bias ranks, ``take_along_axis`` where one does."""
    logits = jnp.dot(x.astype(jnp.float32), w.astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)
    if attrs["score_func"] == "sigmoid":
        probs, eps = jax.nn.sigmoid(logits), attrs["norm_eps"]
    else:
        probs, eps = jax.nn.softmax(logits, axis=-1), 0.0
    if bias is None:
        top, ids = lax.top_k(probs, attrs["k"])
    else:
        _, ids = lax.top_k(probs + lax.stop_gradient(bias), attrs["k"])
        top = jnp.take_along_axis(probs, ids, axis=-1)
    top = top / (jnp.sum(top, axis=-1, keepdims=True) + eps)
    return top * attrs["route_scale"], ids.astype(jnp.int32)


@pytest.mark.parametrize("experts", [64, 128])
@pytest.mark.parametrize("k", [4, 8])
@pytest.mark.parametrize("bias", [False, True], ids=["no_bias", "bias"])
@pytest.mark.parametrize("score_func", ["softmax", "sigmoid"])
def test_the_router_chooses_by_compare_what_the_gather_chose(
        score_func, bias, k, experts):
    """``TopkWeight`` and ``TopkIds`` element for element, the gradients to
    ``X`` and ``Weight`` to float32 rounding (the scatter-add and the dense
    compare add one term to zeros, the products round alike). Token 0's
    scores are all equal and token 1's equal in pairs: the ranking breaks
    ties by the lower output, and equal scores are distinct outputs to the
    compare."""
    rng = np.random.RandomState(experts + k)
    x = rng.randn(TOKENS, D).astype(np.float32)
    w = (rng.randn(D, experts) * 0.3).astype(np.float32)
    x[0] = 0.0
    w[:, 1::2] = w[:, 0::2]
    x, w = jnp.asarray(x), jnp.asarray(w)
    b = jnp.asarray(rng.randn(experts) * 0.05, jnp.float32) if bias else None
    attrs = {"k": k, "score_func": score_func, "norm_eps": 1e-6,
             "route_scale": 2.826}
    top, ids = _router(x, w, b, attrs)
    want_top, want_ids = _router_by_gather(x, w, b, attrs)
    assert top.dtype == jnp.float32 and ids.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(want_ids))
    np.testing.assert_array_equal(np.asarray(top), np.asarray(want_top))
    assert len(set(np.asarray(ids[0]).tolist())) == k

    g = jnp.asarray(rng.randn(TOKENS, k), jnp.float32)
    got = jax.grad(lambda x_, w_: jnp.sum(_router(x_, w_, b, attrs)[0] * g),
                   argnums=(0, 1))(x, w)
    want = jax.grad(
        lambda x_, w_: jnp.sum(_router_by_gather(x_, w_, b, attrs)[0] * g),
        argnums=(0, 1))(x, w)
    for a, r in zip(got, want):
        assert np.abs(np.asarray(r)).max() > 1e-3
        np.testing.assert_allclose(np.asarray(a), np.asarray(r), rtol=1e-5,
                                   atol=1e-6)
    if bias:    # the bias ranks and takes no gradient
        db = jax.grad(lambda b_: jnp.sum(_router(x, w, b_, attrs)[0] * g))(b)
        assert not np.asarray(db).any()


def test_the_routers_backward_keeps_the_choices_not_their_mask():
    """The ``custom_vjp`` saves ``TopkIds`` [N, k]; autodiff of the compare
    would save the [N, k, E] mask."""
    x, w = jnp.ones((TOKENS, D)), jnp.ones((D, 64))
    attrs = {"k": 4, "score_func": "sigmoid"}
    jaxpr = jax.make_jaxpr(lambda x_, w_: jax.vjp(
        lambda a, b: _router(a, b, None, attrs)[0], x_, w_)[1])(x, w)
    assert (TOKENS, 4, 64) not in [v.aval.shape for v in jaxpr.jaxpr.outvars]
    for fn in (lambda x_, w_: _router(x_, w_, None, attrs),
               jax.grad(lambda x_, w_: jnp.sum(
                   _router(x_, w_, None, attrs)[0] ** 2), argnums=(0, 1))):
        text = str(jax.make_jaxpr(fn)(x, w))
        assert "gather" not in text and "scatter" not in text


HELD, EXPERTS, TOP = 4, 8, 4
ROUTINGS = ["uniform", "a_token_with_no_held_pair", "every_pair_held",
            "no_pair_held"]


def _ids(name, rng):
    if name == "every_pair_held":
        ids = [rng.permutation(HELD)[:TOP] for _ in range(TOKENS)]
    elif name == "no_pair_held":
        ids = [HELD + rng.permutation(EXPERTS - HELD)[:TOP]
               for _ in range(TOKENS)]
    else:
        ids = [rng.permutation(EXPERTS)[:TOP] for _ in range(TOKENS)]
        if name == "a_token_with_no_held_pair":
            ids[3] = ids[TOKENS - 1] = [4, 5, 6, 7]
    return jnp.asarray(np.asarray(ids, np.int32))


def _dispatch(x, weight, ids):
    return moe_ops.moe_dispatch(
        _ctx("moe_dispatch"),
        {"X": [x], "TopkWeight": [weight], "TopkIds": [ids]},
        {"experts_held": HELD})


@pytest.mark.parametrize("name", ROUTINGS)
def test_the_dispatch_sorts_the_weights_the_gather_fetched(name):
    """``RowWeight`` is ``TopkWeight`` gathered by ``PairOfRow`` on the
    live rows and 0 on the others; ``TopkWeight``'s gradient is the rows'
    gathered by ``RowOfPair`` on the held pairs and 0 on the others: both
    exactly, a sort moves values and rounds nothing. The dead rows'
    cotangent is NaN, as a kernel may leave it, and none comes through."""
    rng = np.random.RandomState(ROUTINGS.index(name))
    ids = _ids(name, rng)
    x = jnp.asarray(rng.randn(TOKENS, D), jnp.float32)
    weight = jnp.asarray(rng.rand(TOKENS, TOP) + 0.1, jnp.float32)
    outs, vjp = jax.vjp(
        lambda x_, w_: {k: v[0] for k, v in _dispatch(x_, w_, ids).items()
                        if k in ("Rows", "RowWeight")}, x, weight)
    full = _dispatch(x, weight, ids)
    order, row_of_pair = full["PairOfRow"][0], full["RowOfPair"][0]
    counts = np.asarray(full["Counts"][0])
    held = np.asarray(ids) < HELD
    live = int(counts.sum())
    assert live == held.sum()
    np.testing.assert_array_equal(
        counts, np.bincount(np.asarray(ids).reshape(-1),
                            minlength=EXPERTS)[:HELD])
    np.testing.assert_array_equal(
        np.asarray(row_of_pair).reshape(-1)[np.asarray(order)],
        np.arange(TOKENS * TOP))
    want = np.where(np.arange(TOKENS * TOP) < live,
                    np.asarray(weight).reshape(-1)[np.asarray(order)], 0)
    got = np.asarray(outs["RowWeight"])
    assert got.dtype == np.float32 and got.shape == (TOKENS * TOP,)
    np.testing.assert_array_equal(got, want)

    g_weight = rng.randn(TOKENS * TOP).astype(np.float32)
    g_weight[live:] = np.nan
    g_rows = np.zeros((TOKENS * TOP, D), np.float32)
    _, d_weight = vjp({"Rows": jnp.asarray(g_rows),
                       "RowWeight": jnp.asarray(g_weight)})
    np.testing.assert_array_equal(
        np.asarray(d_weight),
        np.where(held, g_weight[np.asarray(row_of_pair)], 0))
    if name == "no_pair_held":
        assert not got.any() and not np.asarray(d_weight).any()
    if name == "a_token_with_no_held_pair":
        assert not np.asarray(d_weight)[3].any()


def test_the_dispatch_moves_no_single_scalar():
    """Neither way: the only gathers left in ``moe_dispatch`` and its
    gradient off the TPU are the two of whole rows (``_rows_of_tokens_xla``,
    ``_sums_of_rows_xla``), which the kernel of ``row_permute`` replaces
    there."""
    ids = _ids("uniform", np.random.RandomState(0))
    x, weight = jnp.ones((TOKENS, D)), jnp.ones((TOKENS, TOP))
    fwd = str(jax.make_jaxpr(lambda x_, w_: _dispatch(x_, w_, ids))(x,
                                                                    weight))

    def loss(x_, w_):
        outs = _dispatch(x_, w_, ids)
        return jnp.sum(outs["RowWeight"][0] ** 2) + jnp.sum(outs["Rows"][0])

    bwd = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(x, weight))
    assert fwd.count("gather") == 1 and "scatter" not in fwd
    assert "scatter" not in bwd


@pytest.mark.parametrize("held, fits", [(255, True), (256, False)])
def test_the_dispatch_refuses_a_sort_key_past_int32(held, fits):
    """The pair's index rides in its sort key's low digits (``key * R +
    pair``, so that the sort need not be stable): 2**23 pairs leave room
    for 255 held experts and the rest, not for 256."""
    tokens, k = 2 ** 20, 8

    def shapes():
        return jax.eval_shape(
            lambda x, w, i: moe_ops.moe_dispatch(
                _ctx("moe_dispatch"),
                {"X": [x], "TopkWeight": [w], "TopkIds": [i]},
                {"experts_held": held})["PairOfRow"][0],
            jax.ShapeDtypeStruct((tokens, 8), jnp.float32),
            jax.ShapeDtypeStruct((tokens, k), jnp.float32),
            jax.ShapeDtypeStruct((tokens, k), jnp.int32))

    if fits:
        assert shapes().shape == (tokens * k,)
    else:
        with pytest.raises(ValueError, match="one int32 sort key"):
            shapes()
