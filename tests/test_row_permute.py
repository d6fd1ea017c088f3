"""The expert layer's row movements as the Pallas kernel of
``kernels/row_permute.py``, interpreted on the CPU at rehearsal sizes,
against the XLA gathers of ``ops/moe_ops.py`` that the CPU itself runs."""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu.fluid as fluid
from paddle_tpu.kernels import row_permute as rp
from paddle_tpu.layers import nn as _nn
from paddle_tpu.ops import moe_ops
from tools.moe_permute_sweep import permutation

TOKENS, TOP, EXPERTS, HELD, D = 256, 4, 8, 4, 128
TILE, CHUNK = 32, 64
ROUTINGS = ["uniform", "same_experts", "an_empty_expert", "none_held",
            "all_held"]


def _routing(name, rng):
    """(TopkIds [TOKENS, TOP], experts held)."""
    if name == "uniform":
        ids = [rng.permutation(EXPERTS)[:TOP] for _ in range(TOKENS)]
    elif name == "same_experts":       # two of every token's four are held
        ids = [[2, 3, 4, 5]] * TOKENS
    elif name == "an_empty_expert":    # held expert 1 gets no token
        ids = [rng.permutation([0, 2, 3, 4, 5, 6, 7])[:TOP]
               for _ in range(TOKENS)]
    elif name == "none_held":
        ids = [[4, 5, 6, 7]] * TOKENS
    else:                              # every pair falls on a held expert
        ids = [rng.permutation(HELD)[:TOP] for _ in range(TOKENS)]
    return np.asarray(ids, np.int32), HELD


def _case(name):
    """(rng, what ``moe_dispatch`` makes of the routing: PairOfRow, Counts,
    RowOfPair, pair is held)."""
    rng = np.random.RandomState(ROUTINGS.index(name))
    ids, held = _routing(name, rng)
    return rng, tuple(jnp.asarray(a) for a in permutation(ids, held))


@pytest.mark.parametrize("name", ROUTINGS)
def test_expand_is_the_gather_bit_for_bit(name):
    rng, (order, counts, _, _) = _case(name)
    live = int(counts.sum())
    x = jnp.asarray(rng.randn(TOKENS, D), jnp.bfloat16)
    got = rp.expand(x, order, counts, TOP, TILE, CHUNK, interpret=True)
    want = moe_ops._rows_of_tokens_xla(x, order, counts, TOP)
    assert got.dtype == want.dtype and got.shape == want.shape
    np.testing.assert_array_equal(
        np.asarray(got[:live].astype(jnp.float32)),
        np.asarray(want[:live].astype(jnp.float32)))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["combine", "dispatch_grad"])
@pytest.mark.parametrize("name", ROUTINGS)
def test_reduce_is_the_sum_of_the_gathered_rows(name, dtype):
    """``moe_combine``'s float32 sum and ``_to_rows_bwd``'s ``dx`` in the
    rows' dtype; the buffer's dead rows hold NaN and none of it comes
    through."""
    rng, (order, counts, row_of_pair, is_held) = _case(name)
    live = int(counts.sum())
    rows = rng.randn(TOKENS * TOP, D).astype(np.float32)
    rows[live:] = np.nan
    rows = jnp.asarray(rows, jnp.bfloat16)
    got = rp.reduce(rows, order, counts, TOP, dtype, TILE, CHUNK,
                    interpret=True)
    want = moe_ops._sums_of_rows_xla(rows, row_of_pair, is_held, dtype)
    assert got.dtype == want.dtype and got.shape == want.shape
    got, want = (np.asarray(a.astype(jnp.float32)) for a in (got, want))
    assert np.isfinite(got).all()
    # float32: the same exact products summed in another order; bf16: one
    # more rounding of that sum
    np.testing.assert_allclose(
        got, want, rtol=1e-6 if dtype == jnp.float32 else 2 ** -7,
        atol=1e-6)
    if name == "none_held":
        assert not got.any()


@pytest.mark.parametrize("by_chunk", [False, True], ids=["by_tile",
                                                         "by_chunk"])
@pytest.mark.parametrize("name", ROUTINGS)
def test_the_list_holds_every_shared_pair_within_its_bound(name, by_chunk):
    _, (order, counts, _, _) = _case(name)
    used, tile_of, chunk_of = (np.asarray(a) for a in rp.visits(
        order, counts, TOP, TOKENS, by_chunk, TILE, CHUNK))
    tiles, chunks = TOKENS * TOP // TILE, TOKENS // CHUNK
    bound = rp.visit_bound(tiles, chunks, HELD) + (chunks if by_chunk else 0)
    assert tile_of.shape == chunk_of.shape == (bound,)
    used = int(used[0])
    assert used <= bound
    listed = list(zip(tile_of[:used].tolist(), chunk_of[:used].tolist()))
    assert len(set(listed)) == used
    # consecutive visits of one output block, in ascending order
    assert listed == sorted(listed, key=(lambda v: v[::-1]) if by_chunk
                            else None)
    live = int(np.asarray(counts).sum())
    token = np.asarray(order)[:live] // TOP
    shared = set(zip((np.arange(live) // TILE).tolist(),
                     (token // CHUNK).tolist()))
    if by_chunk:   # and a visit of tile 0 for a chunk no pair falls in
        assert shared <= set(listed) <= shared | {(0, c)
                                                  for c in range(chunks)}
        assert set(range(chunks)) == {c for _, c in listed}
    else:
        assert set(listed) == shared


def _layer_gradients(x, params, monkeypatch, by_kernel):
    """Out, the parameters' and the input's gradients of one expert layer
    under bf16, through ``Executor.run``; ``by_kernel`` takes the kernel's
    path (interpreted) where the CPU would take XLA's."""
    from paddle_tpu import observability as obs

    if by_kernel:
        monkeypatch.setattr(rp, "_on_tpu", lambda: True)
        for name in ("expand", "reduce"):
            monkeypatch.setattr(rp, name, functools.partial(
                getattr(rp, name), interpret=True))
    obs.set_enabled(True)
    before = [obs.counter_value("moe.permute_" + form)
              for form in ("kernel", "xla")]
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        data = fluid.layers.data(name="x", shape=[D], dtype="float32")
        data.stop_gradient = False
        weight, ids = _nn.moe_router(
            data, EXPERTS, TOP, param_attr=fluid.ParamAttr(name="router"))
        out, _ = _nn.moe_experts(
            data, weight, ids, HELD, 0, 64,
            gate_attr=fluid.ParamAttr(name="gate"),
            up_attr=fluid.ParamAttr(name="up"),
            down_attr=fluid.ParamAttr(name="down"))
        loss = fluid.layers.mean(out * out)
        fluid.backward.append_backward(loss)
    fluid.contrib.mixed_precision.enable_bf16(main)
    exe, scope = fluid.Executor(), fluid.Scope()
    exe.run(startup, scope=scope)
    for name, value in params.items():
        scope.set(name, jnp.asarray(value))
    names = ["x@GRAD"] + [n + "@GRAD" for n in sorted(params)]
    got = exe.run(main, feed={"x": x}, fetch_list=[out] + names, scope=scope)
    exe.close()
    jax.effects_barrier()
    after = [obs.counter_value("moe.permute_" + form)
             for form in ("kernel", "xla")]
    gauges = obs.snapshot()["gauges"]
    obs.set_enabled(False)
    return got, [b - a for a, b in zip(before, after)], gauges


def test_a_layers_gradients_by_the_kernel_are_the_gathers(monkeypatch):
    """One ``moe_experts`` layer forward and backward, both ways: the four
    call sites count themselves under the form they were lowered in, and the
    kernel's results are the gathers' (expand exactly, reduce to a float32
    rounding before the same casts)."""
    rng = np.random.RandomState(7)
    x = rng.randn(TOKENS, D).astype(np.float32)
    params = {"router": rng.randn(D, EXPERTS).astype(np.float32),
              "gate": (rng.randn(HELD, D, 64) * 0.1).astype(np.float32),
              "up": (rng.randn(HELD, D, 64) * 0.1).astype(np.float32),
              "down": (rng.randn(HELD, 64, D) * 0.1).astype(np.float32)}
    want, counted, gauges = _layer_gradients(x, params, monkeypatch, False)
    assert counted == [0, 4]
    assert "moe.permute_visits" not in gauges
    got, counted, gauges = _layer_gradients(x, params, monkeypatch, True)
    assert counted == [4, 0]
    tiles, chunks = TOKENS * TOP // rp._TILE_ROWS, TOKENS // rp._CHUNK_ROWS
    assert 0 < gauges["moe.permute_visits"] <= rp.visit_bound(tiles, chunks,
                                                              HELD)
    for g, w in zip(got, want):
        assert np.isfinite(g).all()
        np.testing.assert_allclose(g, w, rtol=2e-2,
                                   atol=2e-3 * np.abs(w).max())


def test_what_the_kernel_does_not_take_keeps_the_gathers(monkeypatch):
    """The choice is made from what the call site sees: float32 rows, rows
    that are no whole lanes, tokens that are no whole chunks and anything
    off the TPU keep XLA's gathers."""
    tile, chunk = rp._TILE_ROWS, rp._CHUNK_ROWS
    assert not rp.applies(chunk, 8 * chunk, 256, jnp.bfloat16)   # the CPU
    monkeypatch.setattr(rp, "_on_tpu", lambda: True)
    assert rp.applies(chunk, 8 * chunk, 256, jnp.bfloat16)
    assert rp.applies(8192, 65536, 2304, jnp.bfloat16)           # the cell
    assert not rp.applies(chunk, 8 * chunk, 256, jnp.float32)
    assert not rp.applies(chunk, 8 * chunk, 200, jnp.bfloat16)
    assert not rp.applies(chunk + 8, 8 * (chunk + 8), 256, jnp.bfloat16)
    assert not rp.applies(chunk, 3 * tile // 2, 256, jnp.bfloat16)
