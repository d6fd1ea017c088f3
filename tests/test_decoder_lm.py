"""The decoder language model (``models/decoder_lm.py``) and the ops it
brought — ``rms_norm``, ``rotary_embedding``, the expert-layer ops,
``gated_mlp``, ``gated_short_conv`` — through ``Executor.run`` on the CPU
at small sizes, against the benchmark's plain references
(``benchmarks/reference/mellum2.py``, ``trinity_mini.py`` and
``lfm2_moe.py``, which import nothing of the program)."""

import hashlib
import json
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu.fluid as fluid
from paddle_tpu.core.registry import LowerContext, OpRegistry
from paddle_tpu.framework import OpRole
from paddle_tpu.layers import nn as _nn
from paddle_tpu.ops.nn_ops import rope_inv_freq

from benchmarks import compare
from benchmarks.drivers import train
from benchmarks.reference import common, lfm2_moe, mellum2, trinity_mini

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "mellum2_12b.pretrain_s4096_b2"
YARN = {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
        "original_max_position_embeddings": 8192, "beta_fast": 32,
        "beta_slow": 1, "attention_factor": 1.2772588722239782}
DEFAULT = {"rope_type": "default", "rope_theta": 500000}


def _cell(cell=CELL):
    with open(os.path.join(ROOT, "benchmarks", "workloads",
                           cell + ".json")) as f:
        workload = json.load(f)
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           workload["config"] + ".json")) as f:
        config = json.load(f)
    cfg, rows = train.sized(config, workload, rehearse=True)
    return workload, cfg, rows


@pytest.mark.parametrize("seed", [3000000007])
def test_the_program_follows_the_reference_at_the_rehearsal_size(seed):
    """get_model -> enable_bf16 -> Executor.run from the seed's weights,
    the attention kernels in interpret mode: the three losses, every
    leaf's first gradient and every leaf's change after three steps,
    against the float32 reference under the cell's rehearsal limits; and
    the tokens each held expert received come back through fetch_list."""
    workload, cfg, rows = _cell()
    with fluid.unique_name.guard():
        trainer = train.Trainer(cfg, rows, workload, rehearse=True)
    trainer.start(seed)
    program, _ = trainer.warm_up()
    reference = common.follow(mellum2, cfg, rows, seed)
    correct, compared = compare.judge(
        program, reference, train.limits(workload, rehearse=True))
    assert correct, compared
    assert compared["loss_gap"]["value"] < 1e-3
    m = cfg["model"]
    kinds = {op.type for op in trainer.main.desc.global_block().ops}
    assert {"rms_norm", "rotary_embedding", "moe_router", "moe_dispatch",
            "moe_expert_mlp", "moe_combine", "fused_attention"} <= kinds
    windows = [op.attrs.get("window") for op in
               trainer.main.desc.global_block().ops
               if op.type == "fused_attention"]
    assert windows == [m["sliding_window"]] * 3 + [None]
    counts = [op.output("Counts")[0] for op in
              trainer.main.desc.global_block().ops
              if op.type == "moe_dispatch"]
    loads = trainer.exe.run(trainer.main, feed=trainer.pool[0],
                            fetch_list=counts, scope=trainer.scope)
    assert len(loads) == m["num_hidden_layers"]
    pairs = rows * m["seq_len"] * m["num_experts_per_tok"]
    for load in loads:
        assert load.shape == (m["experts_held"],)
        assert 0 < int(load.sum()) < pairs
    trainer.exe.close()


# -- the expert layer alone ----------------------------------------------------

D, WIDTH, EXPERTS, TOP = 32, 16, 8, 3


def _expert_layer(x, params, held, offset):
    """Out and Counts of the program's expert layer for the experts
    ``offset .. offset + held`` of EXPERTS, through Executor.run."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        data = fluid.layers.data(name="x", shape=[D], dtype="float32")
        weight, ids = _nn.moe_router(
            data, EXPERTS, TOP, param_attr=fluid.ParamAttr(name="router"))
        out, counts = _nn.moe_experts(
            data, weight, ids, held, offset, WIDTH,
            gate_attr=fluid.ParamAttr(name="gate"),
            up_attr=fluid.ParamAttr(name="up"),
            down_attr=fluid.ParamAttr(name="down"))
    exe, scope = fluid.Executor(), fluid.Scope()
    exe.run(startup, scope=scope)
    scope.set("router", jnp.asarray(params["router"]))
    for name in ("gate", "up", "down"):
        scope.set(name, jnp.asarray(params[name][offset:offset + held]))
    got = exe.run(main, feed={"x": x}, fetch_list=[out, counts], scope=scope)
    exe.close()
    return got


def _reference_layer(x, params, held, offset):
    m = {"num_experts_per_tok": TOP, "experts_held": held,
         "expert_offset": offset}
    p = {"l.router": params["router"], "l.experts_gate": params["gate"][
        offset:offset + held], "l.experts_up": params["up"][
        offset:offset + held], "l.experts_down": params["down"][
        offset:offset + held]}
    with jax.default_matmul_precision("highest"):
        return np.asarray(mellum2._experts(
            common.Matmuls("f32"), m, {k: jnp.asarray(v) for k, v in
                                       p.items()}, "l.", jnp.asarray(x)))


def _params(rng, router=None):
    return {"router": router if router is not None
            else rng.randn(D, EXPERTS).astype(np.float32),
            "gate": (rng.randn(EXPERTS, D, WIDTH) * 0.3).astype(np.float32),
            "up": (rng.randn(EXPERTS, D, WIDTH) * 0.3).astype(np.float32),
            "down": (rng.randn(EXPERTS, WIDTH, D) * 0.3).astype(np.float32)}


def test_the_shares_add_up_to_the_uncut_layer():
    """8 experts in 4 shares of 2, 3 a token: what the four expert_offsets
    give adds up to the uncut reference layer's result, every share is the
    reference's own share, and every pair is counted once."""
    rng = np.random.RandomState(0)
    x = rng.randn(40, D).astype(np.float32)
    params = _params(rng)
    total, pairs = 0.0, 0
    for offset in range(0, EXPERTS, 2):
        out, counts = _expert_layer(x, params, 2, offset)
        np.testing.assert_allclose(
            out, _reference_layer(x, params, 2, offset), atol=2e-5,
            rtol=2e-4)
        total, pairs = total + out, pairs + int(counts.sum())
    np.testing.assert_allclose(
        total, _reference_layer(x, params, EXPERTS, 0), atol=5e-5,
        rtol=2e-4)
    assert pairs == x.shape[0] * TOP


@pytest.mark.parametrize("held", [2, 4])
def test_no_pair_is_dropped_when_every_token_picks_the_same_experts(held):
    """A router that sends every token to experts 0, 1, 2: a share that
    holds two of them gets two pairs of every token, one that holds all
    three fills its buffer to the last row, and both still give the
    reference's share."""
    rng = np.random.RandomState(1)
    x = rng.randn(24, D).astype(np.float32)
    x[:, 0] = 1.0
    router = np.zeros((D, EXPERTS), np.float32)
    router[0, :3] = (30.0, 20.0, 10.0)
    params = _params(rng, router)
    out, counts = _expert_layer(x, params, held, 0)
    assert counts.tolist() == [24] * min(held, 3) + [0] * (held - 3)
    np.testing.assert_allclose(out, _reference_layer(x, params, held, 0),
                               atol=2e-5, rtol=2e-4)


def test_the_grouped_matmul_kernel_agrees_with_ragged_dot():
    """The megablox kernel in interpret mode against ``lax.ragged_dot``,
    forward and both gradients, in a buffer of which a part is unused."""
    from paddle_tpu.kernels.grouped_matmul import grouped_matmul

    rng = np.random.RandomState(2)
    lhs = jnp.asarray(rng.randn(256, 128), jnp.float32)
    rhs = jnp.asarray(rng.randn(4, 128, 128) * 0.1, jnp.float32)
    sizes = jnp.asarray([40, 0, 77, 19], jnp.int32)
    live = (jnp.arange(256) < 136)[:, None]

    def loss(interpret):
        def fn(lhs_, rhs_):
            out = jnp.where(live, grouped_matmul(lhs_, rhs_, sizes,
                                                 interpret), 0)
            return jnp.sum(out ** 2), out
        return jax.value_and_grad(fn, argnums=(0, 1), has_aux=True)

    (_, out), (dl, dr) = loss(True)(lhs, rhs)
    (_, want), (wl, wr) = loss(False)(lhs, rhs)
    for got, exp in ((out, want), (jnp.where(live, dl, 0), wl), (dr, wr)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(exp),
                                   atol=1e-3, rtol=1e-3)


def _ragged(lhs, rhs, sizes):
    return jax.lax.ragged_dot(lhs, rhs, sizes,
                              preferred_element_type=jnp.float32)


@pytest.mark.parametrize("sizes", [[40, 0, 77, 19], [256, 0, 0, 130],
                                   [0, 3, 0, 0], [300, 100, 60, 52]])
def test_the_pair_kernel_adds_both_products_over_the_live_rows(sizes):
    """``g_a @ Waᵀ + g_b @ Wbᵀ`` in one kernel (interpret mode) against
    two float32 ``ragged_dot``s and an add, over the live rows only (the
    dead ones are unspecified): an empty group, groups that end inside a
    tile, live rows well short of the buffer and a buffer that is full;
    two column tiles, so a left tile is fetched for each."""
    from paddle_tpu.kernels import grouped_matmul as gm

    rng = np.random.RandomState(5)
    rows, k, n = 512, 256, 128
    g_a, g_b = (jnp.asarray(rng.randn(rows, n), jnp.float32)
                for _ in range(2))
    w_a, w_b = (jnp.asarray(rng.randn(4, k, n) * 0.1, jnp.float32)
                for _ in range(2))
    sizes = jnp.asarray(sizes, jnp.int32)
    live = int(sizes.sum())
    want = (_ragged(g_a, w_a.swapaxes(1, 2), sizes)
            + _ragged(g_b, w_b.swapaxes(1, 2), sizes))
    assert gm.pair_by_kernel(rows, k, n, interpret=True)
    assert not gm.pair_by_kernel(rows, k, n)        # the CPU, no interpreter
    assert not gm.pair_by_kernel(rows, k, 100, interpret=True)
    for tn in (256, 128):
        got = gm._pair_gmm_t(g_a, g_b, w_a, w_b, sizes, (256, n, tn), True)
        np.testing.assert_allclose(np.asarray(got)[:live],
                                   np.asarray(want)[:live],
                                   atol=1e-3, rtol=1e-3)
    got = gm.grouped_matmul_pair_t(g_a, g_b, w_a, w_b, sizes, interpret=True)
    np.testing.assert_allclose(np.asarray(got)[:live],
                               np.asarray(want)[:live], atol=1e-3, rtol=1e-3)
    xla = gm.grouped_matmul_pair_t(g_a, g_b, w_a, w_b, sizes)
    np.testing.assert_allclose(np.asarray(xla), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("rows, d, width, pair", [
    (65536, 2304, 896, (256, 896, 1152)),
    (49152, 2048, 1024, (256, 1024, 1024))])
def test_the_pair_tiling_is_reckoned_from_the_shapes(rows, d, width, pair):
    """At the two decoder cells' expert shapes the two-pair kernel keeps
    the contraction whole and halves the columns (the whole ``d`` would
    take the single kernel's block twice over): two pairs of
    double-buffered operands, the result tile and the float32 accumulator
    inside the budget, the next wider tile outside it."""
    from paddle_tpu.kernels import grouped_matmul as gm

    tilings = gm._tilings(rows, d, width, 2)
    assert tilings.pair_rows_gradient == pair
    assert tilings.rows_gradient[:2] == pair[:2]
    tm, k, tn = pair

    def reckoned(cols):
        return (2 * (2 * tm * k + 2 * cols * k) + 2 * tm * cols) * 2 \
            + 4 * tm * cols

    assert reckoned(tn) <= gm._VMEM_BUDGET < reckoned(d)
    assert tilings.rows_gradient[2] >= tn   # one pair takes a wider tile


GATE_CASES = {"every row live": [200, 0, 184, 128],
              "an eighth live": [20, 0, 30, 14],
              "a partly live last tile": [256, 1, 0, 43],
              "no live row": [0, 0, 0, 0],
              "NaN in the dead rows": [20, 0, 30, 14]}


@pytest.mark.parametrize("case", list(GATE_CASES))
def test_the_gate_kernels_visit_the_live_row_tiles_only(case):
    """``gated`` / ``gated_t`` (interpret mode) against XLA's form,
    ``silu_gate`` over all rows and ``jax.vjp`` of it, on the live rows,
    float32 and bf16, in a buffer of two tiles: the grid is as long as the
    live prefix (a tile past it does not hold what XLA's pass over all rows
    computes there), the partly live last tile is computed whole, and NaN
    in the dead rows of every input changes no live row of any result and,
    through ``_expert_mlp`` with every kernel interpreted, nothing that a
    consumer makes of them."""
    from paddle_tpu.kernels import grouped_matmul as gm
    from paddle_tpu.ops import moe_ops

    rng = np.random.RandomState(8)
    rows, width = 512, 128
    sizes = jnp.asarray(GATE_CASES[case], jnp.int32)
    live = int(sizes.sum())
    dead = (np.arange(rows) >= live)[:, None]
    assert gm.gate_by_kernel(rows, width, interpret=True)
    assert not gm.gate_by_kernel(rows, width)       # the CPU, no interpreter
    assert not gm.gate_by_kernel(rows, 100, interpret=True)
    assert not gm.gate_by_kernel(300, width, interpret=True)
    assert int(gm.live_tiles(sizes, 256)) == -(-live // 256)
    for dtype in (jnp.float32, jnp.bfloat16):
        gate, up, d_hidden = (jnp.asarray(rng.randn(rows, width), dtype)
                              for _ in range(3))
        weight = jnp.asarray(rng.rand(rows), jnp.float32)
        want = (gm.silu_gate(gate, up, weight),
                *jax.vjp(gm.silu_gate, gate, up, weight)[1](d_hidden))
        if case.startswith("NaN"):
            gate, up, d_hidden = (jnp.where(dead, jnp.nan, a)
                                  for a in (gate, up, d_hidden))
            weight = jnp.where(dead[:, 0], jnp.nan, weight)
        got = (gm.gated(gate, up, weight, sizes, interpret=True),
               *gm.gated_t(gate, up, weight, d_hidden, sizes,
                           interpret=True))
        for name, a, b in zip(("hidden", "d_gate", "d_up", "d_weight"),
                              got, want):
            assert (a.dtype, a.shape) == (b.dtype, b.shape), name
            np.testing.assert_array_equal(
                np.asarray(a, np.float32)[:live],
                np.asarray(b, np.float32)[:live], err_msg=name)
            # a tile past the live prefix was not computed
            past = -(-live // 256) * 256
            assert past == rows or not np.array_equal(
                np.asarray(a, np.float32)[past:],
                np.asarray(b, np.float32)[past:]), name
    if not case.startswith("NaN"):
        return
    args, alive, _ = _expert_mlp_case(jnp.bfloat16)
    rows_, weight_, counts, wg, wu, wd = args
    wg, wu, wd = (w.astype(jnp.bfloat16) for w in (wg, wu, wd))
    g = jnp.asarray(rng.randn(*rows_.shape), jnp.bfloat16)

    def through(rows_, weight_, g):
        out, back = jax.vjp(lambda r, w, a, b, c: moe_ops._expert_mlp(
            r, w, counts, a, b, c, True), rows_, weight_, wg, wu, wd)
        d_rows, d_weight, *d_weights = back(g)
        return [np.where(alive, np.asarray(a, np.float32), 0)
                for a in (out, d_rows)] + [
            np.where(alive[:, 0], np.asarray(d_weight), 0)] + [
            np.asarray(a, np.float32) for a in d_weights]

    clean = through(rows_, weight_, g)
    planted = through(jnp.where(alive, rows_, jnp.nan),
                      jnp.where(alive[:, 0], weight_, jnp.nan),
                      jnp.where(alive, g, jnp.nan))
    for a, b in zip(planted, clean):
        assert np.isfinite(a).all()
        np.testing.assert_array_equal(a, b)


def _plain_expert_mlp(rows, row_weight, counts, wg, wu, wd):
    """The op's arithmetic as a plain composition that JAX differentiates
    itself (what the op was until PR 34)."""
    dtype = rows.dtype
    gate = _ragged(rows, wg.astype(dtype), counts).astype(dtype)
    up = _ragged(rows, wu.astype(dtype), counts).astype(dtype)
    hidden = (jax.nn.silu(gate.astype(jnp.float32)) * up.astype(jnp.float32)
              * row_weight[:, None]).astype(dtype)
    return _ragged(hidden, wd.astype(dtype), counts).astype(dtype)


def _expert_mlp_case(dtype, rows=256, d=128, width=128):
    rng = np.random.RandomState(6)
    counts = jnp.asarray([40, 0, 77, 19], jnp.int32)
    live = (np.arange(rows) < int(counts.sum()))[:, None]
    args = (jnp.asarray(rng.randn(rows, d), dtype),
            jnp.asarray(rng.rand(rows), jnp.float32), counts,
            jnp.asarray(rng.randn(4, d, width) * 0.1, jnp.float32),
            jnp.asarray(rng.randn(4, d, width) * 0.1, jnp.float32),
            jnp.asarray(rng.randn(4, width, d) * 0.1, jnp.float32))
    target = jnp.asarray(rng.randn(rows, d), jnp.float32)

    def loss(fn):
        def of(rows_, weight_, wg, wu, wd):
            out = fn(rows_, weight_, counts, wg, wu, wd)
            return jnp.sum(jnp.where(live, out.astype(jnp.float32), 0)
                           * target)
        return jax.value_and_grad(of, argnums=(0, 1, 2, 3, 4))

    return args, live, loss


@pytest.mark.parametrize("form", ["xla", "kernels"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_expert_mlp_has_a_backward_of_its_own(dtype, form):
    """``moe_expert_mlp``'s hand-written gradients (rows, row weights and
    all three weights, which come back in the masters' float32) against
    ``jax.grad`` of the plain composition, on the ``ragged_dot`` path with
    XLA's gate and with every kernel in interpret mode (the grouped
    matmuls' six kinds: ``gmm``, its transposed form, the two-pair kernel,
    ``tgmm`` and, since PR 36, the gate and its transpose on the live
    tiles); in bf16 within bf16's rounding of each gradient's largest
    entry."""
    from paddle_tpu.ops import moe_ops

    args, live, loss = _expert_mlp_case(dtype)
    assert moe_ops.gm.gate_by_kernel(256, 128, form == "kernels") == (
        form == "kernels")

    def own(rows_, weight_, counts, wg, wu, wd):
        wg, wu, wd = (w.astype(rows_.dtype) for w in (wg, wu, wd))
        return moe_ops._expert_mlp(rows_, weight_, counts, wg, wu, wd,
                                   form == "kernels")

    rest = args[:2] + args[3:]
    value, got = loss(own)(*rest)
    wanted, want = loss(_plain_expert_mlp)(*rest)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(float(value), float(wanted),
                               rtol=tol, atol=tol)
    masks = (live, live[:, 0], True, True, True)
    for name, a, b, keep in zip(("rows", "row weights", "gate", "up", "down"),
                                got, want, masks):
        a = np.where(keep, np.asarray(a, np.float32), 0)
        b = np.where(keep, np.asarray(b, np.float32), 0)
        assert np.abs(a - b).max() <= tol * max(np.abs(b).max(), 1e-6), name
    assert [g.dtype for g in got] == [dtype, jnp.float32, jnp.float32,
                                      jnp.float32, jnp.float32]


def test_the_expert_mlp_counts_the_form_of_its_pair_product():
    """``moe.gmm_pair_xla`` / ``moe.gmm_pair_kernel``: call sites of the
    two-pair product lowered in each form, counted where the backward is
    traced: one a layer, XLA's on the CPU, the kernel's under the
    interpreter."""
    from paddle_tpu import observability as obs
    from paddle_tpu.ops import moe_ops

    obs.set_enabled(True)
    names = ("moe.gmm_pair_kernel", "moe.gmm_pair_xla")
    args, _, _ = _expert_mlp_case(jnp.float32)

    def counted(interpret):
        before = [obs.counter_value(n) for n in names]
        jax.grad(lambda rows_: jnp.sum(moe_ops._expert_mlp(
            rows_, *args[1:], interpret)[:8]))(args[0])
        return [obs.counter_value(n) - was for n, was in zip(names, before)]

    assert counted(False) == [0, 1]
    assert counted(True) == [1, 0]
    before = [obs.counter_value(n) for n in names]
    rng = np.random.RandomState(3)
    _expert_layer(rng.randn(16, D).astype(np.float32), _params(rng), 2, 2)
    assert [obs.counter_value(n) for n in names] == before  # forward only


def test_the_expert_mlp_counts_the_form_of_its_gate(monkeypatch):
    """``moe.gate_xla`` / ``moe.gate_kernel``: call sites of the gate
    lowered in each form, the forward's counted by the op's lowering and
    the transpose where the backward is traced: XLA's on the CPU, the
    kernels' under the interpreter. Where the kernels run, the gauges
    ``moe.gate_tiles`` (tiles in the buffer) and, a step,
    ``moe.gate_tiles_live`` (tiles visited); none on the CPU's path."""
    from paddle_tpu import observability as obs
    from paddle_tpu.kernels import grouped_matmul as gm
    from paddle_tpu.ops import moe_ops

    obs.set_enabled(True)
    names = ("moe.gate_kernel", "moe.gate_xla")
    args, _, _ = _expert_mlp_case(jnp.float32)

    def counted(run):
        before = [obs.counter_value(n) for n in names]
        out = run()
        jax.effects_barrier()
        return [obs.counter_value(n) - was
                for n, was in zip(names, before)], out

    def backward(interpret):
        return lambda: jax.grad(lambda rows_: jnp.sum(moe_ops._expert_mlp(
            rows_, *args[1:], interpret)[:8]))(args[0])

    assert counted(backward(False))[0] == [0, 1]
    assert counted(backward(True))[0] == [1, 0]
    rng = np.random.RandomState(3)
    x, params = rng.randn(16, D).astype(np.float32), _params(rng)
    count, (want, _) = counted(lambda: _expert_layer(x, params, 2, 2))
    assert count == [0, 1]                           # forward only
    gauges = obs.snapshot()["gauges"]
    assert "moe.gate_tiles" not in gauges
    assert "moe.gate_tiles_live" not in gauges
    # the gate's kernels alone (interpreted: this is not the TPU) between
    # ``ragged_dot``s: the buffer of 16 x TOP rows is one tile
    monkeypatch.setattr(gm, "gate_by_kernel",
                        lambda rows, width, interpret=False: True)
    count, (got, counts) = counted(lambda: _expert_layer(x, params, 2, 2))
    assert count == [1, 0]
    np.testing.assert_allclose(got, want, atol=1e-6, rtol=1e-6)
    gauges = obs.snapshot()["gauges"]
    assert gauges["moe.gate_tiles"] == 1
    assert gauges["moe.gate_tiles_live"] == (counts.sum() > 0)


def test_the_expert_layer_counts_itself():
    """Lowering-time counters (the layer, and the form its two forward row
    movements were lowered in: XLA's gathers on the CPU) and, under the
    metrics flag, the per-step load of the held experts."""
    from paddle_tpu import observability as obs

    obs.set_enabled(True)
    before = {name: obs.counter_value(name) for name in (
        "moe.layers", "moe.permute_xla", "moe.permute_kernel")}
    rng = np.random.RandomState(3)
    _, counts = _expert_layer(rng.randn(16, D).astype(np.float32),
                              _params(rng), 2, 2)
    jax.effects_barrier()
    assert {name: obs.counter_value(name) - was
            for name, was in before.items()} == {
        "moe.layers": 1, "moe.permute_xla": 2, "moe.permute_kernel": 0}
    gauges = obs.snapshot()["gauges"]
    assert "moe.permute_visits" not in gauges
    assert gauges["moe.buffer_rows"] == 16 * TOP
    assert gauges["moe.experts_held"] == 2
    assert gauges["moe.pairs_held"] == int(counts.sum())
    assert gauges["moe.load_max_over_mean"] == pytest.approx(
        counts.max() / counts.mean())


# -- norms and rotary tables ---------------------------------------------------

def test_rms_norm_matches_numpy():
    rng = np.random.RandomState(4)
    x = rng.randn(3, 5, 32).astype(np.float32)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        data = fluid.layers.data(name="x", shape=[5, 32], dtype="float32")
        out = _nn.rms_norm(data, 1e-6, fluid.ParamAttr(name="g"))
    exe, scope = fluid.Executor(), fluid.Scope()
    exe.run(startup, scope=scope)
    g = rng.rand(32).astype(np.float32) + 0.5
    scope.set("g", jnp.asarray(g))
    (got,) = exe.run(main, feed={"x": x}, fetch_list=[out], scope=scope)
    want = x / np.sqrt((x ** 2).mean(-1, keepdims=True) + 1e-6) * g
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)


def test_rotary_tables_follow_the_published_formulas():
    """Default: theta^(-2i/d). YaRN at the configuration's numbers (head
    128, theta 500,000, factor 16, original length 8192, beta 32 / 1):
    dim(r) = 128 ln(8192 / (2 pi r)) / (2 ln theta) puts the ramp between
    dimensions 18 and 35, and the factor on cos and sin is 0.1 ln 16 + 1."""
    i = np.arange(64, dtype=np.float64)
    base = 500000.0 ** (-2 * i / 128)
    inv, scaling = rope_inv_freq(128, DEFAULT)
    np.testing.assert_allclose(inv, base, rtol=1e-12)
    assert scaling == 1.0
    inv, scaling = rope_inv_freq(128, YARN)
    dim = lambda r: 128 * np.log(8192 / (2 * np.pi * r)) / (
        2 * np.log(500000.0))
    low, high = np.floor(dim(32)), np.ceil(dim(1))
    assert (low, high) == (18, 35)
    ramp = np.clip((i - low) / (high - low), 0, 1)
    np.testing.assert_allclose(inv, base * ((1 - ramp) + ramp / 16),
                               rtol=1e-12)
    np.testing.assert_allclose(inv[:19], base[:19], rtol=1e-12)
    np.testing.assert_allclose(inv[35:], base[35:] / 16, rtol=1e-12)
    assert scaling == YARN["attention_factor"]
    no_factor = {k: v for k, v in YARN.items() if k != "attention_factor"}
    assert rope_inv_freq(128, no_factor)[1] == pytest.approx(
        YARN["attention_factor"], rel=1e-12)


@pytest.mark.parametrize("rope", [DEFAULT, YARN], ids=["default", "yarn"])
def test_rotary_embedding_matches_the_reference(rope):
    rng = np.random.RandomState(5)
    q = rng.randn(2, 4, 24, 16).astype(np.float32)
    k = rng.randn(2, 2, 24, 16).astype(np.float32)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        qd = fluid.layers.data(name="q", shape=[4, 24, 16], dtype="float32")
        kd = fluid.layers.data(name="k", shape=[2, 24, 16], dtype="float32")
        outs = _nn.rotary_embedding([qd, kd], **rope)
    exe = fluid.Executor()
    exe.run(startup)
    got = exe.run(main, feed={"q": q, "k": k}, fetch_list=outs)
    cos, sin = mellum2.rope_tables(16, 24, rope)
    for x, y in zip((q, k), got):
        want = mellum2._rope(jnp.asarray(x).transpose(0, 2, 1, 3), cos, sin)
        np.testing.assert_allclose(y, np.asarray(want).transpose(0, 2, 1, 3),
                                   atol=1e-5, rtol=1e-5)


def _rotate_by_slices(x, attrs):
    """The rotation as the program lowered it until PR 32, in plain
    ``jax.numpy``: the head sliced at its middle, float32 halves, a
    concatenate. The lowering is one product now and has to agree."""
    t, d = x.shape[-2:]
    inv, scaling = rope_inv_freq(d, attrs)
    angle = np.arange(t, dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(angle) * scaling, jnp.float32)
    sin = jnp.asarray(np.sin(angle) * scaling, jnp.float32)
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., :d // 2], x32[..., d // 2:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1).astype(x.dtype)


def _rotate_by_the_lowering(xs, attrs):
    op = fluid.Program().global_block().append_op(
        type="rotary_embedding", inputs={}, outputs={}, attrs=dict(attrs))
    return OpRegistry.get("rotary_embedding").lower(
        LowerContext(op, None), {"X": list(xs)}, attrs)["Out"]


@pytest.mark.parametrize("head", [128, 64])
@pytest.mark.parametrize("rope", [DEFAULT, YARN], ids=["default", "yarn"])
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_the_rotation_as_one_product_equals_the_slices(dtype, rope, head):
    """Q and K through the lowering (x*cos + (x @ R)*sin under its own
    ``custom_vjp``) and through the slices, result and ``jax.vjp``
    cotangent: the product only selects and a - b is a + (-b), so op by
    op on the CPU they are equal bit for bit. (Inside one jitted
    computation the CPU's compiler contracts the two backward forms'
    multiply-adds differently, a float32 ulp apart.)"""
    rng = np.random.RandomState(6)
    xs = [jnp.asarray(rng.randn(2, heads, 48, head), dtype)
          for heads in (4, 2)]
    gs = [jnp.asarray(rng.randn(*x.shape), dtype) for x in xs]
    got, got_vjp = jax.vjp(lambda *a: _rotate_by_the_lowering(a, rope), *xs)
    want, want_vjp = jax.vjp(
        lambda *a: [_rotate_by_slices(x, rope) for x in a], *xs)
    for a, b in zip(list(got) + list(got_vjp(gs)),
                    list(want) + list(want_vjp(gs))):
        assert a.dtype == b.dtype == jnp.dtype(dtype)
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_the_rotation_counts_its_tensors_once():
    """``rope.rotations`` under the metrics flag: 2 for one attention's Q
    and K in a step that holds the grad op too, whose replay of the
    forward inside ``jax.vjp`` counts nothing."""
    from paddle_tpu import observability as obs

    rng = np.random.RandomState(7)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        qd = fluid.layers.data(name="q", shape=[4, 24, 16], dtype="float32")
        kd = fluid.layers.data(name="k", shape=[2, 24, 16], dtype="float32")
        w = fluid.layers.create_parameter([16], "float32", name="w")
        q, k = _nn.rotary_embedding([qd * w, kd * w], **YARN)
        loss = fluid.layers.reduce_mean(q) + fluid.layers.reduce_mean(k)
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    assert "rotary_embedding_grad" in [
        op.type for op in main.global_block().ops]
    exe = fluid.Executor()
    exe.run(startup)
    obs.set_enabled(True)
    before = obs.counter_value("rope.rotations")
    exe.run(main, feed={"q": rng.randn(2, 4, 24, 16).astype(np.float32),
                        "k": rng.randn(2, 2, 24, 16).astype(np.float32)},
            fetch_list=[loss])
    assert obs.counter_value("rope.rotations") - before == 2


# -- the sandwich-norm, sigmoid-routed family (the trinity_mini cell) ----------

TRINITY = "trinity_mini.pretrain_b2"
TRINITY_SEED = 3000000007


@pytest.fixture(scope="module")
def trinity():
    """The cell's program at the rehearsal size, built and driven through
    its first three steps once, with the metrics flag up."""
    from paddle_tpu import observability as obs

    workload, cfg, rows = _cell(TRINITY)
    names = ("moe.shared_experts", "moe.router_sigmoid", "moe.bias_updates",
             "gated_mlp.calls", "attn.gated", "attn.qk_norm",
             "attn.unrotated_layers")
    obs.set_enabled(True)
    before = {name: obs.counter_value(name) for name in names}
    with fluid.unique_name.guard():
        trainer = train.Trainer(cfg, rows, workload, rehearse=True)
    trainer.start(TRINITY_SEED)
    program, _ = trainer.warm_up()
    jax.effects_barrier()
    counted = {name: obs.counter_value(name) - was
               for name, was in before.items()}
    yield dict(workload=workload, cfg=cfg, rows=rows, trainer=trainer,
               program=program, counted=counted,
               gauges=obs.snapshot()["gauges"])
    trainer.exe.close()


def test_the_trinity_program_follows_its_reference(trinity):
    """get_model with every new argument -> enable_bf16 -> Executor.run
    against the float32 reference through three steps, under the cell's
    rehearsal limits: losses, first gradients, changes, the bias state's
    change among them."""
    cfg, trainer = trinity["cfg"], trinity["trainer"]
    reference = common.follow(trinity_mini, cfg, trinity["rows"],
                              TRINITY_SEED)
    correct, compared = compare.judge(
        trinity["program"], reference,
        train.limits(trinity["workload"], rehearse=True))
    assert correct, compared
    assert compared["loss_gap"]["value"] < 1e-3
    ops = trainer.main.desc.global_block().ops
    kinds = [op.type for op in ops]
    assert kinds.count("gated_mlp") == 5 and kinds.count("moe_router") == 4
    assert kinds.count("rotary_embedding") == 4     # none on the full layer
    assert kinds.count("moe_bias_update") == 4
    assert kinds.index("moe_bias_update") > max(
        i for i, kind in enumerate(kinds) if kind.endswith("_grad"))
    m = cfg["model"]
    assert [op.attrs.get("window") for op in ops
            if op.type == "fused_attention"] == [m["sliding_window"]] * 4 + [
                None]
    biases = sorted(trinity_mini.state_specs(cfg))
    assert biases == ["layer%d.expert_bias" % i for i in (1, 2, 3, 4)]
    for name in biases:
        moved = reference["change_norms"][name]
        assert moved > 0
        assert trinity["program"]["change_norms"][name] == pytest.approx(
            moved, rel=0.35)
        bias = np.asarray(trainer.scope.get(name))
        assert abs(bias.sum()) < 1e-6 and np.abs(bias).max() <= 6.1e-3


def test_the_trinity_program_counts_what_it_was_built_with(trinity):
    """The new counters under the metrics flag: one dense and four sparse
    layers, each with a gated, q/k-normed attention, the fifth unrotated;
    and the step's gauge over all router outputs."""
    assert trinity["counted"] == {
        "moe.shared_experts": 4, "moe.router_sigmoid": 4,
        "moe.bias_updates": 4, "gated_mlp.calls": 5, "attn.gated": 5,
        "attn.qk_norm": 5, "attn.unrotated_layers": 1}
    assert trinity["gauges"]["moe.router_load_max_over_mean"] >= 1.0


def _router(x, params, bias, fetch_grad=False):
    """TopkWeight, TopkIds and Load of a sigmoid router with a bias, scale
    2.5, through Executor.run; with ``fetch_grad`` the router weight's
    gradient of sum(TopkWeight * arange) too."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        data = fluid.layers.data(name="x", shape=[D], dtype="float32")
        weight, ids, load, bias_var = _nn.moe_router(
            data, EXPERTS, TOP, param_attr=fluid.ParamAttr(name="router"),
            score_func="sigmoid", route_scale=2.5, bias_name="bias")
        fetch = [weight, ids, load]
        if fetch_grad:
            ramp = fluid.layers.assign(
                np.arange(1, TOP + 1, dtype=np.float32))
            loss = fluid.layers.reduce_sum(weight * ramp)
            fluid.optimizer.SGD(learning_rate=1.0).minimize(loss)
            fetch.append(main.global_block().var("router@GRAD"))
    assert bias_var.persistable and bias_var.stop_gradient
    assert not any("bias@GRAD" in name for op in main.global_block().ops
                   for name in op.desc.output_arg_names())
    exe, scope = fluid.Executor(), fluid.Scope()
    exe.run(startup, scope=scope)
    assert np.all(np.asarray(scope.get("bias")) == 0)
    scope.set("router", jnp.asarray(params))
    scope.set("bias", jnp.asarray(bias))
    got = exe.run(main, feed={"x": x}, fetch_list=fetch, scope=scope)
    exe.close()
    return got


def _numpy_router(x, params, bias, scale=2.5):
    scores = 1.0 / (1.0 + np.exp(-(x.astype(np.float64) @ params)))
    ids = np.argsort(-(scores + bias), axis=1, kind="stable")[:, :TOP]
    top = np.take_along_axis(scores, ids, 1)
    return scale * top / (top.sum(1, keepdims=True) + 1e-20), ids


def test_the_sigmoid_router_selects_by_score_plus_bias_and_weighs_by_score():
    """Against numpy: the k largest of sigmoid + bias (a bias large enough
    to push experts 6 and 7 into every token's choice), the weights from
    the sigmoids alone, renormalised, times the scale; Load counts every
    selection; the router's gradient is that of the weights with the
    choice held fixed, and nothing flows to the bias."""
    rng = np.random.RandomState(8)
    x = rng.randn(48, D).astype(np.float32)
    params = (rng.randn(D, EXPERTS) * 0.3).astype(np.float32)
    bias = np.zeros(EXPERTS, np.float32)
    bias[6:] = (3.0, 1.5)
    weight, ids, load, grad = _router(x, params, bias, fetch_grad=True)
    want_weight, want_ids = _numpy_router(x, params, bias)
    np.testing.assert_array_equal(ids, want_ids)
    assert (ids[:, 0] == 6).all() and (ids[:, 1] == 7).all()
    np.testing.assert_allclose(weight, want_weight, rtol=2e-5)
    np.testing.assert_allclose(weight.sum(1), 2.5, rtol=1e-5)
    assert load.tolist() == np.bincount(want_ids.ravel(),
                                        minlength=EXPERTS).tolist()
    assert load.sum() == 48 * TOP
    unbiased, free_ids, _ = _router(x, params, np.zeros(EXPERTS, np.float32))
    assert (free_ids != ids).any()

    def loss(w):
        scores = jax.nn.sigmoid(jnp.dot(
            jnp.asarray(x), w, precision=jax.lax.Precision.HIGHEST))
        top = jnp.take_along_axis(scores, jnp.asarray(want_ids), 1)
        top = 2.5 * top / (top.sum(1, keepdims=True) + 1e-20)
        return jnp.sum(top * jnp.arange(1, TOP + 1, dtype=jnp.float32))

    np.testing.assert_allclose(grad, jax.grad(loss)(jnp.asarray(params)),
                               atol=1e-5, rtol=1e-4)


def test_the_bias_update_on_a_hand_made_load():
    """coeff * sign(mean - load), its mean taken out, added in place: loads
    (9, 1, 5, 5) have mean 5: signs (-1, +1, 0, 0), mean 0; loads (8, 0, 2,
    2) have mean 3: signs (-1, +1, +1, +1), mean 1/2. The reference's own
    update reads the same."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        load = fluid.layers.data(name="load", shape=[4], dtype="int32",
                                 append_batch_size=False)
        bias = main.global_block().create_var(
            name="bias", shape=[4], dtype="float32", persistable=True)
        _nn.moe_bias_update(bias, load, 0.01)
    (op,) = [op for op in main.global_block().ops]
    assert op.type == "moe_bias_update"
    assert int(op.attr("op_role")) & int(OpRole.Optimize)
    exe, scope = fluid.Executor(), fluid.Scope()
    scope.set("bias", jnp.asarray([0.5, 0.0, -0.5, 0.0], jnp.float32))
    exe.run(main, feed={"load": np.asarray([9, 1, 5, 5], np.int32)},
            scope=scope)
    np.testing.assert_allclose(np.asarray(scope.get("bias")),
                               [0.49, 0.01, -0.5, 0.0], atol=1e-7)
    exe.run(main, feed={"load": np.asarray([8, 0, 2, 2], np.int32)},
            scope=scope)
    want = np.asarray([0.49, 0.01, -0.5, 0.0]) + 0.01 * (
        np.asarray([-1.0, 1.0, 1.0, 1.0]) - 0.5)
    np.testing.assert_allclose(np.asarray(scope.get("bias")), want,
                               atol=1e-7)
    np.testing.assert_allclose(trinity_mini.update_bias(
        jnp.asarray([0.49, 0.01, -0.5, 0.0]), jnp.asarray([8, 0, 2, 2]),
        0.01), want, atol=1e-7)
    exe.close()


def test_gated_mlp_and_its_gradient_match_numpy():
    """(silu(x Wg) * (x Wu)) Wd on [3, 5, D] rows, and the four gradients
    of sum(out * c) by the chain rule in numpy (float64)."""
    rng = np.random.RandomState(9)
    x = rng.randn(3, 5, D).astype(np.float32)
    c = rng.randn(3, 5, D).astype(np.float32)
    w = {"g": rng.randn(D, WIDTH) * 0.3, "u": rng.randn(D, WIDTH) * 0.3,
         "d": rng.randn(WIDTH, D) * 0.3}
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        data = fluid.layers.data(name="x", shape=[5, D], dtype="float32")
        data.stop_gradient = False
        coef = fluid.layers.data(name="c", shape=[5, D], dtype="float32")
        out = _nn.gated_mlp(data, WIDTH, *(fluid.ParamAttr(name=n)
                                           for n in "gud"))
        loss = fluid.layers.reduce_sum(out * coef)
        fluid.optimizer.SGD(learning_rate=1.0).minimize(loss)
        grads = [main.global_block().var(n + "@GRAD") for n in "xgud"]
    assert [op.type for op in main.global_block().ops].count(
        "gated_mlp_grad") == 1
    exe, scope = fluid.Executor(), fluid.Scope()
    exe.run(startup, scope=scope)
    for name, value in w.items():
        scope.set(name, jnp.asarray(value, jnp.float32))
    got = exe.run(main, feed={"x": x, "c": c}, fetch_list=[out] + grads,
                  scope=scope)
    exe.close()
    x2, c2 = x.reshape(-1, D).astype(np.float64), c.reshape(-1, D)
    gate, up = x2 @ w["g"], x2 @ w["u"]
    sig = 1.0 / (1.0 + np.exp(-gate))
    hidden = gate * sig * up
    d_hidden = c2 @ w["d"].T
    d_gate = d_hidden * up * (sig + gate * sig * (1.0 - sig))
    d_up = d_hidden * gate * sig
    want = [hidden @ w["d"], d_gate @ w["g"].T + d_up @ w["u"].T,
            x2.T @ d_gate, x2.T @ d_up, hidden.T @ c2]
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a).reshape(b.shape), b,
                                   atol=2e-4, rtol=2e-4)


def _sigmoid_layer(x, params, held, offset, bias):
    """The routed part (for the experts ``offset .. offset + held``) and
    the shared expert of the program's sigmoid-routed layer."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        data = fluid.layers.data(name="x", shape=[D], dtype="float32")
        weight, ids, _, _ = _nn.moe_router(
            data, EXPERTS, TOP, param_attr=fluid.ParamAttr(name="router"),
            score_func="sigmoid", route_scale=2.5, bias_name="bias")
        routed, _ = _nn.moe_experts(
            data, weight, ids, held, offset, WIDTH,
            gate_attr=fluid.ParamAttr(name="gate"),
            up_attr=fluid.ParamAttr(name="up"),
            down_attr=fluid.ParamAttr(name="down"))
        shared = _nn.gated_mlp(data, WIDTH, *(fluid.ParamAttr(name=n) for n
                                              in ("sg", "su", "sd")))
    exe, scope = fluid.Executor(), fluid.Scope()
    exe.run(startup, scope=scope)
    scope.set("router", jnp.asarray(params["router"]))
    scope.set("bias", jnp.asarray(bias))
    for name in ("gate", "up", "down"):
        scope.set(name, jnp.asarray(params[name][offset:offset + held]))
    for name in ("sg", "su", "sd"):
        scope.set(name, jnp.asarray(params[name]))
    got = exe.run(main, feed={"x": x}, fetch_list=[routed, shared],
                  scope=scope)
    exe.close()
    return got


def test_the_sigmoid_shares_and_the_shared_expert_add_up_to_the_uncut_layer():
    """8 experts in 4 shares of 2, 3 a token, a bias that moves the choice:
    the four shares' routed parts and the shared expert counted ONCE add up
    to what the reference gives for the whole layer (all 8 held), and each
    share with the shared expert is the reference's own share."""
    rng = np.random.RandomState(10)
    x = rng.randn(40, D).astype(np.float32)
    params = _params(rng, (rng.randn(D, EXPERTS) * 0.3).astype(np.float32))
    params.update(sg=(rng.randn(D, WIDTH) * 0.3).astype(np.float32),
                  su=(rng.randn(D, WIDTH) * 0.3).astype(np.float32),
                  sd=(rng.randn(WIDTH, D) * 0.3).astype(np.float32))
    bias = (rng.randn(EXPERTS) * 0.2).astype(np.float32)

    def reference(held, offset):
        m = {"num_experts_per_tok": TOP, "experts_held": held,
             "expert_offset": offset, "route_scale": 2.5}
        p = {"l.router": params["router"], "l.shared_gate": params["sg"],
             "l.shared_up": params["su"], "l.shared_down": params["sd"]}
        for name in ("gate", "up", "down"):
            p["l.experts_" + name] = params[name][offset:offset + held]
        with jax.default_matmul_precision("highest"):
            out, load = trinity_mini._experts(
                common.Matmuls("f32"), m,
                {k: jnp.asarray(v) for k, v in p.items()}, "l.",
                jnp.asarray(x), jnp.asarray(bias))
        assert int(load.sum()) == x.shape[0] * TOP
        return np.asarray(out)

    total = 0.0
    for offset in range(0, EXPERTS, 2):
        routed, shared = _sigmoid_layer(x, params, 2, offset, bias)
        np.testing.assert_allclose(routed + shared, reference(2, offset),
                                   atol=5e-5, rtol=2e-4)
        total = total + routed
    np.testing.assert_allclose(total + shared, reference(EXPERTS, 0),
                               atol=1e-4, rtol=2e-4)


# -- the conv / attention hybrid (the lfm2_24b_a2b cell) -----------------------

LFM2 = "lfm2_24b_a2b.pretrain_b2"
LFM2_SEED = 3000000007


def _numpy_short_conv(z, w):
    """C * conv(B * x) and, for the cotangent g, (dz, dw), in float64, by
    loops over the taps: the issue's equations as they stand."""
    z, w = z.astype(np.float64), w.astype(np.float64)
    d, taps = w.shape
    seq = z.shape[1]
    b, c_gate, x = z[..., :d], z[..., d:2 * d], z[..., 2 * d:]
    p = b * x
    c = np.zeros_like(p)
    for t in range(seq):
        for j in range(taps):
            if t - (taps - 1) + j >= 0:
                c[:, t] += w[:, j] * p[:, t - (taps - 1) + j]

    def backward(g):
        gc = c_gate * g.astype(np.float64)
        dp, dw = np.zeros_like(p), np.zeros_like(w)
        for s in range(seq):
            for j in range(taps):
                if s + (taps - 1) - j < seq:
                    dp[:, s] += w[:, j] * gc[:, s + (taps - 1) - j]
        for j in range(taps):
            for t in range(seq):
                if t - (taps - 1) + j >= 0:
                    dw[:, j] += (gc[:, t] * p[:, t - (taps - 1) + j]).sum(0)
        return np.concatenate([dp * x, g * c, dp * b], -1), dw

    return c_gate * c, backward


def _short_conv(z, w, g=None):
    """Out of the ``gated_short_conv`` op through Executor.run and, with a
    cotangent ``g``, the gradients of sum(Out * g) for X and the filter."""
    d, taps = w.shape
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        data = fluid.layers.data(name="z", shape=list(z.shape[1:]),
                                 dtype="float32")
        data.stop_gradient = False
        out = _nn.gated_short_conv(
            data, filter_attr=fluid.ParamAttr(name="filter"), taps=taps)
        fetch = [out]
        if g is not None:
            loss = fluid.layers.reduce_sum(out * fluid.layers.assign(g))
            fluid.optimizer.SGD(learning_rate=1.0).minimize(loss)
            fetch += [main.global_block().var("z@GRAD"),
                      main.global_block().var("filter@GRAD")]
    assert tuple(main.global_block().var("filter").shape) == (d, taps)
    exe, scope = fluid.Executor(), fluid.Scope()
    exe.run(startup, scope=scope)
    start = np.asarray(scope.get("filter"))
    assert np.abs(start).max() <= taps ** -0.5 and start.std() > 0.2
    scope.set("filter", jnp.asarray(w))
    got = exe.run(main, feed={"z": z}, fetch_list=fetch, scope=scope)
    exe.close()
    return got


@pytest.mark.parametrize("taps", [3, 4])
def test_gated_short_conv_and_its_gradient_match_numpy(taps):
    """C * conv(B * x) on [2, 9, 3 x 8] and both gradients of sum(Out * g)
    against loops in numpy; the filter's length is read from its shape."""
    rng = np.random.RandomState(20 + taps)
    z = rng.randn(2, 9, 24).astype(np.float32)
    w = rng.uniform(-0.6, 0.6, (8, taps)).astype(np.float32)
    g = rng.randn(2, 9, 8).astype(np.float32)
    out, dz, dw = _short_conv(z, w, g)
    want, backward = _numpy_short_conv(z, w)
    want_dz, want_dw = backward(g)
    np.testing.assert_allclose(out, want, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(dz, want_dz, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(dw, want_dw, atol=2e-5, rtol=1e-5)
    # the plain reference's own convolution is the same sum
    np.testing.assert_allclose(
        z[..., 8:16] * np.asarray(lfm2_moe.short_conv(
            jnp.asarray(z[..., :8] * z[..., 16:]), jnp.asarray(w))),
        want, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("taps", [3, 4])
def test_gated_short_conv_is_causal_and_starts_from_zeros(taps):
    """Move one input position: the outputs at that position and the
    ``taps - 1`` after it move (the gate C at the position itself), none
    before it and none further on, and no other row of the batch. With a
    filter whose last tap is zero the first position's output is zero: it
    sees only the zeros before the sequence."""
    rng = np.random.RandomState(30 + taps)
    z = rng.randn(2, 12, 12).astype(np.float32)
    w = rng.uniform(0.2, 0.6, (4, taps)).astype(np.float32)
    (base,) = _short_conv(z, w)
    moved = z.copy()
    moved[1, 5] += 1.0
    (out,) = _short_conv(moved, w)
    changed = np.abs(out - base).max(-1) > 1e-6
    assert changed[1].tolist() == [5 <= t < 5 + taps for t in range(12)]
    assert not changed[0].any()
    w[:, -1] = 0.0
    (out,) = _short_conv(z, w)
    assert np.all(out[:, 0] == 0) and np.abs(out[:, 1]).min() > 0
    zeros = z.copy()
    zeros[:, :taps - 1] = 0.0       # p = 0 on the first taps - 1 positions
    (out,) = _short_conv(zeros, w)
    assert np.all(out[:, :taps - 1] == 0)


@pytest.fixture(scope="module")
def lfm2():
    """The cell's program at the rehearsal size, built and driven through
    its first three steps once, with the metrics flag up."""
    from paddle_tpu import observability as obs

    workload, cfg, rows = _cell(LFM2)
    names = ("decoder.conv_layers", "decoder.tied_head", "short_conv.calls",
             "short_conv.taps", "attn.qk_norm", "moe.router_sigmoid",
             "moe.bias_updates", "gated_mlp.calls", "rope.rotations",
             "moe.shared_experts", "attn.gated")
    obs.set_enabled(True)
    before = {name: obs.counter_value(name) for name in names}
    with fluid.unique_name.guard():
        trainer = train.Trainer(cfg, rows, workload, rehearse=True)
    trainer.start(LFM2_SEED)
    program, _ = trainer.warm_up()
    jax.effects_barrier()
    counted = {name: obs.counter_value(name) - was
               for name, was in before.items()}
    yield dict(workload=workload, cfg=cfg, rows=rows, trainer=trainer,
               program=program, counted=counted)
    trainer.exe.close()


def test_the_lfm2_program_follows_its_reference(lfm2):
    """get_model with conv layers, a tied head and the router's epsilon ->
    enable_bf16 -> Executor.run against the float32 reference through three
    steps, under the cell's rehearsal limits: losses, first gradients (the
    filters' and the tied leaf's among them), changes, the bias state's."""
    cfg, trainer = lfm2["cfg"], lfm2["trainer"]
    reference = common.follow(lfm2_moe, cfg, lfm2["rows"], LFM2_SEED)
    correct, compared = compare.judge(
        lfm2["program"], reference,
        train.limits(lfm2["workload"], rehearse=True))
    assert correct, compared
    assert compared["loss_gap"]["value"] < 1e-3
    ops = trainer.main.desc.global_block().ops
    kinds = [op.type for op in ops]
    assert kinds.count("gated_short_conv") == 4
    assert kinds.count("gated_short_conv_grad") == 4
    assert kinds.count("fused_attention") == 1          # no mixer but conv
    assert kinds.count("rotary_embedding") == 1
    assert kinds.count("gated_mlp") == 1 and kinds.count("moe_router") == 4
    assert kinds.count("moe_bias_update") == 4
    assert [op.attrs.get("norm_eps") for op in ops
            if op.type == "moe_router"] == [1e-6] * 4
    names = {p.name for p in trainer.main.all_parameters()}
    assert "lm_head" not in names
    assert {"layer0.conv_filter", "layer0.conv_in_proj",
            "layer0.conv_out_proj", "layer0.conv_norm", "layer1.q_norm",
            "layer1.attn_norm"} <= names
    assert not {"layer0.q_proj", "layer0.attn_norm", "layer1.conv_filter"
                } & names
    for name in ("tok_embedding", "layer0.conv_filter", "layer4.conv_filter"):
        assert reference["grad_norms"][name] > 0
        assert lfm2["program"]["grad_norms"][name] == pytest.approx(
            reference["grad_norms"][name], rel=0.05)
    biases = sorted(lfm2_moe.state_specs(cfg))
    assert biases == ["layer%d.expert_bias" % i for i in (1, 2, 3, 4)]
    for name in biases:
        moved = reference["change_norms"][name]
        assert moved > 0
        assert lfm2["program"]["change_norms"][name] == pytest.approx(
            moved, rel=0.35)
        bias = np.asarray(trainer.scope.get(name))
        assert abs(bias.sum()) < 1e-6 and np.abs(bias).max() <= 6.1e-3


def test_the_tied_leaf_takes_the_sum_of_both_gradients(lfm2):
    """One leaf ``tok_embedding``, read by the look-up and, transposed, by
    the head: its gradient is a ``sum`` op over the look-up's and the
    matmul's, both non-zero, and Adam gets the sum."""
    trainer = lfm2["trainer"]
    ops = trainer.main.desc.global_block().ops
    (total,) = [op for op in ops if op.type == "sum"
                and op.output("Out") == ["tok_embedding@GRAD"]]
    parts = total.input("X")
    assert len(parts) == 2
    makers = sorted(op.type for op in ops if op.type != "sum"
                    and set(op.output_arg_names()) & set(parts))
    assert makers == ["lookup_table_grad", "matmul_grad"]
    head = [op for op in ops if op.type == "matmul"]
    assert len(head) == 1 and head[0].input("Y") == ["tok_embedding"]
    assert head[0].attrs["transpose_Y"]
    (looked_up,) = [op.output("W@GRAD")[0] for op in ops
                    if op.type == "lookup_table_grad"]
    part, both = trainer.exe.run(
        trainer.main, feed=trainer.pool[0], scope=trainer.scope,
        fetch_list=[looked_up, "tok_embedding@GRAD"])
    # (the sum writes over the matmul's part, so that one is not fetched:)
    # a row no id of the batch names has the head's gradient alone, a row
    # that one names has more than the look-up's
    used = np.zeros(part.shape[0], bool)
    used[np.asarray(trainer.pool[0]["ids"]).ravel()] = True
    assert used.any() and not used.all()
    assert np.all(part[~used] == 0) and np.abs(both[~used]).min(0).max() > 0
    assert np.abs(part[used]).max() > 0
    assert np.abs(both[used] - part[used]).max() > 0


def test_the_lfm2_program_counts_what_it_was_built_with(lfm2):
    """The new counters under the metrics flag: four conv layers of three
    taps each, one tied head; one q/k-normed, rotated attention; one dense
    and four sigmoid-routed layers with a bias; no shared expert, no gate."""
    assert lfm2["counted"] == {
        "decoder.conv_layers": 4, "decoder.tied_head": 1,
        "short_conv.calls": 4, "short_conv.taps": 12, "attn.qk_norm": 1,
        "moe.router_sigmoid": 4, "moe.bias_updates": 4,
        "gated_mlp.calls": 1, "rope.rotations": 2, "moe.shared_experts": 0,
        "attn.gated": 0}


@pytest.mark.parametrize("eps", [1e-6, 0.25])
def test_the_router_adds_norm_eps_to_the_sum_of_the_chosen_scores(eps):
    """A sigmoid router at top 4 of 8 with ``norm_eps`` against numpy: the
    weights are the chosen sigmoids over their sum + eps (0.25 is large
    enough to show in float32; 1e-6 is the configuration's), and a router
    built without the argument keeps 1e-20 and no attribute."""
    rng = np.random.RandomState(40)
    x = rng.randn(32, D).astype(np.float32)
    params = (rng.randn(D, EXPERTS) * 0.3).astype(np.float32)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        data = fluid.layers.data(name="x", shape=[D], dtype="float32")
        fetch = list(_nn.moe_router(
            data, EXPERTS, 4, param_attr=fluid.ParamAttr(name="router"),
            score_func="sigmoid", norm_eps=eps))
        fetch += _nn.moe_router(
            data, EXPERTS, 4, param_attr=fluid.ParamAttr(name="router"),
            score_func="sigmoid")
    first, second = [op for op in main.global_block().ops
                     if op.type == "moe_router"]
    assert first.attr("norm_eps") == eps
    assert "norm_eps" not in second.desc.attrs
    exe, scope = fluid.Executor(), fluid.Scope()
    exe.run(startup, scope=scope)
    scope.set("router", jnp.asarray(params))
    weight, ids, plain, plain_ids = exe.run(main, feed={"x": x},
                                            fetch_list=fetch, scope=scope)
    exe.close()
    scores = 1.0 / (1.0 + np.exp(-(x.astype(np.float64) @ params)))
    want_ids = np.argsort(-scores, axis=1, kind="stable")[:, :4]
    top = np.take_along_axis(scores, want_ids, 1)
    np.testing.assert_array_equal(ids, want_ids)
    np.testing.assert_array_equal(plain_ids, want_ids)
    np.testing.assert_allclose(weight, top / (top.sum(1, keepdims=True) + eps),
                               rtol=2e-5)
    np.testing.assert_allclose(plain, top / top.sum(1, keepdims=True),
                               rtol=2e-5)
    np.testing.assert_allclose(plain.sum(1), 1.0, rtol=1e-5)
    if eps > 0.1:
        assert (weight.sum(1) < 0.95).all()


def _lfm2_share(x, params, held, offset, bias):
    """The routed part (experts ``offset .. offset + held`` of 16, 4 a
    token, epsilon 1e-6, a bias) of the program's expert layer."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        data = fluid.layers.data(name="x", shape=[D], dtype="float32")
        weight, ids, _, _ = _nn.moe_router(
            data, 16, 4, param_attr=fluid.ParamAttr(name="router"),
            score_func="sigmoid", bias_name="bias", norm_eps=1e-6)
        routed, _ = _nn.moe_experts(
            data, weight, ids, held, offset, WIDTH,
            gate_attr=fluid.ParamAttr(name="gate"),
            up_attr=fluid.ParamAttr(name="up"),
            down_attr=fluid.ParamAttr(name="down"))
    exe, scope = fluid.Executor(), fluid.Scope()
    exe.run(startup, scope=scope)
    scope.set("router", jnp.asarray(params["router"]))
    scope.set("bias", jnp.asarray(bias))
    for name in ("gate", "up", "down"):
        scope.set(name, jnp.asarray(params[name][offset:offset + held]))
    (got,) = exe.run(main, feed={"x": x}, fetch_list=[routed], scope=scope)
    exe.close()
    return got


def test_the_eight_shares_add_up_to_the_uncut_layer_of_the_lfm2_reference():
    """16 experts in 8 shares of 2, 4 a token, no shared expert, a bias
    that moves the choice: the eight shares' routed parts add up to what
    ``lfm2_moe.experts`` gives for the whole layer (all 16 held), and each
    share is the reference's own share."""
    rng = np.random.RandomState(11)
    x = rng.randn(40, D).astype(np.float32)
    params = {
        "router": (rng.randn(D, 16) * 0.3).astype(np.float32),
        "gate": (rng.randn(16, D, WIDTH) * 0.3).astype(np.float32),
        "up": (rng.randn(16, D, WIDTH) * 0.3).astype(np.float32),
        "down": (rng.randn(16, WIDTH, D) * 0.3).astype(np.float32)}
    bias = (rng.randn(16) * 0.2).astype(np.float32)

    def reference(held, offset):
        m = {"num_experts_per_tok": 4, "experts_held": held,
             "expert_offset": offset, "route_scale": 1,
             "route_norm_eps": 1e-6}
        p = {"l.router": params["router"]}
        for name in ("gate", "up", "down"):
            p["l.experts_" + name] = params[name][offset:offset + held]
        with jax.default_matmul_precision("highest"):
            out, load = lfm2_moe.experts(
                common.Matmuls("f32"), m,
                {k: jnp.asarray(v) for k, v in p.items()}, "l.",
                jnp.asarray(x), jnp.asarray(bias))
        assert int(load.sum()) == x.shape[0] * 4
        return np.asarray(out)

    total = 0.0
    for offset in range(0, 16, 2):
        routed = _lfm2_share(x, params, 2, offset, bias)
        np.testing.assert_allclose(routed, reference(2, offset),
                                   atol=5e-5, rtol=2e-4)
        total = total + routed
    np.testing.assert_allclose(total, reference(16, 0), atol=1e-4, rtol=2e-4)


@pytest.mark.parametrize("config, digest", [
    ("mellum2_12b", "e8299d28f02cc179"), ("trinity_mini", "6be7094ce1eb717c")])
def test_the_accepted_decoder_steps_are_lowered_as_before(config, digest):
    """The ``mellum2_12b`` and ``trinity_mini`` steps (the configurations'
    own sizes, bf16, the CPU's path) trace to the jaxprs these digests were
    taken from: the jaxpr text, source positions struck. ``mellum2_12b``
    pinned at PR 32's commit (7296293) for PR 33's sake, whose builder
    arguments at their defaults had to leave it alone; **re-pinned by PR
    34**, which changes the step on purpose (``moe_expert_mlp`` under a
    ``custom_vjp`` of its own: the CPU's arithmetic is the same
    ``ragged_dot``s, the jaxpr is not). ``trinity_mini`` pinned at PR 34's
    commit (5dcbb94) for PR 35's sake (a ``conv`` layer kind, a tied head
    and the router's ``norm_eps``, all at their defaults here). **Both
    stood through PR 36**: the gate's kernels are chosen by
    ``gate_by_kernel``, which refuses the CPU, so this path still traces
    the gate over all rows and its ``jax.vjp`` equation for equation
    (``_gated`` moved beside the kernels as ``grouped_matmul.silu_gate``,
    whose body they share; the predicate and the counters are Python at
    trace time and leave nothing in the jaxpr). **Both re-pinned by PR
    38**, which changes every expert layer on every backend on purpose:
    the router's chosen scores by a compare over the outputs and the
    dispatch's weights as the payload of its sort (by ``key * R +
    pair``, no longer stable), each under a ``custom_vjp`` (from
    2b79d84d5b45a830 and 28455a677ee1aa16). A PR that means to leave
    these steps alone sees here whether it did."""
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           config + ".json")) as f:
        cfg = json.load(f)
    from paddle_tpu.core.types import convert_dtype_to_np
    from paddle_tpu.models import decoder_lm

    with fluid.unique_name.guard():
        main, _, handle = decoder_lm.get_model(batch_size=2, lr=1e-4,
                                               **cfg["model"])
    fluid.contrib.mixed_precision.enable_bf16(main)
    exe, block = fluid.Executor(), main.desc.block(0)
    seq = cfg["model"]["seq_len"]
    names, values = exe.engine._coerce_feed(block, {
        "ids": np.zeros((2, seq), np.int64),
        "labels": np.zeros((2, seq), np.int64)})
    step = exe.engine.get_compiled(main.desc, 0, names, values,
                                   [handle["loss"].name], False, True, True,
                                   1)

    def shape(name):
        var = block.find_var_recursive(name)
        return jax.ShapeDtypeStruct(tuple(var.shape),
                                    convert_dtype_to_np(var.dtype))

    text = str(jax.make_jaxpr(step.jitted)(
        [jax.ShapeDtypeStruct(v.shape, v.dtype) for v in values],
        [shape(n) for n in step.mutated_names],
        [shape(n) for n in step.readonly_names],
        (np.uint32(0), np.uint32(1))))
    text = re.sub(r" at 0x[0-9a-f]+", "", text)
    text = re.sub(r"\S+\.py:\d+", "F", text)
    exe.close()
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == digest
