"""The decoder language model (``models/decoder_lm.py``) and the ops it
brought — ``rms_norm``, ``rotary_embedding``, the four expert-layer ops —
through ``Executor.run`` on the CPU at small sizes, against the benchmark's
plain reference (``benchmarks/reference/mellum2.py``, which imports nothing
of the program)."""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu.fluid as fluid
from paddle_tpu.core.registry import LowerContext, OpRegistry
from paddle_tpu.layers import nn as _nn
from paddle_tpu.ops.nn_ops import rope_inv_freq

from benchmarks import compare
from benchmarks.drivers import train
from benchmarks.reference import common, mellum2

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "mellum2_12b.pretrain_s4096_b2"
YARN = {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
        "original_max_position_embeddings": 8192, "beta_fast": 32,
        "beta_slow": 1, "attention_factor": 1.2772588722239782}
DEFAULT = {"rope_type": "default", "rope_theta": 500000}


def _cell():
    with open(os.path.join(ROOT, "benchmarks", "workloads",
                           CELL + ".json")) as f:
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
