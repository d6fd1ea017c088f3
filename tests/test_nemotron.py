"""The Nemotron-H tower (``nemotron_twotower_30b_a3b``): the Mamba-2 mixer's
ops (``ssm_conv1d``, ``ssd_scan``, ``gated_rms_norm``), the non-gated relu2
expert MLP, the builder's single-part layers, through ``Executor.run`` on the
CPU at small sizes, against the benchmark's plain reference
(``benchmarks/reference/nemotron_h.py``, which imports nothing of the
program and runs the scan position by position)."""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu.fluid as fluid
from paddle_tpu.layers import nn as _nn
from paddle_tpu.models import decoder_lm
from paddle_tpu.ops import moe_ops, ssm_ops

from benchmarks import compare
from benchmarks.drivers import train
from benchmarks.reference import common, nemotron_h

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "nemotron_twotower_30b_a3b.pretrain_b2"
SEED = 3900000007


def _cell():
    with open(os.path.join(ROOT, "benchmarks", "workloads",
                           CELL + ".json")) as f:
        workload = json.load(f)
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           workload["config"] + ".json")) as f:
        config = json.load(f)
    cfg, rows = train.sized(config, workload, rehearse=True)
    return workload, config, cfg, rows


# -- the scan ------------------------------------------------------------------

def _scan_args(rng, rows=2, t=19, heads=4, p=8, n=16, groups=2,
               dtype=np.float32):
    return (jnp.asarray(rng.randn(rows, t, heads, p), dtype),
            jnp.asarray(np.log1p(np.exp(rng.randn(rows, t, heads))),
                        jnp.float32),
            jnp.asarray(np.log(np.arange(1, heads + 1)) + 0.1
                        * rng.randn(heads), jnp.float32),
            jnp.asarray(rng.randn(rows, t, groups, n) * 0.5, dtype),
            jnp.asarray(rng.randn(rows, t, groups, n) * 0.5, dtype),
            jnp.asarray(rng.randn(heads), jnp.float32))


def _recurrence(x, dt, a_log, b, c, d):
    """The reference's scan, position by position, in float32."""
    return nemotron_h.ssm_scan(
        x.astype(jnp.float32), dt, -jnp.exp(a_log), b.astype(jnp.float32),
        c.astype(jnp.float32), d, block=4)


@pytest.mark.parametrize("t, chunk", [(19, 8), (16, 8), (5, 8), (19, 1)])
def test_the_chunked_scan_and_its_six_gradients_follow_the_recurrence(
        t, chunk):
    """H 4, P 8, N 16, G 2 in float32: the op's chunked form (two whole
    chunks and a part, whole chunks, less than one chunk, chunks of one)
    and its hand-written backward against the per-position recurrence and
    ``jax.vjp`` of it: y and the gradients of x, Δ, A_log, B, C and D."""
    rng = np.random.RandomState(t + chunk)
    args = _scan_args(rng, t=t)
    gy = jnp.asarray(rng.randn(*args[0].shape), jnp.float32)
    with jax.default_matmul_precision("highest"):
        y, back = jax.vjp(lambda *a: ssm_ops._ssd_scan(*a, chunk), *args)
        want, want_back = jax.vjp(_recurrence, *args)
        np.testing.assert_allclose(y, want, atol=2e-5, rtol=2e-5)
        for name, got, ref in zip(("x", "dt", "A_log", "B", "C", "D"),
                                  back(gy), want_back(gy)):
            assert got.shape == ref.shape, name
            np.testing.assert_allclose(got, ref, atol=5e-5 * np.abs(
                ref).max(), rtol=1e-4, err_msg=name)


def test_the_scan_in_bf16_keeps_its_states_in_float32():
    """bf16 x, B and C in, bf16 y out, within bf16's rounding of the float32
    recurrence; a longer sequence at chunks of 8 (16 states carried) does
    not drift from it."""
    rng = np.random.RandomState(3)
    args = _scan_args(rng, t=128, dtype=jnp.bfloat16)
    with jax.default_matmul_precision("highest"):
        y = ssm_ops._ssd_scan(*args, 8)
        want = _recurrence(*args)
    assert y.dtype == jnp.bfloat16
    err = np.abs(np.asarray(y, np.float32) - np.asarray(want))
    assert err.max() <= 3e-2 * np.abs(np.asarray(want)).max()


def test_the_scan_is_causal_and_starts_from_a_zero_state():
    """A change at position 10 leaves y before it alone, and with x = 0 up
    to a position, y is 0 there whatever B, C and Δ are."""
    rng = np.random.RandomState(4)
    x, dt, a_log, b, c, d = _scan_args(rng, t=19)
    y = ssm_ops._ssd_scan(x, dt, a_log, b, c, d, 8)
    moved = ssm_ops._ssd_scan(x.at[:, 10].add(1.0), dt, a_log, b, c, d, 8)
    np.testing.assert_array_equal(moved[:, :10], y[:, :10])
    assert np.abs(np.asarray(moved[:, 10:] - y[:, 10:])).max() > 0
    quiet = ssm_ops._ssd_scan(x.at[:, :12].set(0.0), dt, a_log, b, c, d, 8)
    assert np.all(np.asarray(quiet[:, :12]) == 0)


def test_the_scan_takes_its_decays_in_float32_from_a_bf16_projection():
    """Under AMP the projection's dt comes in bf16: the op adds dt_bias
    and takes the softplus in float32 (dt_bias near -4.6, where a bf16
    sum is 0.03 off, Δ 1.5%), so y is the float32 Δ's and not the one
    rounded to bf16."""
    from types import SimpleNamespace

    from paddle_tpu.core.registry import amp_scope

    rng = np.random.RandomState(5)
    x, _, a_log, b, c, d = _scan_args(rng, t=19, dtype=jnp.bfloat16)
    dt = jnp.asarray(0.3 * rng.randn(2, 19, 4), jnp.bfloat16)
    bias = jnp.asarray([-4.6, -4.55, -3.1, -2.3], jnp.float32)
    ctx = SimpleNamespace(op=SimpleNamespace(type="ssd_scan"), executor=None)
    with amp_scope(True):
        y = ssm_ops.ssd_scan(ctx, {
            "X": [x], "Dt": [dt], "DtBias": [bias], "ALog": [a_log],
            "B": [b], "C": [c], "D": [d]}, {"chunk_size": 8})["Out"][0]
    delta = jax.nn.softplus(dt.astype(jnp.float32) + bias)
    np.testing.assert_array_equal(
        y, ssm_ops._ssd_scan(x, delta, a_log, b, c, d, 8))
    rounded = jax.nn.softplus(dt + bias.astype(jnp.bfloat16))
    assert np.abs(np.asarray(rounded, np.float32) / delta - 1).max() > 5e-3


# -- the conv and the gated norm -------------------------------------------------

def _program(build, feeds, params, fetch):
    """Run ``build(*data) -> out`` through Executor.run with its parameters
    set to ``params`` -> (out, the gradients of sum(out * g) for each feed
    and parameter named in ``fetch``)."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        data = [fluid.layers.data(name=n, shape=list(v.shape[1:]),
                                  dtype="float32") for n, v in feeds.items()]
        for var in data:
            var.stop_gradient = False
        out = build(*data)
        g = fluid.layers.data(name="g", shape=list(out.shape[1:]),
                              dtype="float32")
        fluid.backward.append_backward(fluid.layers.reduce_sum(
            fluid.layers.elementwise_mul(out, g)))
    exe, scope = fluid.Executor(), fluid.Scope()
    exe.run(startup, scope=scope)
    for name, value in params.items():
        scope.set(name, jnp.asarray(value))
    rng = np.random.RandomState(9)
    gv = rng.randn(*next(iter(feeds.values())).shape[:1],
                   *out.shape[1:]).astype(np.float32)
    got = exe.run(main, feed=dict(feeds, g=gv), scope=scope,
                  fetch_list=[out] + [n + "@GRAD" for n in fetch])
    exe.close()
    return got[0], got[1:], gv


@pytest.mark.parametrize("taps", [4, 2])
def test_ssm_conv1d_and_its_gradients_follow_the_reference(taps):
    """silu(bias + causal depthwise conv) through Executor.run against the
    reference's ``causal_conv`` and ``jax.vjp`` of it: the output and the
    gradients of the input, the filter and the bias."""
    rng = np.random.RandomState(taps)
    x = rng.randn(2, 11, 6).astype(np.float32)
    w = rng.randn(6, taps).astype(np.float32)
    b = rng.randn(6).astype(np.float32)
    (out, grads, g) = _program(
        lambda d: _nn.ssm_conv1d(d, taps, fluid.ParamAttr(name="w"),
                                 fluid.ParamAttr(name="b")),
        {"x": x}, {"w": w, "b": b}, ["x", "w", "b"])
    with jax.default_matmul_precision("highest"):
        want, back = jax.vjp(nemotron_h.causal_conv, x, w, b)
    np.testing.assert_allclose(out, want, atol=1e-5, rtol=1e-5)
    for got, ref in zip(grads, back(jnp.asarray(g))):
        np.testing.assert_allclose(got, ref, atol=1e-4, rtol=1e-4)
    # causal: a change at position 5 moves nothing before it
    moved = np.asarray(ssm_ops._ssm_conv1d(x + (np.arange(11) == 5)[
        None, :, None], w, b))
    still = np.asarray(ssm_ops._ssm_conv1d(x, w, b))
    np.testing.assert_array_equal(moved[:, :5], still[:, :5])
    assert np.abs(moved[:, 5:5 + taps] - still[:, 5:5 + taps]).min() > 0


def _numpy_gated_norm(y, z, scale, groups, eps):
    gated = y * (z / (1.0 + np.exp(-z)))
    parts = gated.reshape(gated.shape[:-1] + (groups, -1))
    parts = parts / np.sqrt(np.mean(parts ** 2, -1, keepdims=True) + eps)
    return parts.reshape(gated.shape) * scale


@pytest.mark.parametrize("groups", [1, 4])
def test_gated_rms_norm_and_its_gradients_follow_the_reference(groups):
    """RMS over each group of y * silu(z), times the scale: the output
    against numpy, the gradients of y, z and the scale against ``jax.vjp``
    of the reference mixer's arithmetic."""
    rng = np.random.RandomState(groups)
    y = rng.randn(2, 5, 16).astype(np.float32)
    z = rng.randn(2, 5, 16).astype(np.float32)
    scale = rng.randn(16).astype(np.float32)
    out, grads, g = _program(
        lambda a, b: _nn.gated_rms_norm(a, b, groups, 1e-5,
                                        fluid.ParamAttr(name="s")),
        {"y": y, "z": z}, {"s": scale}, ["y", "z", "s"])
    np.testing.assert_allclose(out, _numpy_gated_norm(y, z, scale, groups,
                                                      1e-5), atol=1e-5,
                               rtol=1e-5)

    def plain(y_, z_, s_):
        gated = (y_ * jax.nn.silu(z_)).reshape(2, 5, groups, -1)
        gated = gated * jax.lax.rsqrt(jnp.mean(gated * gated, -1,
                                               keepdims=True) + 1e-5)
        return gated.reshape(2, 5, 16) * s_

    for got, ref in zip(grads, jax.vjp(plain, y, z, scale)[1](g)):
        np.testing.assert_allclose(got, ref, atol=1e-4, rtol=1e-4)


# -- the relu2 expert MLP ---------------------------------------------------------

def _relu2_case(dtype, rows=256, d=128, width=128):
    rng = np.random.RandomState(6)
    counts = jnp.asarray([40, 0, 77, 19], jnp.int32)
    live = (np.arange(rows) < int(counts.sum()))[:, None]
    args = (jnp.asarray(rng.randn(rows, d), dtype),
            jnp.asarray(rng.rand(rows), jnp.float32),
            jnp.asarray(rng.randn(4, d, width) * 0.1, jnp.float32),
            jnp.asarray(rng.randn(4, width, d) * 0.1, jnp.float32))
    return args, counts, live, jnp.asarray(rng.randn(rows, d), jnp.float32)


def _dense_relu2(rows, row_weight, counts, wu, wd):
    """Each row's expert by a loop over the experts: row r of expert e is
    w_r * relu(x_r Wup_e)^2 Wdown_e, rows of no expert 0."""
    ends = np.cumsum(np.asarray(counts))
    expert = np.searchsorted(ends, np.arange(rows.shape[0]), side="right")
    x = rows.astype(jnp.float32)
    out = jnp.zeros(x.shape, jnp.float32)
    for e in range(wu.shape[0]):
        mine = (expert == e)[:, None]
        part = jnp.square(jax.nn.relu(x @ wu[e])) * row_weight[:, None]
        out = out + jnp.where(mine, part @ wd[e], 0.0)
    return out


@pytest.mark.parametrize("form", ["xla", "kernels"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_relu2_expert_mlp_follows_a_dense_loop_over_the_experts(
        dtype, form):
    """``moe_expert_mlp``'s relu2 form (two grouped products forward, four
    backward, ``jax.vjp`` of the gate between) against a dense loop over
    the experts and ``jax.grad`` of it, on the ``ragged_dot`` path and with
    the grouped-matmul kernels in interpret mode: the output over the live
    rows and the gradients of the rows, their weights and both weights."""
    args, counts, live, target = _relu2_case(dtype)

    def loss(fn):
        def of(rows_, weight_, wu, wd):
            out = fn(rows_, weight_, wu, wd)
            return jnp.sum(jnp.where(live, out.astype(jnp.float32), 0)
                           * target)
        return jax.value_and_grad(of, argnums=(0, 1, 2, 3))

    def own(rows_, weight_, wu, wd):
        return moe_ops._relu2_expert_mlp(
            rows_, weight_, counts, wu.astype(rows_.dtype),
            wd.astype(rows_.dtype), form == "kernels")

    with jax.default_matmul_precision("highest"):
        value, got = loss(own)(*args)
        wanted, want = loss(lambda r, w, u, dn: _dense_relu2(
            r, w, counts, u, dn))(*args)
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(float(value), float(wanted), rtol=tol,
                               atol=tol)
    for name, a, b, keep in zip(("rows", "row weights", "up", "down"), got,
                                want, (live, live[:, 0], True, True)):
        a = np.where(keep, np.asarray(a, np.float32), 0)
        b = np.where(keep, np.asarray(b, np.float32), 0)
        assert np.abs(a - b).max() <= tol * max(np.abs(b).max(), 1e-6), name
    assert [g.dtype for g in got] == [dtype] + [jnp.float32] * 3


D, WIDTH, SHARED, EXPERTS, TOP = 32, 16, 24, 32, 6


def _share(x, params, held, offset, bias):
    """The routed part (experts ``offset .. offset + held`` of 32, 6 a
    token, sigmoid x 2.5, a bias) and the relu2 shared expert of the
    program's expert layer."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        data = fluid.layers.data(name="x", shape=[D], dtype="float32")
        weight, ids, _, _ = _nn.moe_router(
            data, EXPERTS, TOP, param_attr=fluid.ParamAttr(name="router"),
            score_func="sigmoid", route_scale=2.5, bias_name="bias")
        routed, _ = _nn.moe_experts(
            data, weight, ids, held, offset, WIDTH,
            up_attr=fluid.ParamAttr(name="up"),
            down_attr=fluid.ParamAttr(name="down"), activation="relu2")
        shared = fluid.layers.reshape(decoder_lm.relu2_mlp(
            fluid.layers.reshape(data, shape=[1, -1, D]), "shared_", SHARED),
            shape=[-1, D])
    exe, scope = fluid.Executor(), fluid.Scope()
    exe.run(startup, scope=scope)
    for name in ("router", "bias", "shared_up", "shared_down"):
        scope.set(name, jnp.asarray(params[name] if name != "bias"
                                    else bias))
    for name in ("up", "down"):
        scope.set(name, jnp.asarray(params[name][offset:offset + held]))
    got = exe.run(main, feed={"x": x}, fetch_list=[routed, shared],
                  scope=scope)
    exe.close()
    return got


def test_the_four_shares_and_the_shared_expert_add_up_to_the_uncut_layer():
    """32 experts in 4 shares of 8, 6 a token, sigmoid x 2.5, a bias that
    moves the choice: the four shares' routed parts and the shared expert
    counted ONCE add up to what ``nemotron_h.experts`` gives for the whole
    layer (all 32 held), and each share with the shared expert is the
    reference's own share."""
    rng = np.random.RandomState(12)
    x = rng.randn(40, D).astype(np.float32)
    params = {
        "router": (rng.randn(D, EXPERTS) * 0.3).astype(np.float32),
        "up": (rng.randn(EXPERTS, D, WIDTH) * 0.3).astype(np.float32),
        "down": (rng.randn(EXPERTS, WIDTH, D) * 0.3).astype(np.float32),
        "shared_up": (rng.randn(D, SHARED) * 0.3).astype(np.float32),
        "shared_down": (rng.randn(SHARED, D) * 0.3).astype(np.float32)}
    bias = (rng.randn(EXPERTS) * 0.2).astype(np.float32)

    def reference(held, offset):
        m = {"num_experts_per_tok": TOP, "experts_held": held,
             "expert_offset": offset, "route_scale": 2.5}
        p = {"l.router": params["router"],
             "l.shared_up": params["shared_up"],
             "l.shared_down": params["shared_down"],
             "l.experts_up": params["up"][offset:offset + held],
             "l.experts_down": params["down"][offset:offset + held]}
        with jax.default_matmul_precision("highest"):
            out, load = nemotron_h.experts(
                common.Matmuls("f32"), m,
                {k: jnp.asarray(v) for k, v in p.items()}, "l.",
                jnp.asarray(x), jnp.asarray(bias))
        assert int(load.sum()) == x.shape[0] * TOP
        return np.asarray(out)

    total = 0.0
    for offset in range(0, EXPERTS, 8):
        routed, shared = _share(x, params, 8, offset, bias)
        np.testing.assert_allclose(routed + shared, reference(8, offset),
                                   atol=5e-5, rtol=2e-4)
        total = total + routed
    np.testing.assert_allclose(total + shared, reference(EXPERTS, 0),
                               atol=1e-4, rtol=2e-4)


# -- the whole model at the rehearsal size --------------------------------------

@pytest.fixture(scope="module")
def nemotron():
    """The cell's program at the rehearsal size (chunks of 24 over 64
    positions: two whole chunks and a part), built and driven through its
    first three steps once by ``train.Trainer``, with the metrics flag up."""
    from paddle_tpu import observability as obs

    workload, config, cfg, rows = _cell()
    names = ("ssm.layers", "ssm.scan_chunks", "moe.relu2_layers",
             "decoder.single_part_layers", "moe.shared_experts",
             "attn.unrotated_layers", "rope.rotations", "moe.router_sigmoid",
             "moe.bias_updates", "ssm.scan_kernel", "ssm.scan_xla",
             "ssm.gated_norm_kernel", "ssm.gated_norm_xla")
    obs.set_enabled(True)
    before = {name: obs.counter_value(name) for name in names}
    with fluid.unique_name.guard():
        trainer = train.Trainer(cfg, rows, workload, rehearse=True)
    trainer.start(SEED)
    program, _ = trainer.warm_up()
    jax.effects_barrier()
    counted = {name: obs.counter_value(name) - was
               for name, was in before.items()}
    yield dict(workload=workload, config=config, cfg=cfg, rows=rows,
               trainer=trainer, program=program, counted=counted)
    trainer.exe.close()


def test_the_program_follows_its_reference(nemotron):
    """get_model with the pattern -> enable_bf16 -> Executor.run against
    the float32 reference (its scan position by position) through three
    Adam steps, under the cell's rehearsal limits: losses, every leaf's
    first gradient (A_log, D, dt_bias, the conv's among them) and change,
    the bias state's."""
    cfg, trainer = nemotron["cfg"], nemotron["trainer"]
    reference = common.follow(nemotron_h, cfg, nemotron["rows"], SEED)
    correct, compared = compare.judge(
        nemotron["program"], reference,
        train.limits(nemotron["workload"], rehearse=True))
    assert correct, compared
    assert compared["loss_gap"]["value"] < 1e-3
    kinds = [op.type for op in trainer.main.desc.global_block().ops]
    for op_type, count in (("ssm_conv1d", 3), ("ssd_scan", 3),
                           ("gated_rms_norm", 3), ("ssd_scan_grad", 3),
                           ("moe_router", 3), ("moe_bias_update", 3),
                           ("fused_attention", 1), ("rotary_embedding", 0),
                           ("softplus", 0),
                           ("gated_mlp", 0), ("rms_norm", 8)):
        assert kinds.count(op_type) == count, op_type
    # the mixers' own leaves, each within 5% of the median leaf's gradient
    # (A_log's is a sum with cancellations, 8% off its own in bf16; the
    # float32 program reads every leaf within 1e-5 of the reference)
    grads = reference["grad_norms"]
    median = float(np.median(list(grads.values())))
    for layer in (0, 2, 4):
        for leaf in ("A_log", "D", "dt_bias", "conv_filter", "conv_bias",
                     "mixer_norm", "in_proj", "out_proj"):
            name = "layer%d.%s" % (layer, leaf)
            assert grads[name] > 0, name
            assert abs(nemotron["program"]["grad_norms"][name]
                       - grads[name]) <= 0.05 * max(grads[name], median), name
    for name in sorted(nemotron_h.state_specs(cfg)):
        assert reference["change_norms"][name] > 0
        bias = np.asarray(trainer.scope.get(name))
        assert abs(bias.sum()) < 1e-6 and np.abs(bias).max() <= 6.1e-3


def test_the_float32_program_is_the_reference():
    """The same program without ``enable_bf16``: every number of the
    comparison within 1e-4 of the float32 reference, so that what bf16
    moves is rounding and not a different model."""
    workload, config, _, _ = _cell()
    config = dict(config, program=dict(config["program"], amp=None))
    cfg, rows = train.sized(config, workload, rehearse=True)
    with fluid.unique_name.guard():
        trainer = train.Trainer(cfg, rows, workload, rehearse=True)
    trainer.start(SEED)
    program, _ = trainer.warm_up()
    trainer.exe.close()
    _, compared = compare.judge(program, common.follow(
        nemotron_h, cfg, rows, SEED), {})
    for name, number in compared.items():
        assert number["value"] < 1e-4, (name, number)


def test_the_layers_are_single_parts_in_the_published_order(nemotron):
    """Layers 0-6 of the pattern, M E M E M * E: one norm each, a mixer
    or an expert layer or attention alone; the attention is 4 query heads
    on 2 key/value heads here (32 on 2 in the cell), unrotated."""
    names = {p.name for p in nemotron["trainer"].main.all_parameters()}
    assert nemotron["cfg"]["model"]["hybrid_override_pattern"][:7] == \
        "MEMEM*E"
    for i, kind in enumerate("MEMEM*E"):
        prefix = "layer%d." % i
        assert prefix + "norm" in names
        assert (prefix + "in_proj" in names) == (kind == "M")
        assert (prefix + "experts_up" in names) == (kind == "E")
        assert (prefix + "q_proj" in names) == (kind == "*")
        assert not {prefix + "attn_norm", prefix + "mlp_norm",
                    prefix + "experts_gate"} & names
    assert "lm_head" in names and "tok_embedding" in names


def test_the_program_counts_what_it_was_built_with(nemotron):
    """3 Mamba layers of 3 chunks a row (64 positions at chunks of 24), 3
    relu2 expert layers with a shared expert each, 7 single-part layers,
    one unrotated attention; on the CPU the three scans in the plain form
    both ways and the three gated norms in XLA's (6 / 0 and 3 / 0 with the
    kernels in the cell's step for the TPU: ``tests/test_tpu_compile.py``;
    the rehearsal's groups are 32 wide, no lane tile, so even there the
    norm would keep XLA's form)."""
    assert nemotron["counted"] == {
        "ssm.layers": 3, "ssm.scan_chunks": 9, "ssm.scan_kernel": 0,
        "ssm.scan_xla": 6, "ssm.gated_norm_kernel": 0,
        "ssm.gated_norm_xla": 3, "moe.relu2_layers": 3,
        "decoder.single_part_layers": 7, "moe.shared_experts": 3,
        "attn.unrotated_layers": 1, "rope.rotations": 0,
        "moe.router_sigmoid": 3, "moe.bias_updates": 3}


def test_the_configuration_keeps_every_published_width():
    """The file holds the catalog's config with only the three keys of
    ``reduced`` changed, and the builder's sizes are the published ones."""
    _, config, _, _ = _cell()
    m = config["model"]
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                 "vocab_size"]
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"]) == (7, 8, 16384)
    assert config["published"]["num_hidden_layers"] == 52
    for key, value in (("hidden_size", 2688), ("mamba_num_heads", 64),
                       ("mamba_head_dim", 64), ("ssm_state_size", 128),
                       ("n_groups", 8), ("conv_kernel", 4),
                       ("chunk_size", 128), ("num_attention_heads", 32),
                       ("num_key_value_heads", 2), ("head_dim", 128),
                       ("moe_intermediate_size", 1856),
                       ("num_experts_per_tok", 6)):
        assert config[key] == m[key] == value, key
    assert m["shared_expert_intermediate_size"] == config[
        "moe_shared_expert_intermediate_size"] == 3712
    assert m["router_experts"] == 128 and m["route_scale"] == 2.5
    assert m["moe_activation"] == config["mlp_hidden_act"] == "relu2"
    for key in ("deployment", "assumed", "not_modelled", "precision"):
        assert config[key]
    held = sum(int(np.prod(shape)) for shape, _ in
               nemotron_h.param_specs(dict(config, traffic={})).values())
    assert held == pytest.approx(528.1e6, rel=1e-3)


def test_the_published_initialisation_of_the_state_space_leaves():
    """From the seed: A_log = log(1..64), D = 1, softplus(dt_bias) within
    [1e-3, 0.1] and spread over it, the conv within +-1/2, out_proj within
    +-4096^-1/2 / sqrt(52)."""
    _, config, _, _ = _cell()
    specs = nemotron_h.param_specs(config)
    small = {n: s for n, s in specs.items() if n.startswith("layer0.")
             and not n.endswith("proj")}
    small["layer0.out_proj"] = specs["layer0.out_proj"]
    got = common.init_params(small, SEED)
    np.testing.assert_allclose(got["layer0.A_log"], np.log(np.arange(1, 65)),
                               rtol=1e-6)
    assert np.all(np.asarray(got["layer0.D"]) == 1.0)
    delta = np.asarray(jax.nn.softplus(got["layer0.dt_bias"]))
    assert delta.min() >= 1e-3 * 0.999 and delta.max() <= 0.1 * 1.001
    assert np.log(delta).std() > 0.5      # log-uniform: not one value
    assert np.abs(np.asarray(got["layer0.conv_filter"])).max() <= 0.5
    bound = 4096 ** -0.5 / np.sqrt(52)
    out = np.asarray(got["layer0.out_proj"])
    assert np.abs(out).max() <= bound and np.abs(out).max() > 0.9 * bound
