"""JoyAI-LLM-Flash (``joyai_llm_flash``): the flash kernels at a q.k width
that is not the values' (192 / 128), the rotation by interleaved pairs,
latent attention (``latent_qkv``) and the multi-token-prediction module
of the builder, through ``Executor.run`` on the CPU at small sizes,
against the benchmark's plain reference
(``benchmarks/reference/joyai_llm_flash.py``, which imports nothing of the
program and rotates in the published de-interleaved form)."""

import importlib
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu.fluid as fluid
from paddle_tpu.layers import nn as _nn
from paddle_tpu.models import decoder_lm
from paddle_tpu.ops import nn_ops

from benchmarks import compare
from benchmarks.drivers import train
from benchmarks.reference import common, joyai_llm_flash

# the module, not the function ``paddle_tpu.kernels`` exports by its name
fa = importlib.import_module("paddle_tpu.kernels.flash_attention")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "joyai_llm_flash.pretrain_b1"
SEED = 4200000007


def _cell(amp="bf16"):
    with open(os.path.join(ROOT, "benchmarks", "workloads",
                           CELL + ".json")) as f:
        workload = json.load(f)
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           workload["config"] + ".json")) as f:
        config = json.load(f)
    config = dict(config, program=dict(config["program"], amp=amp))
    cfg, rows = train.sized(config, workload, rehearse=True)
    return workload, config, cfg, rows


# -- the flash kernels at a q.k width of 192 and values of 128 ------------------

def _qkv(rng, b=1, h=2, t=256, dqk=192, dv=128, dtype=np.float32):
    return tuple(jnp.asarray(rng.randn(b, h, t, d) * s, dtype)
                 for d, s in ((dqk, 0.5), (dqk, 0.5), (dv, 1.0)))


def _plain(q, k, v, causal):
    """softmax(q k^T / sqrt(Dqk)) v in float32, by jax.numpy alone."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   precision=jax.lax.Precision.HIGHEST) * q.shape[-1] ** -0.5
    if causal:
        t = q.shape[2]
        s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v,
                      precision=jax.lax.Precision.HIGHEST)


@pytest.mark.parametrize("causal", [True, False])
def test_the_forward_kernel_takes_values_of_their_own_width(causal):
    """Interpret mode, [1, 2, 256, 192] q and k, [1, 2, 256, 128] v: the
    output is 128 wide and is ``_xla_attention_lse``'s and the plain
    softmax's, and so is the logsumexp."""
    q, k, v = _qkv(np.random.RandomState(1))
    out, lse = fa.flash_attention_lse(q, k, v, None, None, 0, causal, None,
                                      0.0, 128, 128, True)
    want, want_lse = fa._xla_attention_lse(q, k, v, causal,
                                           q.shape[-1] ** -0.5)
    assert out.shape == v.shape
    np.testing.assert_allclose(out, want, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(lse, want_lse, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(out, _plain(q, k, v, causal), atol=2e-5,
                               rtol=2e-5)


@pytest.mark.parametrize("form", ["fused", "split"])
def test_both_backward_forms_take_values_of_their_own_width(monkeypatch,
                                                            form):
    """The one-kernel backward and the dQ + dK/dV pair at 192 / 128,
    causal: dQ and dK 192 wide, dV 128, each as autodiff of the plain
    softmax and of ``_xla_attention_lse`` gives them."""
    if form == "split":
        monkeypatch.setattr(fa, "_bwd_fused_fits", lambda *a: False)
    rng = np.random.RandomState(2)
    q, k, v = _qkv(rng)
    g = jnp.asarray(rng.randn(*v.shape), jnp.float32)

    def kernels(q_, k_, v_):
        return fa.flash_attention_lse(q_, k_, v_, None, None, 0, True, None,
                                      0.0, 128, 128, True)[0]

    got = jax.vjp(kernels, q, k, v)[1](g)
    plain = jax.vjp(lambda *a: _plain(*a, True), q, k, v)[1](g)
    xla = jax.vjp(lambda *a: fa._xla_attention_lse(
        *a, True, q.shape[-1] ** -0.5)[0], q, k, v)[1](g)
    for name, a, b, c in zip("qkv", got, plain, xla):
        assert a.shape == (q if name != "v" else v).shape, name
        np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-5, err_msg=name)
        np.testing.assert_allclose(a, c, atol=5e-5, rtol=5e-5, err_msg=name)


def test_the_fused_backward_reckons_each_width_where_it_applies():
    """``_bwd_fused_fits`` at equal widths is what it was; at q.k 192 /
    v 128 it reckons more than at 128 / 128 and less than at 192 / 192,
    and the cell's 4096 positions in bf16 still fit the one kernel."""
    args = (4096, 128, jnp.bfloat16, 1024, 1024)
    assert fa._bwd_fused_fits(*args) == fa._bwd_fused_fits(*args, dv=128)
    assert fa._bwd_fused_fits(4096, 192, jnp.bfloat16, 1024, 1024, dv=128)
    # at 12288 positions the dQ accumulator grows past the budget at 192
    # wide whatever V's width
    assert not fa._bwd_fused_fits(12288, 192, jnp.bfloat16, 1024, 1024,
                                  dv=128)


# -- the rotation by interleaved pairs ------------------------------------------

def test_the_interleaved_rotation_gives_the_published_forms_q_dot_k():
    """``rotary_embedding`` with ``interleaved`` on q and k [1, 2, 32, 64]
    at theta 3.2e7 against the reference's published form (de-interleave,
    then rotate halves): the same q.k at every pair of positions, though
    the columns come out in another order; and each vector keeps its
    norm, pair by pair."""
    rng = np.random.RandomState(3)
    q = rng.randn(1, 2, 32, 64).astype(np.float32)
    k = rng.randn(1, 2, 32, 64).astype(np.float32)
    rotate = nn_ops._rotation(32, 64, jnp.float32, {
        "rope_theta": 3.2e7, "interleaved": True})
    got_q, got_k = rotate(jnp.asarray(q)), rotate(jnp.asarray(k))
    want_q, want_k = (joyai_llm_flash.rotate_published(
        jnp.asarray(x).transpose(0, 2, 1, 3), 3.2e7).transpose(0, 2, 1, 3)
        for x in (q, k))
    hi = jax.lax.Precision.HIGHEST
    np.testing.assert_allclose(
        jnp.einsum("bhqd,bhkd->bhqk", got_q, got_k, precision=hi),
        jnp.einsum("bhqd,bhkd->bhqk", want_q, want_k, precision=hi),
        atol=1e-4, rtol=1e-5)
    pairs = np.asarray(got_q).reshape(1, 2, 32, 32, 2)
    np.testing.assert_allclose(np.square(pairs).sum(-1),
                               np.square(q.reshape(pairs.shape)).sum(-1),
                               rtol=1e-5)
    # the halves convention is another rotation: it does not give this q.k
    halves = nn_ops._rotation(32, 64, jnp.float32, {"rope_theta": 3.2e7})
    assert not np.allclose(
        jnp.einsum("bhqd,bhkd->bhqk", halves(jnp.asarray(q)),
                   halves(jnp.asarray(k)), precision=hi),
        jnp.einsum("bhqd,bhkd->bhqk", want_q, want_k, precision=hi),
        atol=1e-2)


def test_the_rotary_op_counts_what_it_rotates_by_interleaved_pairs():
    """``latent_qkv`` is the op that rotates by interleaved pairs: the
    attribute is in its desc as the builder asks for it, and
    ``rope.interleaved`` counts its two rotated tensors (q_pe and the
    shared k_pe) in a lowered step where it is set, none where it is not;
    ``rotary_embedding`` (the halves) carries no such attribute."""
    from paddle_tpu import observability as obs

    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        cq = fluid.layers.data(name="cq", shape=[8, 12], dtype="float32")
        ckv = fluid.layers.data(name="ckv", shape=[8, 10], dtype="float32")
        kpe = fluid.layers.data(name="kpe", shape=[8, 4], dtype="float32")
        pairs = _nn.latent_qkv(cq, ckv, kpe, 2, 6, 4, 6, rope_theta=1e4,
                               q_b_attr=fluid.ParamAttr(name="wuq"),
                               kv_b_attr=fluid.ParamAttr(name="wukv"),
                               interleaved=True)
        halves = _nn.latent_qkv(cq, ckv, kpe, 2, 6, 4, 6, rope_theta=1e4,
                                q_b_attr=fluid.ParamAttr(name="wuq"),
                                kv_b_attr=fluid.ParamAttr(name="wukv"))
        plain = _nn.rotary_embedding([pairs[1]], rope_theta=1e4)
    ops = [op for op in main.desc.global_block().ops
           if op.type in ("latent_qkv", "rotary_embedding")]
    assert [(op.type, op.attrs.get("interleaved")) for op in ops] == [
        ("latent_qkv", True), ("latent_qkv", False),
        ("rotary_embedding", None)]
    obs.set_enabled(True)
    before = obs.counter_value("rope.interleaved")
    exe = fluid.Executor()
    exe.run(startup)
    rng = np.random.RandomState(4)
    got = exe.run(main, feed={
        "cq": rng.randn(1, 8, 12).astype(np.float32),
        "ckv": rng.randn(1, 8, 10).astype(np.float32),
        "kpe": rng.randn(1, 8, 4).astype(np.float32)},
        fetch_list=[pairs[0], halves[0]] + plain)
    exe.close()
    assert obs.counter_value("rope.interleaved") - before == 2
    # the same up-projection, rotated two ways: the non-rotary columns
    # agree, the rotary ones do not
    np.testing.assert_allclose(got[0][..., :6], got[1][..., :6])
    assert not np.allclose(got[0][..., 6:], got[1][..., 6:])


# -- the program at the rehearsal size ------------------------------------------

@pytest.fixture(scope="module")
def joyai():
    """The cell's program at the rehearsal size, bf16, built and driven
    through its first three steps once by ``train.Trainer`` (the flash
    kernels in interpret mode at q.k 24 / v 8), with the metrics flag
    up."""
    from paddle_tpu import observability as obs

    workload, config, cfg, rows = _cell()
    names = ("flash.split_v_calls", "rope.interleaved", "attn.mla_layers",
             "decoder.mtp_depth", "moe.bias_updates", "moe.shared_experts",
             "gated_mlp.calls")
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


def test_the_program_follows_its_reference(joyai):
    """get_model with ``mla`` and ``mtp`` -> enable_bf16 -> Executor.run
    against the float32 reference through three Adam steps, under the
    cell's rehearsal limits: losses, every leaf's first gradient (the MTP
    module's among them) and change, the bias states'."""
    cfg, trainer = joyai["cfg"], joyai["trainer"]
    reference = common.follow(joyai_llm_flash, cfg, joyai["rows"], SEED)
    correct, compared = compare.judge(
        joyai["program"], reference,
        train.limits(joyai["workload"], rehearse=True))
    assert correct, compared
    assert compared["loss_gap"]["value"] < 1e-3
    kinds = [op.type for op in trainer.main.desc.global_block().ops]
    for op_type, count in (("latent_qkv", 6), ("latent_qkv_grad", 6),
                           ("fused_attention", 6),
                           ("fused_attention_grad", 6),
                           ("rotary_embedding", 0), ("moe_router", 5),
                           ("moe_bias_update", 5), ("gated_mlp", 6),
                           ("lookup_table", 2)):
        assert kinds.count(op_type) == count, op_type
    grads = reference["grad_norms"]
    for name in ("mtp.eh_proj", "mtp.enorm", "mtp.hnorm", "mtp.q_b_proj",
                 "mtp.kv_b_proj", "mtp.router", "mtp.final_norm",
                 "layer0.kv_a_proj", "layer0.q_a_norm", "layer0.kv_a_norm"):
        assert grads[name] > 0, name
    for name in sorted(joyai_llm_flash.state_specs(cfg)):
        assert reference["change_norms"][name] > 0
        bias = np.asarray(trainer.scope.get(name))
        assert abs(bias.sum()) < 1e-6 and np.abs(bias).max() <= 6.1e-3


def test_the_float32_program_is_the_reference():
    """The same program without ``enable_bf16``: every number of the
    comparison within 1e-4 of the float32 reference, so that what bf16
    moves is rounding and not a different model."""
    workload, _, cfg, rows = _cell(amp=None)
    with fluid.unique_name.guard():
        trainer = train.Trainer(cfg, rows, workload, rehearse=True)
    trainer.start(SEED)
    program, _ = trainer.warm_up()
    trainer.exe.close()
    _, compared = compare.judge(program, common.follow(
        joyai_llm_flash, cfg, rows, SEED), {})
    for name, number in compared.items():
        assert number["value"] < 1e-4, (name, number)


def test_the_program_counts_what_it_was_built_with(joyai):
    """5 layers and the MTP module, each with latent attention: 6 flash
    forward and 6 backward calls whose widths differ (on the CPU's
    interpret path here; in the cell's step for the TPU too:
    ``tests/test_tpu_compile.py``), q_pe and k_pe rotated by interleaved
    pairs in each, one module of depth 1, 5 routers with their bias
    updates and shared experts (layer 0 is dense)."""
    assert joyai["counted"] == {
        "flash.split_v_calls": 12, "rope.interleaved": 12,
        "attn.mla_layers": 6, "decoder.mtp_depth": 1,
        "moe.bias_updates": 5, "moe.shared_experts": 5,
        "gated_mlp.calls": 6}


def test_the_mtp_module_predicts_two_ahead_and_leaves_the_last_out():
    """In the float32 program at the rehearsal size, with a second row:
    the module's targets are ``labels[:, 1:]`` (t_{i+2} for positions
    0..T-2), its logits T-1 a row, and its loss their mean cross-entropy;
    the program's loss is the next-token loss + 0.3 x that."""
    _, _, cfg, rows = _cell(amp=None)
    m = cfg["model"]
    with fluid.unique_name.guard():
        main, startup, handle = decoder_lm.get_model(
            batch_size=rows, lr=1e-4, is_train=False, **m)
    exe, scope = fluid.Executor(), fluid.Scope()
    exe.run(startup, scope=scope)
    batch = joyai_llm_flash.make_batch(cfg, rows, common.batch_rng(SEED, 0))
    mtp = handle["mtp"]
    loss, logits, targets, mtp_loss = exe.run(
        main, feed=batch, scope=scope,
        fetch_list=[handle["loss"], mtp["logits"], mtp["targets"],
                    mtp["loss"]])
    exe.close()
    t = m["seq_len"]
    assert logits.shape == (rows * (t - 1), m["vocab_size"])
    np.testing.assert_array_equal(targets, batch["labels"][:, 1:])
    np.testing.assert_array_equal(targets[:, :-1], batch["ids"][:, 2:])
    logp = jax.nn.log_softmax(jnp.asarray(logits), -1)
    ce = -np.take_along_axis(np.asarray(logp),
                             targets.reshape(-1, 1), -1).mean()
    np.testing.assert_allclose(mtp_loss, ce, rtol=1e-5)
    assert float(loss) > 0.3 * float(mtp_loss)


def test_the_configuration_keeps_every_published_width():
    """The file holds the catalog's config with only the three keys of
    ``reduced`` changed, and the builder's sizes are the published ones."""
    _, config, _, _ = _cell()
    m, a = config["model"], config["model"]["mla"]
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                 "vocab_size"]
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"]) == (5, 16, 16160)
    assert config["published"] == dict(config["published"],
                                       num_hidden_layers=40,
                                       n_routed_experts=256,
                                       vocab_size=129280)
    for key in ("q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
                "qk_rope_head_dim", "v_head_dim"):
        assert a[key] == config[key], key
    assert a["rope_interleave"] is config["rope_interleave"] is True
    assert config["qk_head_dim"] == a["qk_nope_head_dim"] + a[
        "qk_rope_head_dim"] == 192
    for key, value in (("hidden_size", 2048), ("num_attention_heads", 32),
                       ("moe_intermediate_size", 768),
                       ("intermediate_size", 7168),
                       ("num_experts_per_tok", 8)):
        assert config[key] == m[key] == value, key
    assert m["router_experts"] == 256 and m["experts_held"] == 16
    assert m["route_scale"] == config["routed_scaling_factor"] == 2.5
    assert m["rope_parameters"]["full_attention"]["rope_theta"] == config[
        "rope_theta"]
    assert m["rms_norm_eps"] == config["rms_norm_eps"]
    assert m["mlp_layer_types"][:config["first_k_dense_replace"]] == [
        "dense"]
    assert m["mtp"]["num_nextn_predict_layers"] == config[
        "num_nextn_predict_layers"] == 1
    for key in ("deployment", "assumed", "precision"):
        assert config[key]
    held = sum(int(np.prod(shape)) for shape, _ in
               joyai_llm_flash.param_specs(dict(config, traffic={})).values())
    assert held == pytest.approx(680.4e6, rel=1e-3)


# -- one chip's share of the expert layer ----------------------------------------

D, WIDTH, EXPERTS, TOP = 32, 16, 32, 8


def _share(x, params, held, offset, bias, capacity_factor=None):
    """The routed part (experts ``offset .. offset + held`` of 32, 8 a
    token, sigmoid x 2.5, a bias), the SwiGLU shared expert of the
    program's expert layer and the tokens each held expert took."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        data = fluid.layers.data(name="x", shape=[D], dtype="float32")
        weight, ids, _, _ = _nn.moe_router(
            data, EXPERTS, TOP, param_attr=fluid.ParamAttr(name="router"),
            score_func="sigmoid", route_scale=2.5, bias_name="bias")
        routed, counts = _nn.moe_experts(
            data, weight, ids, held, offset, WIDTH,
            gate_attr=fluid.ParamAttr(name="gate"),
            up_attr=fluid.ParamAttr(name="up"),
            down_attr=fluid.ParamAttr(name="down"),
            capacity_per_token=capacity_factor and (
                capacity_factor * TOP / EXPERTS))
        shared = decoder_lm.gated_mlp(data, "shared_", WIDTH)
    exe, scope = fluid.Executor(), fluid.Scope()
    exe.run(startup, scope=scope)
    for name in ("router", "bias", "shared_gate", "shared_up",
                 "shared_down"):
        scope.set(name, jnp.asarray(params[name] if name != "bias"
                                    else bias))
    for name in ("gate", "up", "down"):
        scope.set(name, jnp.asarray(params[name][offset:offset + held]))
    got = exe.run(main, feed={"x": x}, fetch_list=[routed, shared, counts],
                  scope=scope)
    exe.close()
    return got


def _layer_params(rng):
    params = {"router": (rng.randn(D, EXPERTS) * 0.3).astype(np.float32)}
    for name, shape in (("gate", (EXPERTS, D, WIDTH)),
                        ("up", (EXPERTS, D, WIDTH)),
                        ("down", (EXPERTS, WIDTH, D)),
                        ("shared_gate", (D, WIDTH)), ("shared_up", (D, WIDTH)),
                        ("shared_down", (WIDTH, D))):
        params[name] = (rng.randn(*shape) * 0.3).astype(np.float32)
    return params


def _reference_share(x, params, held, offset, bias, capacity_factor=None):
    """``joyai_llm_flash.experts`` over experts ``offset .. offset +
    held``: (its output, its loads)."""
    m = {"num_experts_per_tok": TOP, "experts_held": held,
         "expert_offset": offset, "route_scale": 2.5,
         "expert_capacity_factor": capacity_factor}
    p = {"l.router": params["router"]}
    for name in ("gate", "up", "down"):
        p["l.experts_" + name] = params[name][offset:offset + held]
        p["l.shared_" + name] = params["shared_" + name]
    with jax.default_matmul_precision("highest"):
        out, load = joyai_llm_flash.experts(
            common.Matmuls("f32"), m,
            {k: jnp.asarray(v) for k, v in p.items()}, "l.",
            jnp.asarray(x), jnp.asarray(bias))
    return np.asarray(out), np.asarray(load)


def test_the_sixteen_shares_and_the_shared_expert_add_up_to_the_uncut_layer():
    """32 experts in 16 shares of 2, 8 a token, sigmoid x 2.5, a bias that
    moves the choice: the shares' routed parts and the shared expert
    counted ONCE add up to what ``joyai_llm_flash.experts`` gives for the
    whole layer (all 32 held), and each share with the shared expert is
    the reference's own share."""
    rng = np.random.RandomState(12)
    x = rng.randn(40, D).astype(np.float32)
    params = _layer_params(rng)
    bias = (rng.randn(EXPERTS) * 0.2).astype(np.float32)

    def reference(held, offset):
        out, load = _reference_share(x, params, held, offset, bias)
        assert int(load.sum()) == x.shape[0] * TOP
        return out

    total = 0.0
    for offset in range(0, EXPERTS, 2):
        routed, shared, _ = _share(x, params, 2, offset, bias)
        np.testing.assert_allclose(routed + shared, reference(2, offset),
                                   atol=5e-5, rtol=2e-4)
        total = total + routed
    np.testing.assert_allclose(total + shared, reference(EXPERTS, 0),
                               atol=1e-4, rtol=2e-4)


def test_a_held_expert_takes_the_first_tokens_up_to_its_capacity():
    """40 tokens, 8 of 32 experts held, 8 a token, a bias of 1 that sends
    every token to the 8 held ones, capacity factor 1: ceil(40 x 8 / 32) =
    10 pairs a held expert. The program's counts stop at 10 of the 40 the
    router sends; its routed part is the reference's under the same
    capacity; the first 10 tokens get what they get without it and the
    other 30 nothing; the loads count every selection."""
    rng = np.random.RandomState(13)
    x = rng.randn(40, D).astype(np.float32)
    params = _layer_params(rng)
    bias = np.where(np.arange(EXPERTS) < 8, 1.0, 0.0).astype(np.float32)
    routed, shared, counts = _share(x, params, 8, 0, bias,
                                    capacity_factor=1.0)
    want, load = _reference_share(x, params, 8, 0, bias, capacity_factor=1.0)
    np.testing.assert_array_equal(counts, [10] * 8)
    np.testing.assert_array_equal(load, [40] * 8 + [0] * 24)
    np.testing.assert_allclose(routed + shared, want, atol=5e-5, rtol=2e-4)
    uncut, _, full = _share(x, params, 8, 0, bias)
    np.testing.assert_array_equal(full, [40] * 8)
    np.testing.assert_allclose(routed[:10], uncut[:10], atol=1e-6)
    assert not routed[10:].any() and np.abs(uncut[10:]).min() > 0
