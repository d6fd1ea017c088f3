"""One chip's share of NVIDIA-Nemotron-3-Super-120B-A12B
(``nemotron3_super_120b_a12b``): LatentMoE expert layers (``moe_latent``
round the held relu² experts, 22 of 512 a token, held experts capped),
the Mamba-2 mixer and the attention as a share of their heads, and a
multi-token-prediction module of single-part ``*E`` layers, through
``Executor.run`` on the CPU at small sizes, against the benchmark's plain
reference (``benchmarks/reference/nemotron3_super.py``, which imports
nothing of the program)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu.fluid as fluid
from paddle_tpu.models import decoder_lm

from benchmarks import compare
from benchmarks.drivers import train
from benchmarks.reference import common, nemotron3_super, nemotron_h

import decoder_cells
from test_nemotron import _program

CELL = "nemotron3_super_120b_a12b.pretrain_b1"
SEED = 4700000007
COUNTERS = ("moe.latent_layers", "decoder.mtp_layers", "decoder.mtp_depth",
            "decoder.single_part_layers", "moe.relu2_layers", "moe.layers",
            "moe.shared_experts", "moe.bias_updates", "ssm.layers",
            "attn.unrotated_layers")


@pytest.fixture(scope="module")
def super_cell():
    """The cell's program at the rehearsal size (11 layers of the pattern
    and the ``*E`` module; 6 of 32 experts a token, 4 held, capped at their
    balanced share), bf16, driven through its first three steps once."""
    driven = decoder_cells.drive(CELL, SEED, COUNTERS, ("moe.buffer_rows",))
    yield driven
    driven["trainer"].exe.close()


@pytest.fixture(scope="module")
def reference():
    """The float32 reference through its first three steps from ``SEED`` at
    the rehearsal size, once for both comparisons."""
    _, _, cfg, rows = decoder_cells.cell(CELL)
    return common.follow(nemotron3_super, cfg, rows, SEED)


def test_the_program_follows_its_reference(super_cell, reference):
    """get_model with ``moe_latent_size`` and ``mtp_hybrid_override_pattern``
    -> enable_bf16 -> Executor.run against the float32 reference through
    three Adam steps, under the cell's rehearsal limits: losses, every
    leaf's first gradient (the latent projections' and the MTP block's
    among them) and change, the bias states'."""
    cfg, trainer = super_cell["cfg"], super_cell["trainer"]
    correct, compared = compare.judge(
        super_cell["program"], reference,
        train.limits(super_cell["workload"], rehearse=True))
    assert correct, compared
    assert compared["loss_gap"]["value"] < 1e-3
    kinds = [op.type for op in trainer.main.desc.global_block().ops]
    for op_type, count in (("moe_latent", 12), ("moe_latent_grad", 12),
                           ("moe_router", 6), ("moe_bias_update", 6),
                           ("moe_expert_mlp", 6), ("ssd_scan", 5),
                           ("fused_attention", 2), ("lookup_table", 2),
                           ("rotary_embedding", 0), ("gated_mlp", 0)):
        assert kinds.count(op_type) == count, op_type
    grads = reference["grad_norms"]
    for name in ("layer1.latent_down", "layer1.latent_up", "mtp.eh_proj",
                 "mtp.enorm", "mtp.hnorm", "mtp.0.q_proj", "mtp.0.norm",
                 "mtp.1.router", "mtp.1.latent_down", "mtp.1.experts_up",
                 "mtp.1.shared_up", "mtp.final_norm", "layer0.A_log"):
        assert grads[name] > 0, name
    for name in sorted(nemotron3_super.state_specs(cfg)):
        assert reference["change_norms"][name] > 0
        bias = np.asarray(trainer.scope.get(name))
        assert abs(bias.sum()) < 1e-6 and np.abs(bias).max() <= 6.1e-3


def test_the_float32_program_is_the_reference(reference):
    """The same program without ``enable_bf16``: every number of the
    comparison within 1e-4 of the float32 reference, so that what bf16
    moves is rounding and not a different model."""
    for name, number in decoder_cells.float32_compared(
            CELL, reference, SEED).items():
        assert number["value"] < 1e-4, (name, number)


def test_the_program_counts_what_it_was_built_with(super_cell):
    """5 Mamba layers, 5 LatentMoE layers and one attention layer, then the
    MTP module's attention and LatentMoE layers: 6 latent layers of relu²
    experts with a shared expert and a bias each, 13 single-part layers, 2
    unrotated attentions, an MTP module of depth 1 and 2 layers. The
    dispatch buffer holds the 4 held experts' capped pairs, 4 x ceil(128 x
    6 / 32) = 96, in one whole tile of 256 rows, not 128 x 6 = 768."""
    assert super_cell["counted"] == {
        "moe.latent_layers": 6, "decoder.mtp_layers": 2,
        "decoder.mtp_depth": 1, "decoder.single_part_layers": 13,
        "moe.relu2_layers": 6, "moe.layers": 6, "moe.shared_experts": 6,
        "moe.bias_updates": 6, "ssm.layers": 5, "attn.unrotated_layers": 2}
    assert super_cell["gauges"] == {"moe.buffer_rows": 256}


def test_the_mtp_block_predicts_two_ahead_and_leaves_the_last_out():
    """In the float32 program at the rehearsal size, two rows: the module
    is the single-part layers ``mtp.0.`` (attention) and ``mtp.1.`` (a
    LatentMoE layer), one norm each and no two-part leaves; its targets are
    ``labels[:, 1:]`` (t_{i+2} for positions 0..T-2), its logits T-1 a
    row, its loss their mean cross-entropy; the program's loss adds 0.3 x
    that to the next-token loss."""
    _, _, cfg, rows = decoder_cells.cell(CELL, amp=None)
    m = cfg["model"]
    with fluid.unique_name.guard():
        main, startup, handle = decoder_lm.get_model(
            batch_size=rows, lr=1e-4, is_train=False, **m)
    names = {p.name for p in main.all_parameters()}
    assert {"mtp.0.norm", "mtp.0.q_proj", "mtp.1.norm", "mtp.1.router",
            "mtp.1.latent_down", "mtp.1.latent_up"} <= names
    assert not {n for n in names if n.startswith("mtp.") and (
        "attn_norm" in n or "mlp_norm" in n or n.startswith("mtp.2."))}
    exe, scope = fluid.Executor(), fluid.Scope()
    exe.run(startup, scope=scope)
    batch = nemotron3_super.make_batch(cfg, rows, common.batch_rng(SEED, 0))
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
    """Only the seven keys of ``reduced`` differ from what the file states
    was published: the depth, the experts, the vocabulary and the mixers'
    head counts, each the chip's share of the stated deployment; every
    width is the published one, in the file and in the builder's sizes;
    the parameters held are the count the file states."""
    _, config, _, _ = decoder_cells.cell(CELL)
    m, published = config["model"], config["published"]
    assert config["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size",
        "mamba_num_heads", "n_groups", "num_attention_heads",
        "num_key_value_heads"]
    shares = {"num_hidden_layers": 8, "n_routed_experts": 64,
              "vocab_size": 8, "mamba_num_heads": 8, "n_groups": 8,
              "num_attention_heads": 8, "num_key_value_heads": 2}
    for key, share in shares.items():
        assert config[key] * share == published[key], key
    for key, value in (("hidden_size", 4096), ("mamba_head_dim", 64),
                       ("ssm_state_size", 128), ("conv_kernel", 4),
                       ("chunk_size", 128), ("head_dim", 128),
                       ("moe_intermediate_size", 2688),
                       ("moe_latent_size", 1024),
                       ("num_experts_per_tok", 22)):
        assert config[key] == m[key] == value, key
    assert m["shared_expert_intermediate_size"] == config[
        "moe_shared_expert_intermediate_size"] == 5376
    assert m["router_experts"] == published["n_routed_experts"] == 512
    assert m["experts_held"] == config["n_routed_experts"]
    assert m["route_scale"] == config["routed_scaling_factor"] == 5
    assert m["moe_activation"] == config["mlp_hidden_act"] == "relu2"
    assert m["hybrid_override_pattern"] == config["hybrid_override_pattern"]
    assert m["hybrid_override_pattern"][:11] == "MEMEMEM*EME"
    assert m["mtp_hybrid_override_pattern"] == config[
        "mtp_hybrid_override_pattern"] == "*E"
    assert m["mtp"]["num_nextn_predict_layers"] == config[
        "num_nextn_predict_layers"] == 1
    assert m["rms_norm_eps"] == config["layer_norm_epsilon"]
    for key in ("deployment", "assumed", "precision"):
        assert config[key]
    held = sum(int(np.prod(shape)) for shape, _ in
               nemotron3_super.param_specs(config).values())
    assert held == config["parameters_held"] == 838246896


# -- the shares: of the mixers' heads, of the experts -------------------------

D, T = 32, 19
MIXER = {"hidden_size": D, "mamba_num_heads": 16, "mamba_head_dim": 4,
         "n_groups": 8, "ssm_state_size": 8, "conv_kernel": 4,
         "chunk_size": 8, "num_attention_heads": 16, "num_key_value_heads": 2,
         "head_dim": 8, "rms_norm_eps": 1e-5}


def _mixer_leaves(rng, kind):
    """An uncut mixer's leaves at the test's sizes, under the names
    ``nemotron_h.param_specs`` gives layer 0 (Mamba) or 1 (attention)."""
    m = dict(MIXER, hybrid_override_pattern="M*", num_hidden_layers=2,
             router_experts=4, experts_held=1, moe_intermediate_size=4,
             shared_expert_intermediate_size=4, vocab_size=8)
    spec = nemotron_h.param_specs({"model": m, "init": {
        "normal_std": 0.3, "dt": [1e-3, 0.1, 1e-4], "residual_layers": 1}})
    pre = "layer0." if kind == "mamba" else "layer1."
    spec = {n: s for n, s in spec.items()
            if n.startswith(pre) and not n.endswith("norm")}
    leaves = dict(common.init_params(spec, int(rng.randint(1 << 30))))
    if kind == "mamba":
        leaves[pre + "mixer_norm"] = jnp.asarray(
            1.0 + 0.1 * rng.randn(16 * 4), jnp.float32)
    return m, pre, {n: np.asarray(v) for n, v in leaves.items()}


def _program_mixers(kind, shares, u):
    """The program's mixers on u [rows, T, D], one a share: each of
    ``shares`` (sizes, leaves under the prefix ``layer<i>.``) built as the
    builder builds that layer's mixer, all in one program -> their
    outputs."""
    def build(x):
        outs = []
        for i, (m, _) in enumerate(shares):
            pre = "layer%d." % i
            if kind == "mamba":
                outs.append(decoder_lm.mamba(
                    x, pre, m["mamba_num_heads"], m["mamba_head_dim"],
                    m["ssm_state_size"], m["n_groups"], m["conv_kernel"],
                    m["chunk_size"], m["rms_norm_eps"]))
            else:
                outs.append(decoder_lm.attention(
                    x, pre, m["num_attention_heads"],
                    m["num_key_value_heads"], m["head_dim"], None, None))
        return fluid.layers.stack(outs, axis=1)

    params = {}
    for i, (_, leaves) in enumerate(shares):
        params.update({"layer%d.%s" % (i, n.split(".", 1)[1]): v
                       for n, v in leaves.items()})
    got = _program(build, {"u": u}, params, ())[0]
    return [got[:, i] for i in range(len(shares))]


@pytest.mark.parametrize("kind", ["mamba", "attention"])
def test_the_eight_head_shares_add_up_to_the_uncut_mixer(kind):
    """The Mamba mixer of 16 heads in 8 groups, and the attention of 16
    query heads on 2 key/value heads, each in 8 shares by heads
    (``nemotron3_super.mamba_share`` / ``attention_share``: 2 heads and
    their group, or 2 query heads and their key/value head): the program's
    mixer on each share is the reference's on that share, and the 8
    shares' outputs add up to what the reference's uncut mixer gives."""
    rng = np.random.RandomState(21 if kind == "mamba" else 22)
    m, pre, leaves = _mixer_leaves(rng, kind)
    u = (rng.randn(2, T, D) * 0.5).astype(np.float32)
    share_of = (nemotron3_super.mamba_share if kind == "mamba"
                else nemotron3_super.attention_share)
    reference = nemotron_h._mamba if kind == "mamba" else \
        nemotron_h._attention
    mm = common.Matmuls("f32")

    def ref(sizes, p):
        with jax.default_matmul_precision("highest"):
            return np.asarray(reference(mm, sizes, {
                n: jnp.asarray(v) for n, v in p.items()}, pre,
                jnp.asarray(u)))

    shares = [share_of(m, {n: jnp.asarray(v) for n, v in leaves.items()},
                       pre, 8, s) for s in range(8)]
    shares = [(sizes, {n: np.asarray(v) for n, v in p.items()})
              for sizes, p in shares]
    got = _program_mixers(kind, shares, u)
    for (sizes, p), out in zip(shares, got):
        np.testing.assert_allclose(out, ref(sizes, p), atol=2e-5, rtol=2e-4)
    np.testing.assert_allclose(sum(got), ref(m, leaves), atol=5e-5,
                               rtol=2e-4)


EXPERTS, TOP, LATENT, WIDTH, SHARED, SHARES = 64, 6, 16, 8, 24, 64


def _expert_leaves(rng):
    """The uncut LatentMoE layer's leaves (all 64 experts), as the builder
    names them under the prefix ``l.``."""
    return {"l.router": rng.randn(D, EXPERTS) * 0.3,
            "l.latent_down": rng.randn(D, LATENT) * 0.3,
            "l.experts_up": rng.randn(EXPERTS, LATENT, WIDTH) * 0.3,
            "l.experts_down": rng.randn(EXPERTS, WIDTH, LATENT) * 0.3,
            "l.latent_up": rng.randn(LATENT, D) * 0.3,
            "l.shared_up": rng.randn(D, SHARED) * 0.3,
            "l.shared_down": rng.randn(SHARED, D) * 0.3}


def test_the_sixty_four_expert_shares_add_up_to_the_uncut_latent_layer():
    """64 experts in 64 shares of 1 (the cell's expert axis), 6 a token,
    sigmoid x 5, a bias that moves the choice, each held expert capped at
    ceil(40 x 6 / 64) = 4 tokens: in one program, the router and the
    projections into and out of the latent, each whole on every chip and
    so made once here, round the 64 shares' routed parts; share s's part
    out of the latent is the reference's share without the shared expert,
    and the 64 parts summed in the latent, projected out once, with the
    shared expert once, are what ``nemotron3_super.experts`` gives for the
    uncut layer (all 64 held)."""
    from paddle_tpu.layers import nn as _nn

    rng = np.random.RandomState(23)
    x = rng.randn(40, D).astype(np.float32)
    leaves = {n: v.astype(np.float32) for n, v in _expert_leaves(rng).items()}
    bias = (rng.randn(EXPERTS) * 0.2).astype(np.float32)
    shown = (0, 17, 63)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        data = fluid.layers.data(name="x", shape=[D], dtype="float32")
        weight, ids, _, _ = _nn.moe_router(
            data, EXPERTS, TOP, param_attr=fluid.ParamAttr(name="l.router"),
            score_func="sigmoid", route_scale=5.0, bias_name="l.expert_bias")
        latent = _nn.moe_latent(data, LATENT,
                                fluid.ParamAttr(name="l.latent_down"))
        parts = [_nn.moe_experts(
            latent, weight, ids, 1, s, WIDTH,
            up_attr=fluid.ParamAttr(name="s%d.experts_up" % s),
            down_attr=fluid.ParamAttr(name="s%d.experts_down" % s),
            activation="relu2", capacity_per_token=TOP / EXPERTS)[0]
            for s in range(SHARES)]

        def up(y):
            return _nn.moe_latent(y, D, fluid.ParamAttr(name="l.latent_up"))

        total = up(fluid.layers.sums(parts))
        alone = [up(parts[s]) for s in shown]
        shared = fluid.layers.reshape(decoder_lm.relu2_mlp(
            fluid.layers.reshape(data, shape=[1, -1, D]), "l.shared_",
            SHARED), shape=[-1, D])
    exe, scope = fluid.Executor(), fluid.Scope()
    exe.run(startup, scope=scope)
    for name in ("router", "latent_down", "latent_up", "shared_up",
                 "shared_down"):
        scope.set("l." + name, jnp.asarray(leaves["l." + name]))
    scope.set("l.expert_bias", jnp.asarray(bias))
    for s in range(SHARES):
        for name in ("experts_up", "experts_down"):
            scope.set("s%d.%s" % (s, name),
                      jnp.asarray(leaves["l." + name][s:s + 1]))
    total, shared, *alone = exe.run(
        main, feed={"x": x}, fetch_list=[total, shared] + alone, scope=scope)
    exe.close()

    def reference(held, offset, with_shared=True):
        m = {"num_experts_per_tok": TOP, "experts_held": held,
             "expert_offset": offset, "route_scale": 5.0,
             "expert_capacity_factor": 1.0}
        p = dict(leaves, **{
            "l.experts_up": leaves["l.experts_up"][offset:offset + held],
            "l.experts_down": leaves["l.experts_down"][offset:offset + held]})
        if not with_shared:
            p["l.shared_down"] = np.zeros_like(p["l.shared_down"])
        with jax.default_matmul_precision("highest"):
            out, load = nemotron3_super.experts(
                common.Matmuls("f32"), m,
                {k: jnp.asarray(v) for k, v in p.items()}, "l.",
                jnp.asarray(x), jnp.asarray(bias))
        assert int(load.sum()) == x.shape[0] * TOP
        return np.asarray(out)

    for s, got in zip(shown, alone):
        np.testing.assert_allclose(got, reference(1, s, False), atol=2e-5,
                                   rtol=2e-4)
    np.testing.assert_allclose(total + shared, reference(EXPERTS, 0),
                               atol=1e-4, rtol=2e-4)
