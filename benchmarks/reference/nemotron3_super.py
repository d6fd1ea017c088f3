"""Plain reference of the decoder pre-training step of one chip's share of
NVIDIA-Nemotron-3-Super-120B-A12B (``configs/nemotron3_super_120b_a12b.json``,
``model_type`` ``nemotron_h``), written from the layer equations and not
from the program. Float32 throughout, plain ``jax.numpy``, nothing of the
program imported. The Mamba-2 mixer and its per-position scan, the
attention and the relu² MLP are ``nemotron_h.py``'s; the capacity, the
summed cross-entropy and the multi-token-prediction module's form are
``joyai_llm_flash.py``'s: imported, not copied.

    RMS(x; g) = x / sqrt(mean(x^2, last axis) + eps) * g,  eps 1e-5
    h = E[ids]                                        (no scale)
    layer i, of kind M, E or * (``hybrid_override_pattern``), one part a
    layer:  h += Part_i(RMS(h; norm_i))
    M (this chip's heads h0 .. h0 + H - 1 of the published 128, and the G
        groups they read):
        [z, xBC, dt] = u Win                          [I, I + 2 G N, H]
        xBC = silu(conv_4(xBC) + b);  [x, B, C] = xBC [I, G N, G N]
        Δ = softplus(dt + dt_bias);  A = -exp(A_log)
        S_t = exp(Δ_t A) S_{t-1} + Δ_t x_t ⊗ B_t,  y_t = S_t C_t + D x_t
        Part = RMS_G(y * silu(z); g_m) Wout           the RMS over each of
                                                      the G groups of I / G
        (nemotron_h ``_mamba``; in the cell H 16 heads of 64, G 1,
        I 1,024)
    *:  q, k, v = u Wq, u Wk, u Wv, not rotated; causal softmax attention,
        scale head^-1/2, query head n on key/value head n // (Hq / Hkv);
        Part = o Wo                                   (nemotron_h
        ``_attention``; in the cell 4 query heads on 1 key/value head)
    E (LatentMoE):
        s = sigmoid(m Wr) over all E router outputs, in float32; the k
        chosen are the largest of s + b (b: state, no gradient);
        w_e = route_scale * s_e / (sum of the chosen s + 1e-20)
        l = m W_down_lat                              [L], no norm, no bias
        r = sum over chosen e HELD HERE, within its capacity, of
            w_e Wdown_e relu(Wup_e l)^2               Wup_e [L, width]
        Part = r W_up_lat + Wdown_sh relu(Wup_sh m)^2 the shared expert at
                                                      full width
        a held expert takes the first ceil(N x capacity factor x k / E) of
        the N tokens of the batch that chose it (rows, then positions);
        what the absent experts would add, and a held expert's pairs past
        its capacity, are left out
    loss_main = mean next-token cross-entropy of RMS(h; final_norm) Whead
    MTP, depth 1, from h before the final norm (``mtp_hybrid_override_
    pattern`` ``*E``):
        h' = [RMS(E[t_{i+1}]; mtp.enorm) ; RMS(h; mtp.hnorm)] W_eh
        h' += Part(RMS(h'; mtp.0.norm)), the * above, leaves ``mtp.0.*``
        h' += Part(RMS(h'; mtp.1.norm)), the E above, leaves ``mtp.1.*``
        loss_mtp = mean over i = 0..T-2 of the cross-entropy of
                   RMS(h'_i; mtp.final_norm) Whead against t_{i+2}
    loss = loss_main + loss_weight * loss_mtp
    after the backward, for every E layer (the MTP module's too):
    load_e = selections of the step that fell on router output e (all of
    them, held or not); d = coeff * sign(mean(load) - load);
    b += d - mean(d)

**The head shares** (``mamba_share``, ``attention_share``): the chip's
mixers are the published ones split by heads over a tensor axis. A share
of the Mamba mixer is its heads' columns of z, x and dt, its groups'
columns of B and C (the convolution's channels likewise), its heads'
Δ bias, A_log and D, its channels' norm scale and its channels' rows of
Wout; the gated norm's groups are whole in a share, so the shares' outputs
add up to the uncut mixer's. A share of the attention is its query heads'
columns of Wq, their key/value head's columns of Wk and Wv, and their rows
of Wo. ``tests/test_nemotron3_super.py`` adds the shares up.

Departures from the published model, each in both program and reference:
the heads, experts and vocabulary rows not held here, the 77 layers on
other pipeline stages and the exchange are left out (the configuration's
``deployment``); a held expert takes no more than its balanced share of
the batch's pairs (``expert_capacity_factor`` 1), as in
``joyai_llm_flash``; ``n_group`` = ``topk_group`` = 1 is no grouping of
the router; the clamp of Δ is no operation.

Memory, as ``nemotron_h.py``: the bias is state that the step returns, so
``row_blocks`` gives the whole batch; each layer is under a
``jax.checkpoint``, inside it the scores are made one block of queries at
a time and the experts one at a time; the heads and the losses walk the
rows in a ``lax.scan``, a row under a checkpoint.
"""

import jax
import jax.numpy as jnp

from benchmarks import work_moe, work_ssd
from benchmarks.reference import joyai_llm_flash, nemotron_h
from benchmarks.reference.common import normal

HIGHEST = jax.lax.Precision.HIGHEST
MTP = "mtp."
KINDS = nemotron_h.KINDS

# what the program's pipeline stage shares with the reference's
first_gradient_state = nemotron_h.first_gradient_state
make_batch = nemotron_h.make_batch
normalisers = joyai_llm_flash.normalisers
capacity = joyai_llm_flash.capacity
held_pairs = joyai_llm_flash.held_pairs
update_bias = nemotron_h.update_bias


def row_blocks(cfg):
    """The whole batch at once: the step keeps the state it returns."""
    return None


def _prefixes(m):
    """(prefix, kind) of every layer: the pattern's, then the MTP
    module's."""
    layers = [("layer%d." % i, kind)
              for i, kind in enumerate(nemotron_h.kinds(m))]
    return layers + [("%s%d." % (MTP, i), KINDS[c]) for i, c in
                     enumerate(m.get("mtp_hybrid_override_pattern") or "")]


def param_specs(cfg):
    """The program's parameters by the names its builder gives them, at
    the published initialisation (``assumed``: ``initialisation``)."""
    m, init = cfg["model"], cfg["init"]
    d, latent = m["hidden_size"], m["moe_latent_size"]
    held, width = m["experts_held"], m["moe_intermediate_size"]
    matrix, one = normal(init["normal_std"]), ("const", 1.0)
    # the mixers', the attention's, the embedding's and the head's leaves
    # are nemotron_h's; out_proj is drawn by the PUBLISHED mixer's fan-in,
    # of which this chip holds a share of the rows
    spec = nemotron_h.param_specs(cfg)
    bound = init["out_proj_fan_in"] ** -0.5 / init["residual_layers"] ** 0.5
    shared = m["shared_expert_intermediate_size"]

    def moe(p):
        spec[p + "router"] = ((d, m["router_experts"]), matrix)
        spec[p + "latent_down"] = ((d, latent), matrix)
        spec[p + "experts_up"] = ((held, latent, width), matrix)
        spec[p + "experts_down"] = ((held, width, latent), matrix)
        spec[p + "latent_up"] = ((latent, d), matrix)
        spec[p + "shared_up"] = ((d, shared), matrix)
        spec[p + "shared_down"] = ((shared, d), matrix)

    def attention(p):
        q = m["num_attention_heads"] * m["head_dim"]
        kv = m["num_key_value_heads"] * m["head_dim"]
        for name, shape in (("q_proj", (d, q)), ("k_proj", (d, kv)),
                            ("v_proj", (d, kv)), ("o_proj", (q, d))):
            spec[p + name] = (shape, matrix)

    for p, kind in _prefixes(m):
        if kind == "mamba":
            spec[p + "out_proj"] = (spec[p + "out_proj"][0],
                                    ("uniform", bound))
        elif kind == "moe":
            moe(p)
        if p.startswith(MTP):
            spec[p + "norm"] = ((d,), one)
            if kind == "attention":
                attention(p)
    if m.get("mtp"):
        spec[MTP + "enorm"] = ((d,), one)
        spec[MTP + "hnorm"] = ((d,), one)
        spec[MTP + "eh_proj"] = ((2 * d, d), matrix)
        spec[MTP + "final_norm"] = ((d,), one)
    return spec


def state_specs(cfg):
    """Every expert layer's balancing bias, the MTP module's too, 0 at the
    start."""
    m = cfg["model"]
    return {p + "expert_bias": ((m["router_experts"],), 0.0)
            for p, kind in _prefixes(m) if kind == "moe"}


def step_flops(cfg, rows):
    """Model operations of one pre-training step (forward + backward = 3 x
    forward for a matmul). Per token: on a Mamba layer the input and
    output projections, the convolution's taps and the chunked scan's
    products (``work_ssd.ssd_flops``); on an attention layer the Q, K, V
    and O projections and the scores over the keys a causal query sees;
    on an expert layer the router, the two latent projections, the shared
    expert's two products and the two products of the pairs on held
    experts at the latent's width (``held_pairs``: the expectation, which
    the capacity equals at a factor of 1); the MTP module's layers as
    these, its ``eh_proj``, and the shared head over the vocabulary held
    for every position and again for the T-1 that the MTP module
    predicts. Look-ups, norms, the gate, softplus, softmax, top-k, sort,
    gathers and the bias update are not counted."""
    m = cfg["model"]
    d, seq = m["hidden_size"], m["seq_len"]
    tokens = rows * seq
    heads, inner = m["mamba_num_heads"], m["mamba_num_heads"] * m[
        "mamba_head_dim"]
    conv = inner + 2 * m["n_groups"] * m["ssm_state_size"]
    q = m["num_attention_heads"] * m["head_dim"]
    kv = m["num_key_value_heads"] * m["head_dim"]
    kinds = [kind for _, kind in _prefixes(m)]
    mambas, moes = kinds.count("mamba"), kinds.count("moe")
    attentions = kinds.count("attention")
    per_token = mambas * (2 * d * (inner + conv + heads) + 2 * inner * d
                          + 2 * m["conv_kernel"] * conv)
    per_token += moes * (2 * d * m["router_experts"]
                         + 4 * d * m["moe_latent_size"]
                         + 4 * d * m["shared_expert_intermediate_size"])
    per_token += attentions * (4 * d * q + 4 * d * kv)
    head_tokens = tokens
    if m.get("mtp"):
        per_token += 2 * (2 * d) * d
        head_tokens += rows * (seq - 1)
    experts = moes * 3 * 4 * held_pairs(m, tokens) * m[
        "moe_latent_size"] * m["moe_intermediate_size"]
    scan = mambas * sum(work_ssd.ssd_flops(
        tokens, heads, m["mamba_head_dim"], m["n_groups"],
        m["ssm_state_size"], m["chunk_size"]))
    attention = attentions * sum(work_moe.masked_attention_flops(
        rows, m["num_attention_heads"], seq, m["head_dim"]))
    return (3 * tokens * per_token + 3 * head_tokens * 2 * d * m["vocab_size"]
            + experts + scan + attention)


# -- the head shares of the mixers --------------------------------------------

def mamba_share(m, p, pre, shares, s):
    """-> (the sizes, the leaves) of share ``s`` of ``shares`` that split
    the Mamba mixer of ``m`` (its leaves ``p[pre + ...]``) by heads, under
    the same names: the module's docstring."""
    heads, hd = m["mamba_num_heads"], m["mamba_head_dim"]
    groups, n = m["n_groups"], m["ssm_state_size"]
    inner, gn = heads * hd, groups * n
    h, g = heads // shares, groups // shares
    assert h * shares == heads and g * shares == groups, (heads, groups)
    chans = jnp.arange(s * h * hd, (s + 1) * h * hd)
    bc = jnp.arange(s * g * n, (s + 1) * g * n)
    mine = jnp.arange(s * h, (s + 1) * h)
    conv = jnp.concatenate([chans, inner + bc, inner + gn + bc])
    cols = jnp.concatenate([chans, inner + conv, 2 * inner + 2 * gn + mine])
    leaves = {"in_proj": p[pre + "in_proj"][:, cols],
              "conv_filter": p[pre + "conv_filter"][conv],
              "conv_bias": p[pre + "conv_bias"][conv],
              "dt_bias": p[pre + "dt_bias"][mine],
              "A_log": p[pre + "A_log"][mine], "D": p[pre + "D"][mine],
              "mixer_norm": p[pre + "mixer_norm"][chans],
              "out_proj": p[pre + "out_proj"][chans]}
    return (dict(m, mamba_num_heads=h, n_groups=g),
            {pre + k: v for k, v in leaves.items()})


def attention_share(m, p, pre, shares, s):
    """-> (the sizes, the leaves) of share ``s`` of ``shares`` that split
    the attention of ``m`` by query heads, each share with the one
    key/value head its query heads read."""
    hq, hkv, dh = (m["num_attention_heads"], m["num_key_value_heads"],
                   m["head_dim"])
    h = hq // shares
    assert h * shares == hq and (hq // hkv) % h == 0, (hq, hkv, shares)
    q = jnp.arange(s * h * dh, (s + 1) * h * dh)
    kv_head = s * h // (hq // hkv)
    kv = jnp.arange(kv_head * dh, (kv_head + 1) * dh)
    leaves = {"q_proj": p[pre + "q_proj"][:, q],
              "k_proj": p[pre + "k_proj"][:, kv],
              "v_proj": p[pre + "v_proj"][:, kv],
              "o_proj": p[pre + "o_proj"][q]}
    return (dict(m, num_attention_heads=h, num_key_value_heads=1),
            {pre + k: v for k, v in leaves.items()})


# -- the layers, for the batch x [rows, seq, d] -------------------------------

def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def experts(mm, m, p, pre, x, bias):
    """-> (the LatentMoE layer's part for the tokens x [N, d]: the held
    experts' within their capacities through the latent, and the shared
    expert's; the selections that fell on each router output [E] int32)."""
    scores = jax.nn.sigmoid(jnp.dot(x, p[pre + "router"], precision=HIGHEST))
    _, chosen = jax.lax.top_k(scores + jax.lax.stop_gradient(bias),
                              m["num_experts_per_tok"])
    mask = jnp.sum(jax.nn.one_hot(chosen, scores.shape[-1]), 1)
    picked = mask * scores
    picked = m["route_scale"] * picked / (
        jnp.sum(picked, -1, keepdims=True) + 1e-20)
    first = m.get("expert_offset", 0)
    share = picked[:, first:first + m["experts_held"]]     # [N, held]
    cap = capacity(dict(m, router_experts=scores.shape[-1]), x.shape[0])
    if cap is not None:
        # a held expert's pairs past the first ``cap`` tokens add nothing
        share = share * (jnp.cumsum(
            mask[:, first:first + m["experts_held"]], 0) <= cap)
    latent = mm.dot(x, p[pre + "latent_down"])

    @jax.checkpoint
    def one(up, down, weight):
        return weight[:, None] * nemotron_h._relu2(mm, latent, up, down)

    # (the sum is outside the checkpoint: its backward needs no partial sum)
    routed, _ = jax.lax.scan(
        lambda acc, expert: (acc + one(*expert), None),
        jnp.zeros_like(latent),
        (p[pre + "experts_up"], p[pre + "experts_down"], share.T))
    out = mm.dot(routed, p[pre + "latent_up"]) + jax.checkpoint(
        lambda x_: nemotron_h._relu2(mm, x_, p[pre + "shared_up"],
                                     p[pre + "shared_down"]))(x)
    return out, jnp.sum(mask, 0).astype(jnp.int32)


def layer(mm, m, p, bias, pre, kind, h):
    """-> (one single-part layer's output, its router's loads or None)."""
    u = _rms(h, p[pre + "norm"], m["rms_norm_eps"])
    if kind == "mamba":
        return h + nemotron_h._mamba(mm, m, p, pre, u), None
    if kind == "attention":
        return h + nemotron_h._attention(mm, m, p, pre, u), None
    part, load = experts(mm, m, p, pre, u.reshape(-1, h.shape[-1]), bias)
    return h + part.reshape(h.shape), load


def block_loss(p, state, block, norm, cfg, mm):
    """The step's loss over the rows of ``block`` (the whole batch) and the
    state the step leaves: every expert layer's bias moved by its loads."""
    m = cfg["model"]
    eps, labels = m["rms_norm_eps"], block["labels"]
    new_state = {}

    def run(pre, kind, h):
        name = pre + "expert_bias"
        h, load = jax.checkpoint(
            lambda p_, h_: layer(mm, m, p_, state.get(name), pre, kind,
                                 h_))(p, h)
        if load is not None:
            new_state[name] = update_bias(state[name], load,
                                          m["load_balance_coeff"])
        return h

    h = p["tok_embedding"][block["ids"].astype(jnp.int32)]
    prefixes = _prefixes(m)
    for pre, kind in prefixes:
        if not pre.startswith(MTP):
            h = run(pre, kind, h)
    loss = joyai_llm_flash._summed_cross_entropy(
        mm, m, p, "final_norm", h, labels) / norm["tokens"]
    if not m.get("mtp"):
        return loss, new_state
    e = p["tok_embedding"][labels.astype(jnp.int32)]
    h = mm.dot(jnp.concatenate([_rms(e, p[MTP + "enorm"], eps),
                                _rms(h, p[MTP + "hnorm"], eps)], -1),
               p[MTP + "eh_proj"])
    for pre, kind in prefixes:
        if pre.startswith(MTP):
            h = run(pre, kind, h)
    mtp = joyai_llm_flash._summed_cross_entropy(
        mm, m, p, MTP + "final_norm", h[:, :-1], labels[:, 1:]) / norm[
        "mtp_tokens"]
    return loss + m["mtp"]["loss_weight"] * mtp, new_state
