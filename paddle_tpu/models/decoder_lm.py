"""A decoder language model with a per-layer attention pattern and a
mixture-of-experts feed-forward, for the chip's share of an
expert-parallel deployment. One builder for every family of block; every
argument past ``rms_norm_eps`` defaults to the plain pre-norm form:

    h = x + Wo . Attn(RoPE(Wq n1), RoPE(Wk n1), Wv n1)       n1 = RMSNorm(x)
    y = h + sum over the token's top-k experts HELD HERE of
            w_e . Wdown_e(silu(Wgate_e n2) * Wup_e n2)       n2 = RMSNorm(h)

grouped-query attention (``num_key_value_heads`` under
``num_attention_heads``), causal, ``sliding_attention`` layers with a
window and ``full_attention`` layers without; each kind with its own
rotary parameters, and a kind that ``rope_parameters`` does not list is
not rotated at all; a router over ``router_experts`` in float32, top
``num_experts_per_tok``, weights renormalised over all of them, of which
this chip computes the ``experts_held`` from ``expert_offset``; final
RMSNorm, untied head, mean next-token cross-entropy over the vocabulary
held. No biases. Every parameter has an explicit name.

What the arguments add, each alone:

    scale_embedding    the embedding times sqrt(hidden_size)
    qk_norm            RMSNorm over each head of q and of k (one scale of
                       ``head_dim`` each a layer), before the rotation
    attention_gate     Wo . (Attn(...) * sigmoid(Wg n1))
    post_norms         x + RMSNorm(Wo ...) and h + RMSNorm(MLP(n2)): four
                       norms a layer, the residual adding the normed output
    mlp_layer_types    ``dense`` layers: a gated SiLU MLP ``intermediate_size``
                       wide in place of the experts (``sparse``)
    num_shared_experts a gated SiLU MLP of that many expert widths that
                       every token passes, added to the held experts' part
    score_func, route_scale   the router's scores (``softmax`` over all, or
                       a ``sigmoid`` each) and a factor on the top-k weights
    load_balance_coeff above 0: a bias a router output that ranks the
                       experts and is no weight: state
                       (``layerN.expert_bias``) that the step itself moves,
                       after the backward, by that much against each
                       output's load (layers/nn.py ``moe_bias_update``)
    route_norm_eps     what a sigmoid router adds to the sum of the chosen
                       scores before it divides by it (None: the op's 1e-20)
    a ``conv`` layer   (a kind in ``layer_types``) has no attention, no
                       rotary table and no q/k norm: its token mixer is the
                       gated short convolution
                           z = n1 Win [d, 3d];  B, C, x = z's three chunks
                           h = x + Wout . (C * conv(B * x))
                       ``conv`` a causal depthwise convolution over time of
                       ``conv_L_cache`` taps a channel (filter
                       ``layerN.conv_filter`` [d, taps]; the last tap
                       multiplies the current position), no activation, no
                       bias (layers/nn.py ``gated_short_conv``); its norm is
                       ``layerN.conv_norm``
    tie_word_embeddings the head is the embedding itself: logits =
                       RMSNorm(h) . tok_embedding^T, no ``lm_head``; the one
                       leaf's gradient is the sum of both uses
    hybrid_override_pattern  a layer kind a character, read as
                       ``layer_types`` (the first ``num_hidden_layers``
                       are used): ``M`` ``mamba``, ``E`` ``moe`` (the expert
                       layer), ``*`` ``full_attention``; each layer is that
                       one part alone, one norm (``layerN.norm``) and one
                       residual:
                           h = h + Part(RMSNorm(h))
    a ``mamba`` layer  (``M``) the Mamba-2 mixer of ``mamba_num_heads`` heads
                       of ``mamba_head_dim``, B and C in ``n_groups`` groups
                       of ``ssm_state_size``:
                           [z, xBC, dt] = u Win;  xBC = silu(conv(xBC) + b)
                           [x, B, C] = xBC;  y = ssd_scan(x, dt, B, C)
                           Part(u) = GatedRMSNorm(y, z) Wout
                       ``conv`` causal, depthwise, ``conv_kernel`` taps with
                       a bias (layers/nn.py ``ssm_conv1d``); the scan by
                       chunks of ``chunk_size`` (``ssd_scan``); the norm over
                       ``n_groups`` groups of y * silu(z) (``gated_rms_norm``)
    moe_activation     ``relu2``: every expert and the shared expert is
                       Wdown relu(Wup m)^2, no gate (default ``swiglu``)
    shared_expert_intermediate_size  the shared expert's width (default
                       ``num_shared_experts`` x ``moe_intermediate_size``)
    mla                latent attention on every attention layer, its sizes
                       ``q_lora_rank`` (Cq), ``kv_lora_rank`` (Ckv),
                       ``qk_nope_head_dim`` (N), ``qk_rope_head_dim`` (R),
                       ``v_head_dim`` (V) and ``rope_interleave``:
                           c_q = RMSNorm(n1 Wq_a);  [c_kv | k_pe] = n1 Wkv_a
                           [q_nope | q_pe] = c_q Wq_b        N + R a head
                           [k_nope | v] = RMSNorm(c_kv) Wkv_b   N + V a head
                           q = [q_nope | RoPE(q_pe)]
                           k = [k_nope | RoPE(k_pe)], k_pe one head for all
                           h = x + Wo . Attn(q, k, v), scale (N + R)^-1/2
                       the rotation by interleaved pairs with
                       ``rope_interleave``; the up-projections, rotations
                       and broadcast are one op (layers/nn.py
                       ``latent_qkv``); ``head_dim`` and
                       ``num_key_value_heads`` go unused
    mtp                one multi-token-prediction module (DeepSeek-V3,
                       arXiv:2412.19437 section 2.2, at depth 1) after the
                       last layer, whose output h it reads before the final
                       norm: h' = Weh [RMSNorm(E[t_{i+1}]) ; RMSNorm(h_i)],
                       one more ``full_attention`` layer with experts
                       (``mtp.`` leaves), its own norm and the shared head
                       and embedding; the loss adds ``loss_weight`` x the
                       mean cross-entropy of positions 0..T-2 against
                       t_{i+2}. t_{i+1} is ``labels[i]``: nothing more is
                       fed. The handle's ``mtp`` gives its logits, targets
                       and loss
    expert_capacity_factor  a held expert takes at most this times its
                       balanced share of the pairs (tokens x
                       ``num_experts_per_tok`` / ``router_experts``), the
                       first in token order; its later pairs add nothing,
                       as the absent experts' do
    moe_latent_size    LatentMoE: the held experts work on a latent of this
                       width between two projections (``moe_latent`` ops,
                       ``layerN.latent_down`` / ``latent_up``, no norm, no
                       bias); the router reads the full-width token and the
                       shared expert stays at full width:
                           l = m Wdown_lat
                           y = h + (sum over held e of w_e Expert_e(l)) Wup_lat
                                 + Shared(m)
    mtp_hybrid_override_pattern  the multi-token-prediction module's layers
                       as single-part layers of this pattern (``*E``: an
                       attention layer, then an expert layer), ``mtp.0.``,
                       ``mtp.1.`` ..., in place of the one two-part
                       ``full_attention`` layer; needs
                       ``hybrid_override_pattern``
"""

import paddle_tpu.fluid as fluid
from paddle_tpu import observability as obs
from paddle_tpu.layers import nn as _nn


# the layer kinds of ``hybrid_override_pattern``'s characters
_PATTERN = {"M": "mamba", "E": "moe", "*": "full_attention"}


def _proj(x, size, name):
    return fluid.layers.fc(input=x, size=size, num_flatten_dims=2,
                           bias_attr=False,
                           param_attr=fluid.ParamAttr(name=name))


def _norm(x, eps, name):
    return _nn.rms_norm(x, eps, fluid.ParamAttr(name=name))


def attention(x, prefix, num_heads, num_kv_heads, head_dim, window, rope,
              rms_norm_eps=None, qk_norm=False, gate=False):
    """``rope`` None: q and k are not rotated. The counters (``metrics``
    flag) say what the Program was built with: compositions of ops that
    know nothing of attention have no lowering to count in."""
    def heads(y, n, norm=None):
        y = fluid.layers.reshape(y, shape=[0, 0, n, head_dim])
        if norm:
            y = _norm(y, rms_norm_eps, prefix + norm)
        return fluid.layers.transpose(y, perm=[0, 2, 1, 3])  # [B, H, T, D]

    q = heads(_proj(x, num_heads * head_dim, prefix + "q_proj"), num_heads,
              "q_norm" if qk_norm else None)
    k = heads(_proj(x, num_kv_heads * head_dim, prefix + "k_proj"),
              num_kv_heads, "k_norm" if qk_norm else None)
    v = heads(_proj(x, num_kv_heads * head_dim, prefix + "v_proj"),
              num_kv_heads)
    if rope is not None:
        q, k = _nn.rotary_embedding([q, k], **rope)
    ctx = _nn.fused_attention(q, k, v, causal=True, scale=head_dim ** -0.5,
                              window=window)
    ctx = fluid.layers.transpose(ctx, perm=[0, 2, 1, 3])
    ctx = fluid.layers.reshape(ctx, shape=[0, 0, num_heads * head_dim])
    if gate:
        ctx = fluid.layers.elementwise_mul(ctx, fluid.layers.sigmoid(
            _proj(x, num_heads * head_dim, prefix + "gate_proj")))
    for name, built in (("attn.qk_norm", qk_norm), ("attn.gated", gate),
                        ("attn.unrotated_layers", rope is None)):
        if built:
            obs.inc(name)
    return _proj(ctx, int(x.shape[-1]), prefix + "o_proj")


def short_conv(x, prefix, taps):
    """The gated short convolution in place of ``attention``: input
    projection to the three chunks B, C, x, C * conv(B * x), output
    projection."""
    d = int(x.shape[-1])
    mixed = _nn.gated_short_conv(
        _proj(x, 3 * d, prefix + "conv_in_proj"),
        filter_attr=fluid.ParamAttr(name=prefix + "conv_filter"), taps=taps)
    obs.inc("decoder.conv_layers")
    return _proj(mixed, d, prefix + "conv_out_proj")


def mamba(x, prefix, heads, head_dim, state, groups, taps, chunk, eps):
    """The Mamba-2 mixer in place of ``attention`` (the module's
    docstring): input projection to [z, xBC, dt], the convolution, the
    scan, the gated norm, the output projection."""
    inner, d = heads * head_dim, int(x.shape[-1])
    z, xbc, dt = fluid.layers.split(
        _proj(x, 2 * inner + 2 * groups * state + heads, prefix + "in_proj"),
        [inner, inner + 2 * groups * state, heads], dim=-1)
    xbc = _nn.ssm_conv1d(
        xbc, taps, filter_attr=fluid.ParamAttr(name=prefix + "conv_filter"),
        bias_attr=fluid.ParamAttr(name=prefix + "conv_bias"))
    xs, b, c = fluid.layers.split(
        xbc, [inner, groups * state, groups * state], dim=-1)
    y = _nn.ssd_scan(
        fluid.layers.reshape(xs, shape=[0, 0, heads, head_dim]), dt,
        fluid.layers.reshape(b, shape=[0, 0, groups, state]),
        fluid.layers.reshape(c, shape=[0, 0, groups, state]), chunk,
        dt_bias_attr=fluid.ParamAttr(name=prefix + "dt_bias"),
        a_log_attr=fluid.ParamAttr(name=prefix + "A_log"),
        d_attr=fluid.ParamAttr(name=prefix + "D"))
    y = _nn.gated_rms_norm(
        fluid.layers.reshape(y, shape=[0, 0, inner]), z, groups, eps,
        fluid.ParamAttr(name=prefix + "mixer_norm"))
    return _proj(y, d, prefix + "out_proj")


def latent_attention(x, prefix, heads, mla, rope, eps):
    """Latent attention in place of ``attention`` (the module's
    docstring): the low-rank query and the latent with its shared rotary
    key, their norms, the up-projections (``latent_qkv``), causal flash
    attention whose q.k width is N + R and whose values are V wide, the
    output projection."""
    d = int(x.shape[-1])
    nope, pe = mla["qk_nope_head_dim"], mla["qk_rope_head_dim"]
    width = mla["v_head_dim"]
    cq = _norm(_proj(x, mla["q_lora_rank"], prefix + "q_a_proj"), eps,
               prefix + "q_a_norm")
    ckv, kpe = fluid.layers.split(
        _proj(x, mla["kv_lora_rank"] + pe, prefix + "kv_a_proj"),
        [mla["kv_lora_rank"], pe], dim=-1)
    q, k, v = _nn.latent_qkv(
        cq, _norm(ckv, eps, prefix + "kv_a_norm"), kpe, heads, nope, pe,
        width, q_b_attr=fluid.ParamAttr(name=prefix + "q_b_proj"),
        kv_b_attr=fluid.ParamAttr(name=prefix + "kv_b_proj"),
        interleaved=mla.get("rope_interleave", False), **rope)
    ctx = _nn.fused_attention(q, k, v, causal=True,
                              scale=(nope + pe) ** -0.5)
    ctx = fluid.layers.transpose(ctx, perm=[0, 2, 1, 3])
    ctx = fluid.layers.reshape(ctx, shape=[0, 0, heads * width])
    obs.inc("attn.mla_layers")
    return _proj(ctx, d, prefix + "o_proj")


def next_token_module(h, labels, seq_len, vocab_size, layer, head, eps, mtp,
                      pattern=None):
    """The multi-token-prediction module of depth 1 (the module's
    docstring), from the last layer's output ``h`` before the final norm:
    -> (its logits over positions 0..T-2, their targets t_{i+2}). Its
    layers are one two-part ``full_attention`` layer, or the single-part
    layers that ``pattern`` names."""
    if mtp.get("num_nextn_predict_layers", 1) != 1:
        raise ValueError("multi-token prediction is built at depth 1 only")
    d = int(h.shape[-1])
    # the next token's embedding, from the shared table: t_{i+1} is
    # labels[i], so position T-1 has one too
    emb = fluid.layers.embedding(
        input=labels, size=[vocab_size, d],
        param_attr=fluid.ParamAttr(name="tok_embedding"))
    joined = fluid.layers.concat(
        [_norm(emb, eps, "mtp.enorm"), _norm(h, eps, "mtp.hnorm")], axis=-1)
    out = _proj(joined, d, "mtp.eh_proj")
    if pattern:
        for i, c in enumerate(pattern):
            out = layer(out, "mtp.%d." % i, _PATTERN[c], False)
    else:
        out = layer(out, "mtp.", "full_attention", False)
    obs.inc("decoder.mtp_depth")
    obs.inc("decoder.mtp_layers", len(pattern) if pattern else 1)
    # position T-1 has no t_{T+1}: its prediction is left out
    logits = head(fluid.layers.slice(out, axes=[1], starts=[0],
                                     ends=[seq_len - 1]), "mtp.final_norm")
    return logits, fluid.layers.slice(labels, axes=[1], starts=[1],
                                      ends=[seq_len])


def relu2_mlp(x, prefix, width):
    """Wdown relu(Wup x)^2, no biases: fc, relu, square, fc."""
    return _proj(fluid.layers.square(fluid.layers.relu(
        _proj(x, width, prefix + "up"))), int(x.shape[-1]), prefix + "down")


def gated_mlp(x, prefix, width):
    return _nn.gated_mlp(
        x, width, gate_attr=fluid.ParamAttr(name=prefix + "gate"),
        up_attr=fluid.ParamAttr(name=prefix + "up"),
        down_attr=fluid.ParamAttr(name=prefix + "down"))


def moe(x, prefix, router_experts, experts_held, expert_offset,
        experts_per_token, width, score_func="softmax", route_scale=1.0,
        biased=False, norm_eps=None, activation="swiglu",
        capacity_factor=None, latent=None):
    """-> (the held experts' part of the layer [B, T, d], tokens each held
    expert received, the router's [load, bias] with ``biased`` or []).
    ``latent``: the experts work between the projections into and out of
    a latent of that width (the module's ``moe_latent_size``)."""
    d = int(x.shape[-1])
    flat = fluid.layers.reshape(x, shape=[-1, d])
    weight, ids, *balance = _nn.moe_router(
        flat, router_experts, experts_per_token,
        param_attr=fluid.ParamAttr(name=prefix + "router"),
        score_func=score_func, route_scale=route_scale,
        bias_name=prefix + "expert_bias" if biased else None,
        norm_eps=norm_eps)
    rows = flat
    if latent:
        rows = _nn.moe_latent(flat, latent,
                              fluid.ParamAttr(name=prefix + "latent_down"))
        obs.inc("moe.latent_layers")
    out, counts = _nn.moe_experts(
        rows, weight, ids, experts_held, expert_offset, width,
        gate_attr=fluid.ParamAttr(name=prefix + "experts_gate"),
        up_attr=fluid.ParamAttr(name=prefix + "experts_up"),
        down_attr=fluid.ParamAttr(name=prefix + "experts_down"),
        activation=activation,
        capacity_per_token=capacity_factor and (
            capacity_factor * experts_per_token / router_experts))
    if latent:
        out = _nn.moe_latent(out, d,
                             fluid.ParamAttr(name=prefix + "latent_up"))
    return (fluid.layers.reshape(out, shape=[-1, int(x.shape[1]), d]), counts,
            balance)


def get_model(batch_size, seq_len, vocab_size, hidden_size, num_hidden_layers,
              num_attention_heads, num_key_value_heads, head_dim,
              router_experts, experts_held, expert_offset,
              num_experts_per_tok, moe_intermediate_size, sliding_window,
              layer_types, rope_parameters, rms_norm_eps, lr, is_train=True,
              mlp_layer_types=None, intermediate_size=None,
              num_shared_experts=0, score_func="softmax", route_scale=1.0,
              load_balance_coeff=0.0, qk_norm=False, attention_gate=False,
              post_norms=False, scale_embedding=False, route_norm_eps=None,
              conv_L_cache=3, tie_word_embeddings=False,
              hybrid_override_pattern=None, moe_activation="swiglu",
              shared_expert_intermediate_size=None, mamba_num_heads=None,
              mamba_head_dim=None, ssm_state_size=None, n_groups=None,
              conv_kernel=4, chunk_size=128, mla=None, mtp=None,
              expert_capacity_factor=None, moe_latent_size=None,
              mtp_hybrid_override_pattern=None):
    """Next-token pre-training program; the configuration gives every size
    (``batch_size`` is the feed's own: the batch axis stays open).
    ``layer_types`` names each layer ``sliding_attention``,
    ``full_attention`` or ``conv`` (the first ``num_hidden_layers`` entries
    are used; ``hybrid_override_pattern``, where given, instead);
    ``rope_parameters`` gives each kind that is rotated its rotary
    attributes; ``mlp_layer_types`` names each layer ``sparse`` (the
    default) or ``dense``. The rest: the module's docstring."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        ids = fluid.layers.data(name="ids", shape=[seq_len], dtype="int64")
        labels = fluid.layers.data(name="labels", shape=[seq_len],
                                   dtype="int64")
        h = fluid.layers.embedding(
            input=ids, size=[vocab_size, hidden_size],
            param_attr=fluid.ParamAttr(name="tok_embedding"))
        if scale_embedding:
            h = fluid.layers.scale(h, scale=float(hidden_size) ** 0.5)
        loads, balances = [], []

        def experts(n2, prefix):
            """The expert layer's part: the held experts' and the shared
            expert's."""
            part, counts, balance = moe(
                n2, prefix, router_experts, experts_held, expert_offset,
                num_experts_per_tok, moe_intermediate_size, score_func,
                route_scale, load_balance_coeff > 0, route_norm_eps,
                moe_activation, expert_capacity_factor, moe_latent_size)
            loads.append(counts)
            if balance:
                balances.append(balance)
            if num_shared_experts:
                shared = relu2_mlp if moe_activation == "relu2" else gated_mlp
                part = fluid.layers.elementwise_add(part, shared(
                    n2, prefix + "shared_", shared_expert_intermediate_size
                    or num_shared_experts * moe_intermediate_size))
                obs.inc("moe.shared_experts", num_shared_experts)
            return part

        single = bool(hybrid_override_pattern)
        if single:
            layer_types = [_PATTERN[c] for c in hybrid_override_pattern]
        elif mtp_hybrid_override_pattern:
            raise ValueError("mtp_hybrid_override_pattern names single-part "
                             "layers: it needs hybrid_override_pattern")

        def layer(h, prefix, kind, dense):
            """One decoder layer of ``kind``, its MLP ``dense`` or not."""
            def norm(x, name):
                return _norm(x, rms_norm_eps,
                             prefix + ("norm" if single else name))

            if kind == "conv":
                attn = short_conv(norm(h, "conv_norm"), prefix, conv_L_cache)
            elif kind == "mamba":
                attn = mamba(norm(h, "attn_norm"), prefix, mamba_num_heads,
                             mamba_head_dim, ssm_state_size, n_groups,
                             conv_kernel, chunk_size, rms_norm_eps)
            elif kind != "moe" and mla:
                attn = latent_attention(
                    norm(h, "attn_norm"), prefix, num_attention_heads, mla,
                    rope_parameters[kind], rms_norm_eps)
            elif kind != "moe":
                rope = rope_parameters.get(kind)
                attn = attention(
                    norm(h, "attn_norm"), prefix, num_attention_heads,
                    num_key_value_heads, head_dim,
                    sliding_window if kind == "sliding_attention" else None,
                    None if rope is None else dict(rope), rms_norm_eps,
                    qk_norm, attention_gate)
            if kind != "moe":
                if post_norms:
                    attn = _norm(attn, rms_norm_eps,
                                 prefix + "post_attn_norm")
                h = fluid.layers.elementwise_add(h, attn)
            if single:
                obs.inc("decoder.single_part_layers")
                if kind != "moe":
                    return h
            n2 = norm(h, "mlp_norm")
            if dense:
                part = gated_mlp(n2, prefix + "mlp_", intermediate_size)
            else:
                part = experts(n2, prefix)
            if post_norms:
                part = _norm(part, rms_norm_eps, prefix + "post_mlp_norm")
            return fluid.layers.elementwise_add(h, part)

        def head(h, norm_name):
            """Logits [tokens, vocabulary] of RMSNorm(h)."""
            h = _norm(h, rms_norm_eps, norm_name)
            if tie_word_embeddings:
                obs.inc("decoder.tied_head")
                return fluid.layers.matmul(
                    fluid.layers.reshape(h, shape=[-1, hidden_size]),
                    main.global_block().var("tok_embedding"),
                    transpose_y=True)
            return fluid.layers.reshape(_proj(h, vocab_size, "lm_head"),
                                        shape=[-1, vocab_size])

        def cross_entropy(logits, targets):
            return fluid.layers.mean(fluid.layers.softmax_with_cross_entropy(
                logits=logits,
                label=fluid.layers.reshape(targets, shape=[-1, 1])))

        for i in range(num_hidden_layers):
            h = layer(h, "layer%d." % i, layer_types[i],
                      bool(mlp_layer_types) and mlp_layer_types[i] == "dense")
        loss = cross_entropy(head(h, "final_norm"), labels)
        predicted = {}
        if mtp:
            logits, targets = next_token_module(
                h, labels, seq_len, vocab_size, layer, head, rms_norm_eps,
                mtp, mtp_hybrid_override_pattern)
            predicted = {"logits": logits, "targets": targets,
                         "loss": cross_entropy(logits, targets)}
            loss = fluid.layers.elementwise_add(loss, fluid.layers.scale(
                predicted["loss"], scale=float(mtp["loss_weight"])))
        if is_train:
            fluid.optimizer.Adam(learning_rate=lr).minimize(loss)
            for load, bias in balances:
                _nn.moe_bias_update(bias, load, load_balance_coeff)
    return main, startup, {"feeds": {"ids": ids, "labels": labels},
                           "loss": loss, "expert_loads": loads,
                           "mtp": predicted}
