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
"""

import paddle_tpu.fluid as fluid
from paddle_tpu import observability as obs
from paddle_tpu.layers import nn as _nn


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


def gated_mlp(x, prefix, width):
    return _nn.gated_mlp(
        x, width, gate_attr=fluid.ParamAttr(name=prefix + "gate"),
        up_attr=fluid.ParamAttr(name=prefix + "up"),
        down_attr=fluid.ParamAttr(name=prefix + "down"))


def moe(x, prefix, router_experts, experts_held, expert_offset,
        experts_per_token, width, score_func="softmax", route_scale=1.0,
        biased=False, norm_eps=None):
    """-> (the held experts' part of the layer [B, T, d], tokens each held
    expert received, the router's [load, bias] with ``biased`` or [])."""
    d = int(x.shape[-1])
    flat = fluid.layers.reshape(x, shape=[-1, d])
    weight, ids, *balance = _nn.moe_router(
        flat, router_experts, experts_per_token,
        param_attr=fluid.ParamAttr(name=prefix + "router"),
        score_func=score_func, route_scale=route_scale,
        bias_name=prefix + "expert_bias" if biased else None,
        norm_eps=norm_eps)
    out, counts = _nn.moe_experts(
        flat, weight, ids, experts_held, expert_offset, width,
        gate_attr=fluid.ParamAttr(name=prefix + "experts_gate"),
        up_attr=fluid.ParamAttr(name=prefix + "experts_up"),
        down_attr=fluid.ParamAttr(name=prefix + "experts_down"))
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
              conv_L_cache=3, tie_word_embeddings=False):
    """Next-token pre-training program; the configuration gives every size
    (``batch_size`` is the feed's own: the batch axis stays open).
    ``layer_types`` names each layer ``sliding_attention``,
    ``full_attention`` or ``conv`` (the first ``num_hidden_layers`` entries
    are used);
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
        for i in range(num_hidden_layers):
            prefix, kind = "layer%d." % i, layer_types[i]
            if kind == "conv":
                attn = short_conv(
                    _norm(h, rms_norm_eps, prefix + "conv_norm"), prefix,
                    conv_L_cache)
            else:
                rope = rope_parameters.get(kind)
                attn = attention(
                    _norm(h, rms_norm_eps, prefix + "attn_norm"), prefix,
                    num_attention_heads, num_key_value_heads, head_dim,
                    sliding_window if kind == "sliding_attention" else None,
                    None if rope is None else dict(rope), rms_norm_eps,
                    qk_norm, attention_gate)
            if post_norms:
                attn = _norm(attn, rms_norm_eps, prefix + "post_attn_norm")
            h = fluid.layers.elementwise_add(h, attn)
            n2 = _norm(h, rms_norm_eps, prefix + "mlp_norm")
            if mlp_layer_types and mlp_layer_types[i] == "dense":
                part = gated_mlp(n2, prefix + "mlp_", intermediate_size)
            else:
                part, counts, balance = moe(
                    n2, prefix, router_experts, experts_held, expert_offset,
                    num_experts_per_tok, moe_intermediate_size, score_func,
                    route_scale, load_balance_coeff > 0, route_norm_eps)
                loads.append(counts)
                if balance:
                    balances.append(balance)
                if num_shared_experts:
                    part = fluid.layers.elementwise_add(part, gated_mlp(
                        n2, prefix + "shared_",
                        num_shared_experts * moe_intermediate_size))
                    obs.inc("moe.shared_experts", num_shared_experts)
            if post_norms:
                part = _norm(part, rms_norm_eps, prefix + "post_mlp_norm")
            h = fluid.layers.elementwise_add(h, part)
        h = _norm(h, rms_norm_eps, "final_norm")
        if tie_word_embeddings:
            logits = fluid.layers.matmul(
                fluid.layers.reshape(h, shape=[-1, hidden_size]),
                main.global_block().var("tok_embedding"), transpose_y=True)
            obs.inc("decoder.tied_head")
        else:
            logits = fluid.layers.reshape(_proj(h, vocab_size, "lm_head"),
                                          shape=[-1, vocab_size])
        loss = fluid.layers.mean(fluid.layers.softmax_with_cross_entropy(
            logits=logits,
            label=fluid.layers.reshape(labels, shape=[-1, 1])))
        if is_train:
            fluid.optimizer.Adam(learning_rate=lr).minimize(loss)
            for load, bias in balances:
                _nn.moe_bias_update(bias, load, load_balance_coeff)
    return main, startup, {"feeds": {"ids": ids, "labels": labels},
                           "loss": loss, "expert_loads": loads}
