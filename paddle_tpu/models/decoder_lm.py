"""A pre-norm decoder language model with a per-layer attention pattern and
a mixture-of-experts feed-forward, for the chip's share of an
expert-parallel deployment:

    h = x + Wo . Attn(RoPE(Wq n1), RoPE(Wk n1), Wv n1)       n1 = RMSNorm(x)
    y = h + sum over the token's top-k experts HELD HERE of
            w_e . Wdown_e(silu(Wgate_e n2) * Wup_e n2)       n2 = RMSNorm(h)

grouped-query attention (``num_key_value_heads`` under
``num_attention_heads``), causal, ``sliding_attention`` layers with a
window and ``full_attention`` layers without, each kind with its own
rotary parameters; a softmax router over ``router_experts`` in float32,
top ``num_experts_per_tok``, weights renormalised over all of them, of
which this chip computes the ``experts_held`` from ``expert_offset``; final
RMSNorm, untied head, mean next-token cross-entropy over the vocabulary
held. No biases. Every parameter has an explicit name.
"""

import paddle_tpu.fluid as fluid
from paddle_tpu.layers import nn as _nn


def _proj(x, size, name):
    return fluid.layers.fc(input=x, size=size, num_flatten_dims=2,
                           bias_attr=False,
                           param_attr=fluid.ParamAttr(name=name))


def attention(x, prefix, num_heads, num_kv_heads, head_dim, window, rope):
    def heads(y, n):
        y = fluid.layers.reshape(y, shape=[0, 0, n, head_dim])
        return fluid.layers.transpose(y, perm=[0, 2, 1, 3])  # [B, H, T, D]

    q = heads(_proj(x, num_heads * head_dim, prefix + "q_proj"), num_heads)
    k = heads(_proj(x, num_kv_heads * head_dim, prefix + "k_proj"),
              num_kv_heads)
    v = heads(_proj(x, num_kv_heads * head_dim, prefix + "v_proj"),
              num_kv_heads)
    q, k = _nn.rotary_embedding([q, k], **rope)
    ctx = _nn.fused_attention(q, k, v, causal=True, scale=head_dim ** -0.5,
                              window=window)
    ctx = fluid.layers.transpose(ctx, perm=[0, 2, 1, 3])
    ctx = fluid.layers.reshape(ctx, shape=[0, 0, num_heads * head_dim])
    return _proj(ctx, int(x.shape[-1]), prefix + "o_proj")


def moe(x, prefix, router_experts, experts_held, expert_offset,
        experts_per_token, width):
    """-> (the held experts' part of the layer [B, T, d], tokens each held
    expert received)."""
    d = int(x.shape[-1])
    flat = fluid.layers.reshape(x, shape=[-1, d])
    weight, ids = _nn.moe_router(
        flat, router_experts, experts_per_token,
        param_attr=fluid.ParamAttr(name=prefix + "router"))
    out, counts = _nn.moe_experts(
        flat, weight, ids, experts_held, expert_offset, width,
        gate_attr=fluid.ParamAttr(name=prefix + "experts_gate"),
        up_attr=fluid.ParamAttr(name=prefix + "experts_up"),
        down_attr=fluid.ParamAttr(name=prefix + "experts_down"))
    return fluid.layers.reshape(out, shape=[-1, int(x.shape[1]), d]), counts


def get_model(batch_size, seq_len, vocab_size, hidden_size, num_hidden_layers,
              num_attention_heads, num_key_value_heads, head_dim,
              router_experts, experts_held, expert_offset,
              num_experts_per_tok, moe_intermediate_size, sliding_window,
              layer_types, rope_parameters, rms_norm_eps, lr, is_train=True):
    """Next-token pre-training program; the configuration gives every size
    (``batch_size`` is the feed's own: the batch axis stays open).
    ``layer_types`` names each layer ``sliding_attention`` or
    ``full_attention`` (the first ``num_hidden_layers`` entries are used);
    ``rope_parameters`` gives each kind its rotary attributes."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        ids = fluid.layers.data(name="ids", shape=[seq_len], dtype="int64")
        labels = fluid.layers.data(name="labels", shape=[seq_len],
                                   dtype="int64")
        h = fluid.layers.embedding(
            input=ids, size=[vocab_size, hidden_size],
            param_attr=fluid.ParamAttr(name="tok_embedding"))
        loads = []
        for i in range(num_hidden_layers):
            prefix, kind = "layer%d." % i, layer_types[i]
            n1 = _nn.rms_norm(h, rms_norm_eps,
                              fluid.ParamAttr(name=prefix + "attn_norm"))
            attn = attention(
                n1, prefix, num_attention_heads, num_key_value_heads,
                head_dim,
                sliding_window if kind == "sliding_attention" else None,
                dict(rope_parameters.get(kind, {})))
            h = fluid.layers.elementwise_add(h, attn)
            n2 = _nn.rms_norm(h, rms_norm_eps,
                              fluid.ParamAttr(name=prefix + "mlp_norm"))
            part, counts = moe(n2, prefix, router_experts, experts_held,
                               expert_offset, num_experts_per_tok,
                               moe_intermediate_size)
            loads.append(counts)
            h = fluid.layers.elementwise_add(h, part)
        h = _nn.rms_norm(h, rms_norm_eps,
                         fluid.ParamAttr(name="final_norm"))
        logits = _proj(h, vocab_size, "lm_head")
        loss = fluid.layers.mean(fluid.layers.softmax_with_cross_entropy(
            logits=fluid.layers.reshape(logits, shape=[-1, vocab_size]),
            label=fluid.layers.reshape(labels, shape=[-1, 1])))
        if is_train:
            fluid.optimizer.Adam(learning_rate=lr).minimize(loss)
    return main, startup, {"feeds": {"ids": ids, "labels": labels},
                           "loss": loss, "expert_loads": loads}

