"""Plain reference of the decoder pre-training step of the LFM2-24B-A2B
configuration (``configs/lfm2_24b_a2b.json``, ``model_type`` ``lfm2_moe``),
written from the layer equations and not from the program. Float32
throughout, plain ``jax.numpy``, nothing of the program imported.

    RMS(x; g) = x / sqrt(mean(x^2, last axis) + eps) * g
    h = E[ids]
    layer:  u = RMS(h; conv_norm or attn_norm)
            conv:  z = u Win [d, 3d];  B, C, x = z's three chunks of d
                   p = B * x
                   c[t] = sum_j w[:, j] * p[t - (L - 1) + j], w [d, L], p
                          zero before the sequence's first position
                   h += (C * c) Wout
            full_attention:
                   q, k, v = u Wq, u Wk, u Wv;  q, k = RMS(q; q_norm),
                   RMS(k; k_norm) over each head, then rotated (halves
                   convention, whole head); o = causal softmax attention,
                   scale head^-1/2, query head n on key/value head
                   n // group, no window;  h += o Wo
            m = RMS(h; mlp_norm)
            dense:  h += (silu(m Wgate) * (m Wup)) Wdown
            sparse: s = sigmoid(m Wr) in float32; the k chosen are the
                    largest of s + b (b: state, no gradient); w_e =
                    route_scale * s_e / (sum of the chosen s + eps_r);
                    h += sum over chosen e HELD HERE of w_e Expert_e(m);
                    what the absent experts would add is left out
    loss = mean next-token cross-entropy of RMS(h; final_norm) E^T: the
    embedding itself is the head, one leaf with both gradients
    after the backward, for every sparse layer: load_e = selections of the
    step that fell on router output e (all of them, held or not);
    d = coeff * sign(mean(load) - load); b += d - mean(d)

The convolution is an explicit sum over the taps of a zero-padded array.
The held experts are looped densely over all tokens, each weighted by the
token's weight for it (zero where it is not among the token's chosen): no
sort, no buffer, no grouped matmul.

Memory, as ``trinity_mini.py``: the bias is state that the step returns,
and ``common``'s step keeps a block's new state only where the batch is one
block, so ``row_blocks`` gives the whole batch and ``block_loss`` keeps it
small itself. Each layer is under a ``jax.checkpoint``; inside it the
scores are made one row's block of 256 queries at a time, the dense MLP a
block of tokens at a time and the experts one at a time, each under a
checkpoint of its own; the head and the loss walk the rows in a
``lax.scan``, a row under a checkpoint.
"""

import numpy as np

import jax
import jax.numpy as jnp

from benchmarks import work_moe
from benchmarks.reference.common import normal

QUERY_BLOCK = 256
TOKEN_BLOCK = 4096
HIGHEST = jax.lax.Precision.HIGHEST


def row_blocks(cfg):
    """The whole batch at once: the step keeps the state it returns."""
    return None


def _sparse_layers(m):
    return [i for i in range(m["num_hidden_layers"])
            if m["mlp_layer_types"][i] != "dense"]


def _conv_layers(m):
    return [i for i in range(m["num_hidden_layers"])
            if m["layer_types"][i] == "conv"]


def param_specs(cfg):
    """The program's parameters by the names its builder gives them."""
    m = cfg["model"]
    d, taps = m["hidden_size"], m["conv_L_cache"]
    q = m["num_attention_heads"] * m["head_dim"]
    kv = m["num_key_value_heads"] * m["head_dim"]
    held, width = m["experts_held"], m["moe_intermediate_size"]
    matrix, one = normal(cfg["init"]["normal_std"]), ("const", 1.0)
    spec = {"tok_embedding": ((m["vocab_size"], d), matrix)}
    for i in range(m["num_hidden_layers"]):
        p = "layer%d." % i
        if m["layer_types"][i] == "conv":
            spec[p + "conv_norm"] = ((d,), one)
            spec[p + "conv_in_proj"] = ((d, 3 * d), matrix)
            spec[p + "conv_filter"] = ((d, taps), ("uniform", taps ** -0.5))
            spec[p + "conv_out_proj"] = ((d, d), matrix)
        else:
            spec[p + "attn_norm"] = ((d,), one)
            spec[p + "q_norm"] = ((m["head_dim"],), one)
            spec[p + "k_norm"] = ((m["head_dim"],), one)
            spec[p + "q_proj"] = ((d, q), matrix)
            spec[p + "k_proj"] = ((d, kv), matrix)
            spec[p + "v_proj"] = ((d, kv), matrix)
            spec[p + "o_proj"] = ((q, d), matrix)
        spec[p + "mlp_norm"] = ((d,), one)
        if i in _sparse_layers(m):
            spec[p + "router"] = ((d, m["router_experts"]), matrix)
            spec[p + "experts_gate"] = ((held, d, width), matrix)
            spec[p + "experts_up"] = ((held, d, width), matrix)
            spec[p + "experts_down"] = ((held, width, d), matrix)
        else:
            dense = m["intermediate_size"]
            spec[p + "mlp_gate"] = ((d, dense), matrix)
            spec[p + "mlp_up"] = ((d, dense), matrix)
            spec[p + "mlp_down"] = ((dense, d), matrix)
    spec["final_norm"] = ((d,), one)
    return spec


def state_specs(cfg):
    """Every sparse layer's balancing bias, 0 at the start."""
    m = cfg["model"]
    return {"layer%d.expert_bias" % i: ((m["router_experts"],), 0.0)
            for i in _sparse_layers(m)}


def step_flops(cfg, rows):
    """Model operations of one pre-training step (forward + backward = 3 x
    forward). Per token: on a conv layer the input and output projections
    and the (2 L + 2) d of the two gates and the L taps; on the attention
    layer the Q, K, V and O projections; the dense layer's gated MLP; a
    sparse layer's router and the gated MLP of the token's pairs that fall
    on held experts (``work_moe.pairs_held``: the EXPECTATION, tokens x k
    x held / experts a layer); the attention over the keys a causal query
    sees; the head over the vocabulary held (the tied embedding, as a
    matmul). Embedding look-ups, norms, rotations, softmax, top-k, sort,
    gathers and the bias update are not counted."""
    m = cfg["model"]
    d, seq, taps = m["hidden_size"], m["seq_len"], m["conv_L_cache"]
    tokens, width = rows * seq, m["moe_intermediate_size"]
    q = m["num_attention_heads"] * m["head_dim"]
    kv = m["num_key_value_heads"] * m["head_dim"]
    conv, sparse = len(_conv_layers(m)), len(_sparse_layers(m))
    full = m["num_hidden_layers"] - conv
    per_token = conv * (2 * d * 3 * d + 2 * d * d + (2 * taps + 2) * d)
    per_token += full * (4 * d * q + 4 * d * kv)
    per_token += (m["num_hidden_layers"] - sparse) * 6 * d * m[
        "intermediate_size"]
    per_token += sparse * 2 * d * m["router_experts"]
    per_token += 2 * d * m["vocab_size"]
    pairs = work_moe.pairs_held(tokens, m["num_experts_per_tok"],
                                m["experts_held"], m["router_experts"])
    experts = sparse * sum(work_moe.grouped_matmul_flops(pairs, d, width))
    attention = full * sum(work_moe.masked_attention_flops(
        rows, m["num_attention_heads"], seq, m["head_dim"]))
    return 3 * tokens * per_token + experts + attention


def first_gradient_state(name, cfg):
    """Adam's first moment after one step is (1 - beta1) * g."""
    return name + "_moment1_0", 1.0 / (1.0 - cfg["optimizer"].get("beta1", 0.9))


def make_batch(cfg, rows, rng):
    """Full-length sequences of uniform ids over the vocabulary held, and
    their next-token labels."""
    m = cfg["model"]
    toks = rng.integers(0, m["vocab_size"],
                        (rows, m["seq_len"] + 1)).astype(np.int64)
    return {"ids": toks[:, :-1], "labels": toks[:, 1:]}


def normalisers(batch):
    return {"tokens": batch["labels"].size}


# -- the layers, for the batch x [rows, seq, d] --------------------------------

def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rotate(x, theta):
    """x [rows, seq, heads, head]: x cos + [-x2, x1] sin over the halves,
    at the default frequencies theta^(-2i/head), tables made in float64."""
    seq, head = x.shape[1], x.shape[3]
    inv = float(theta) ** (-2.0 * np.arange(head // 2, dtype=np.float64)
                           / head)
    angle = np.arange(seq, dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(angle), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(angle), jnp.float32)[None, :, None, :]
    x1, x2 = x[..., :head // 2], x[..., head // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _mlp(mm, x, gate, up, down):
    return mm.dot(jax.nn.silu(mm.dot(x, gate)) * mm.dot(x, up), down)


def short_conv(p, w):
    """c[t] = sum_j w[:, j] * p[t - (L - 1) + j] for p [rows, seq, d] and
    w [d, L]: every tap a slice of p with L - 1 zero positions put before
    each sequence."""
    seq, taps = p.shape[1], w.shape[1]
    padded = jnp.pad(p, ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(w[:, j] * padded[:, j:j + seq] for j in range(taps))


def _conv_operator(mm, p, pre, u):
    d = u.shape[-1]
    z = mm.dot(u, p[pre + "conv_in_proj"])
    b, c, x = z[..., :d], z[..., d:2 * d], z[..., 2 * d:]
    return mm.dot(c * short_conv(b * x, p[pre + "conv_filter"]),
                  p[pre + "conv_out_proj"])


def _attention(mm, m, p, pre, u):
    rows, seq, _ = u.shape
    hq, hkv, dh = (m["num_attention_heads"], m["num_key_value_heads"],
                   m["head_dim"])
    eps = m["rms_norm_eps"]
    rope = m["rope_parameters"]["full_attention"]
    assert rope.get("rope_type", "default") == "default", rope
    q = _rms(mm.dot(u, p[pre + "q_proj"]).reshape(rows, seq, hq, dh),
             p[pre + "q_norm"], eps)
    k = _rms(mm.dot(u, p[pre + "k_proj"]).reshape(rows, seq, hkv, dh),
             p[pre + "k_norm"], eps)
    v = mm.dot(u, p[pre + "v_proj"]).reshape(rows, seq, hkv, dh)
    q, k = _rotate(q, rope["rope_theta"]), _rotate(k, rope["rope_theta"])
    block = min(QUERY_BLOCK, seq)
    blocks = seq // block
    # query head n reads key/value head n // (hq // hkv)
    q = q.reshape(rows * blocks, block, hkv, hq // hkv, dh)
    key_pos = jnp.arange(seq)

    @jax.checkpoint
    def one(q_blk, row, first):
        scores = mm.einsum("qngd,knd->ngqk", q_blk, k[row]) * (dh ** -0.5)
        seen = key_pos[None, :] <= (first + jnp.arange(block))[:, None]
        weights = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
        return mm.einsum("ngqk,knd->qngd", weights, v[row])

    o = jax.lax.map(lambda a: one(*a), (
        q, jnp.repeat(jnp.arange(rows), blocks),
        jnp.tile(jnp.arange(blocks) * block, rows)))
    return mm.dot(o.reshape(rows, seq, hq * dh), p[pre + "o_proj"])


def _dense(mm, p, pre, x):
    """The dense layer's MLP over the tokens x [N, d], a block of tokens
    at a time (its three [N, 11776] float32 activations at once would not
    leave the weights room)."""
    block = min(TOKEN_BLOCK, x.shape[0])
    weights = (p[pre + "mlp_gate"], p[pre + "mlp_up"], p[pre + "mlp_down"])
    out = jax.lax.map(jax.checkpoint(lambda blk: _mlp(mm, blk, *weights)),
                      x.reshape(-1, block, x.shape[-1]))
    return out.reshape(x.shape)


def experts(mm, m, p, pre, x, bias):
    """-> (the held experts' part for the tokens x [N, d], the selections
    that fell on each router output [E] int32)."""
    scores = jax.nn.sigmoid(jnp.dot(x, p[pre + "router"], precision=HIGHEST))
    _, chosen = jax.lax.top_k(scores + jax.lax.stop_gradient(bias),
                              m["num_experts_per_tok"])
    mask = jnp.sum(jax.nn.one_hot(chosen, scores.shape[-1]), 1)
    picked = mask * scores
    picked = m["route_scale"] * picked / (
        jnp.sum(picked, -1, keepdims=True) + m["route_norm_eps"])
    first = m.get("expert_offset", 0)
    share = picked[:, first:first + m["experts_held"]]     # [N, held]

    @jax.checkpoint
    def one(gate, up, down, weight):
        return weight[:, None] * _mlp(mm, x, gate, up, down)

    # (the sum is outside the checkpoint: its backward needs no partial sum)
    out, _ = jax.lax.scan(lambda acc, expert: (acc + one(*expert), None),
                          jnp.zeros_like(x),
                          (p[pre + "experts_gate"], p[pre + "experts_up"],
                           p[pre + "experts_down"], share.T))
    return out, jnp.sum(mask, 0).astype(jnp.int32)


def _layer(mm, m, p, bias, layer, h):
    """-> (the layer's output, its router's loads or None on a dense one)."""
    pre, eps = "layer%d." % layer, m["rms_norm_eps"]
    if m["layer_types"][layer] == "conv":
        h = h + _conv_operator(mm, p, pre,
                               _rms(h, p[pre + "conv_norm"], eps))
    else:
        h = h + _attention(mm, m, p, pre, _rms(h, p[pre + "attn_norm"], eps))
    x = _rms(h, p[pre + "mlp_norm"], eps).reshape(-1, h.shape[-1])
    if m["mlp_layer_types"][layer] == "dense":
        f, load = _dense(mm, p, pre, x), None
    else:
        f, load = experts(mm, m, p, pre, x, bias)
    return h + f.reshape(h.shape), load


def update_bias(bias, load, coeff):
    load = load.astype(jnp.float32)
    delta = coeff * jnp.sign(jnp.mean(load) - load)
    return bias + delta - jnp.mean(delta)


def block_loss(p, state, block, norm, cfg, mm):
    """The step's loss over the rows of ``block`` (the whole batch) and the
    state the step leaves: every sparse layer's bias moved by its loads."""
    m = cfg["model"]
    h = p["tok_embedding"][block["ids"].astype(jnp.int32)]
    new_state = {}
    for layer in range(m["num_hidden_layers"]):
        name = "layer%d.expert_bias" % layer
        h, load = jax.checkpoint(
            lambda p_, h_, layer=layer, name=name: _layer(
                mm, m, p_, state.get(name), layer, h_))(p, h)
        if load is not None:
            new_state[name] = update_bias(state[name], load,
                                          m["load_balance_coeff"])

    @jax.checkpoint
    def row_loss(head, h_row, labels):
        logits = mm.dot(_rms(h_row, head["final_norm"], m["rms_norm_eps"]),
                        head["tok_embedding"].T)
        return jnp.sum(jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
            logits, labels.astype(jnp.int32)[:, None], -1)[:, 0])

    head = {n: p[n] for n in ("final_norm", "tok_embedding")}
    loss, _ = jax.lax.scan(
        lambda acc, row: (acc + row_loss(head, *row), None),
        jnp.float32(0.0), (h, block["labels"]))
    return loss / norm["tokens"], new_state
