"""Plain reference of the decoder pre-training step that
``paddle_tpu/models/decoder_lm.py`` builds for the Mellum2 configuration
(``configs/mellum2_12b.json``): pre-norm decoder, RMSNorm, no biases;
grouped-query causal attention, ``sliding_attention`` layers that see the
last ``sliding_window`` keys and ``full_attention`` layers that see all,
rotary embedding over the whole head (halves convention), default tables on
the window layers and YaRN on the full ones; a softmax router over
``router_experts`` in float32, top ``num_experts_per_tok``, weights
renormalised over the chosen; a gated SiLU MLP per expert; final RMSNorm,
untied head, mean next-token cross-entropy. Float32 throughout, plain
``jax.numpy``, nothing of the program imported.

The chip's share, as the configuration states it: experts
``expert_offset .. expert_offset + experts_held`` of ``router_experts`` and
a slice of the vocabulary. The router still ranks all experts and a token's
weights are renormalised over all of its chosen ones; what the experts held
elsewhere would add is left out, and that partial result goes on to the
next layer. Here the held experts are looped densely over all tokens, each
weighted by the token's renormalised probability (zero where the expert is
not among the token's chosen): no sort, no buffer, no grouped matmul.

Memory: one ``jax.checkpoint`` a layer; inside it the scores are made a
block of queries at a time and the experts one at a time, each under a
``jax.checkpoint`` of its own, so that a layer's backward holds one block's
[heads, block, keys] scores and one expert's [tokens, width] intermediates.
Rows are independent but for the loss's mean over all tokens, so the step
is summed over blocks of one sequence (``row_blocks``): beside 9.5 GB of
float32 weights, Adam state and gradients, ``follow`` then holds a second
gradient tree (2.4 GB) and one sequence's activations.
"""

import numpy as np

import jax
import jax.numpy as jnp

from benchmarks import work_moe
from benchmarks.reference.common import normal

QUERY_BLOCK = 512
BLOCK_TOKENS = 4096
HIGHEST = jax.lax.Precision.HIGHEST


def row_blocks(cfg):
    """Rows a block holds: 4096 tokens' worth."""
    return max(1, BLOCK_TOKENS // cfg["model"]["seq_len"])


def param_specs(cfg):
    """The program's parameters by the names its builder gives them."""
    m = cfg["model"]
    d, vocab = m["hidden_size"], m["vocab_size"]
    q = m["num_attention_heads"] * m["head_dim"]
    kv = m["num_key_value_heads"] * m["head_dim"]
    held, width = m["experts_held"], m["moe_intermediate_size"]
    matrix, one = normal(cfg["init"]["normal_std"]), ("const", 1.0)
    spec = {"tok_embedding": ((vocab, d), matrix)}
    for i in range(m["num_hidden_layers"]):
        p = "layer%d." % i
        spec[p + "attn_norm"] = ((d,), one)
        spec[p + "q_proj"] = ((d, q), matrix)
        spec[p + "k_proj"] = ((d, kv), matrix)
        spec[p + "v_proj"] = ((d, kv), matrix)
        spec[p + "o_proj"] = ((q, d), matrix)
        spec[p + "mlp_norm"] = ((d,), one)
        spec[p + "router"] = ((d, m["router_experts"]), matrix)
        spec[p + "experts_gate"] = ((held, d, width), matrix)
        spec[p + "experts_up"] = ((held, d, width), matrix)
        spec[p + "experts_down"] = ((held, width, d), matrix)
    spec["final_norm"] = ((d,), one)
    spec["lm_head"] = ((d, vocab), matrix)
    return spec


def state_specs(cfg):
    return {}


def _window(m, layer):
    return (m["sliding_window"]
            if m["layer_types"][layer] == "sliding_attention" else None)


def step_flops(cfg, rows):
    """Model operations of one pre-training step (forward + backward = 3 x
    forward for a matmul): per token and layer the Q, K, V, O projections,
    the router and the gated MLP of the token's pairs that fall on held
    experts (``work_moe.pairs_held``: the expectation); the attention over
    the keys a query really sees (``work_moe.masked_attention_flops``);
    the head over the vocabulary held. Embedding look-ups, norms, rotary
    embedding, softmax, top-k, sort, gathers are not matmul work."""
    m = cfg["model"]
    d, seq, layers = m["hidden_size"], m["seq_len"], m["num_hidden_layers"]
    tokens = rows * seq
    q = m["num_attention_heads"] * m["head_dim"]
    kv = m["num_key_value_heads"] * m["head_dim"]
    dense = tokens * layers * (4 * d * q + 4 * d * kv
                               + 2 * d * m["router_experts"])
    dense += tokens * 2 * d * m["vocab_size"]
    pairs = work_moe.pairs_held(tokens, m["num_experts_per_tok"],
                                m["experts_held"], m["router_experts"])
    experts = layers * sum(work_moe.grouped_matmul_flops(
        pairs, d, m["moe_intermediate_size"]))
    attention = sum(sum(work_moe.masked_attention_flops(
        rows, m["num_attention_heads"], seq, m["head_dim"],
        _window(m, i))) for i in range(layers))
    return 3 * dense + experts + attention


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


# -- the layers ---------------------------------------------------------------

def rope_tables(head_dim, seq, rope):
    """(cos, sin) [seq, head_dim / 2] of a layer kind's rotary embedding,
    made in float64. ``default``: inv_freq_i = theta^(-2i/d). ``yarn``:
    dim(r) = d ln(L / (2 pi r)) / (2 ln theta); low = floor(dim(beta_fast)),
    high = ceil(dim(beta_slow)), clipped to [0, d - 1]; ramp_i = clip((i -
    low) / (high - low), 0, 1); inv_freq_i = theta^(-2i/d) ((1 - ramp_i) +
    ramp_i / factor); cos and sin times ``attention_factor``."""
    theta, half = float(rope["rope_theta"]), head_dim // 2
    i = np.arange(half, dtype=np.float64)
    inv, factor = theta ** (-2.0 * i / head_dim), 1.0
    if rope["rope_type"] == "yarn":
        length = float(rope["original_max_position_embeddings"])

        def dim(turns):
            return (head_dim * np.log(length / (2 * np.pi * turns))
                    / (2 * np.log(theta)))

        low = max(np.floor(dim(rope["beta_fast"])), 0)
        high = min(np.ceil(dim(rope["beta_slow"])), head_dim - 1)
        ramp = np.clip((i - low) / (high - low), 0, 1)
        inv = inv * ((1 - ramp) + ramp / rope["factor"])
        factor = rope["attention_factor"]
    angle = np.arange(seq, dtype=np.float64)[:, None] * inv[None, :]
    return (jnp.asarray(np.cos(angle) * factor, jnp.float32),
            jnp.asarray(np.sin(angle) * factor, jnp.float32))


def _rope(x, cos, sin):
    """x [rows, seq, heads, d]: x cos + [-x2, x1] sin over the halves."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    cos, sin = cos[None, :, None, :], sin[None, :, None, :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _attention(mm, m, p, pre, x, layer):
    rows, seq, _ = x.shape
    hq, hkv, dh = (m["num_attention_heads"], m["num_key_value_heads"],
                   m["head_dim"])
    kind = m["layer_types"][layer]
    cos, sin = rope_tables(dh, seq, m["rope_parameters"][kind])
    q = _rope(mm.dot(x, p[pre + "q_proj"]).reshape(rows, seq, hq, dh),
              cos, sin)
    k = _rope(mm.dot(x, p[pre + "k_proj"]).reshape(rows, seq, hkv, dh),
              cos, sin)
    v = mm.dot(x, p[pre + "v_proj"]).reshape(rows, seq, hkv, dh)
    window = _window(m, layer)
    block = min(QUERY_BLOCK, seq)
    # query head i reads key/value head i // (hq // hkv)
    q = q.reshape(rows, seq // block, block, hkv, hq // hkv, dh)
    key_pos = jnp.arange(seq)

    @jax.checkpoint
    def one(q_blk, first):
        scores = mm.einsum("bqngd,bknd->bngqk", q_blk, k) * (dh ** -0.5)
        q_pos = first + jnp.arange(block)
        seen = key_pos[None, :] <= q_pos[:, None]
        if window is not None:
            seen &= q_pos[:, None] - key_pos[None, :] < window
        weights = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
        return mm.einsum("bngqk,bknd->bqngd", weights, v)

    ctx = jax.lax.map(lambda a: one(*a),
                      (jnp.moveaxis(q, 1, 0),
                       jnp.arange(seq // block) * block))
    ctx = jnp.moveaxis(ctx, 0, 1).reshape(rows, seq, hq * dh)
    return mm.dot(ctx, p[pre + "o_proj"])


def _experts(mm, m, p, pre, x):
    """The held experts' part of the layer for the tokens x [N, d]."""
    probs = jax.nn.softmax(jnp.dot(x, p[pre + "router"], precision=HIGHEST),
                           -1)
    _, chosen = jax.lax.top_k(probs, m["num_experts_per_tok"])
    picked = jnp.sum(jax.nn.one_hot(chosen, probs.shape[-1]), 1) * probs
    picked = picked / jnp.sum(picked, -1, keepdims=True)
    first = m.get("expert_offset", 0)
    share = picked[:, first:first + m["experts_held"]]     # [N, held]

    @jax.checkpoint
    def one(acc, expert):
        gate, up, down, weight = expert
        hidden = jax.nn.silu(mm.dot(x, gate)) * mm.dot(x, up)
        return acc + weight[:, None] * mm.dot(hidden, down), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(x), (
        p[pre + "experts_gate"], p[pre + "experts_up"],
        p[pre + "experts_down"], share.T))
    return out


def _layer(mm, m, p, layer, x):
    pre, eps = "layer%d." % layer, m["rms_norm_eps"]
    h = x + _attention(mm, m, p, pre, _rms(x, p[pre + "attn_norm"], eps),
                       layer)
    n2 = _rms(h, p[pre + "mlp_norm"], eps)
    return h + _experts(mm, m, p, pre, n2.reshape(-1, n2.shape[-1])
                        ).reshape(h.shape)


def block_loss(p, state, block, norm, cfg, mm):
    """The part of the step's loss that the rows of ``block`` give."""
    m = cfg["model"]
    x = p["tok_embedding"][block["ids"].astype(jnp.int32)]
    for layer in range(m["num_hidden_layers"]):
        x = jax.checkpoint(
            lambda p_, x_, layer=layer: _layer(mm, m, p_, layer, x_))(p, x)
    logits = mm.dot(_rms(x, p["final_norm"], m["rms_norm_eps"]),
                    p["lm_head"])
    label = block["labels"].astype(jnp.int32)
    loss = (jax.nn.logsumexp(logits, -1)
            - jnp.take_along_axis(logits, label[..., None], -1)[..., 0])
    return jnp.sum(loss) / norm["tokens"], state
