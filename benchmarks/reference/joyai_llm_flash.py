"""Plain reference of the decoder pre-training step of the JoyAI-LLM-Flash
configuration (``configs/joyai_llm_flash.json``, ``model_type``
``joyai_llm_flash``, DeepSeek-V3's layout), written from the layer
equations and not from the program. Float32 throughout, plain
``jax.numpy``, nothing of the program imported.

    RMS(x; g) = x / sqrt(mean(x^2, last axis) + eps) * g
    h = E[ids]                                        (no scale)
    layer:  u = RMS(h; attn_norm)
            c_q = RMS(u W_DQ; q_a_norm)               [q_lora_rank]
            [q_nope | q_pe] = c_q W_UQ                N + R a head
            [c_kv | k_pe] = u W_DKV                   [kv_lora_rank | R]
            [k_nope | v] = RMS(c_kv; kv_a_norm) W_UKV N + V a head
            q_pe, k_pe rotated at positions 0..T-1; k_pe one head, the
                same for every head (every head's k and v made here)
            q = [q_nope | q_pe], k = [k_nope | k_pe]
            o = causal softmax(q k^T / sqrt(N + R)) v  V wide a head
            h += o W_O
            m = RMS(h; mlp_norm)
            dense:  h += (silu(m Wgate) * (m Wup)) Wdown
            sparse: s = sigmoid(m Wr) in float32; the k chosen are the
                    largest of s + b (b: state, no gradient); w_e =
                    route_scale * s_e / (sum of the chosen s + 1e-20);
                    h += Shared(m) + sum over chosen e HELD HERE of
                    w_e Expert_e(m); what the absent experts would add is
                    left out, and so is what a held expert would add for
                    a token past its capacity: ceil(N x capacity factor x
                    k / E) of the N tokens of the batch, the first in
                    token order (rows, then positions)
    CE_main = mean next-token cross-entropy of RMS(h; final_norm) Whead
    MTP, depth 1, from h before the final norm:
            h' = [RMS(E[t_{i+1}]; mtp.enorm) ; RMS(h; mtp.hnorm)] W_eh
            h' = the sparse layer above on h', its leaves ``mtp.*``
            CE_mtp = mean over i = 0..T-2 of the cross-entropy of
                     RMS(h'_i; mtp.final_norm) Whead against t_{i+2}
    loss = CE_main + loss_weight * CE_mtp
    after the backward, for every sparse layer (the MTP module's too):
    load_e = selections of the step that fell on router output e (all of
    them, held or not); d = coeff * sign(mean(load) - load);
    b += d - mean(d)

**The rotation is the published form** (``rope_interleave``): de-interleave
the R rotary columns (``view(R/2, 2).transpose``: the even columns, then
the odd) and rotate halves, x cos + [-x2, x1] sin at theta^(-2i/R). It
leaves q_pe and k_pe in the de-interleaved order, the same for both, so
q.k is that of the rotation by interleaved pairs that the program makes in
place (``tests/test_joyai.py``).

The held experts are looped densely over all tokens, each weighted by the
token's weight for it (zero where it is not among the token's chosen): no
sort, no buffer, no grouped matmul.

Departures from the published model, each in both program and reference:
the experts and vocabulary rows not held here, the layers past the fifth,
and the exchange, are left out (the configuration's ``deployment``); a
held expert takes no more than its balanced share of the batch's pairs
(``expert_capacity_factor`` 1), so that the routers, which would otherwise
learn that only the held experts add anything, cannot pile tokens onto
them; ``n_group`` = ``topk_group`` = 1 is no grouping of the router.

Memory, as ``trinity_mini.py``: the bias is state that the step returns, so
``row_blocks`` gives the whole batch and ``block_loss`` keeps it small
itself. Each layer is under a ``jax.checkpoint``; inside it the scores are
made one row's block of 256 queries at a time and the experts one at a
time, each under a checkpoint of its own; the heads and the losses walk
the rows in a ``lax.scan``, a row under a checkpoint.
"""

import math

import numpy as np

import jax
import jax.numpy as jnp

from benchmarks import work_mla, work_moe
from benchmarks.reference.common import normal

QUERY_BLOCK = 256
HIGHEST = jax.lax.Precision.HIGHEST
MTP = "mtp."


def row_blocks(cfg):
    """The whole batch at once: the step keeps the state it returns."""
    return None


def _sparse(m, layer):
    return m["mlp_layer_types"][layer] != "dense"


def _prefixes(m):
    """(prefix, sparse) of every decoder layer, the MTP module's last."""
    layers = [("layer%d." % i, _sparse(m, i))
              for i in range(m["num_hidden_layers"])]
    return layers + [(MTP, True)] if m.get("mtp") else layers


def param_specs(cfg):
    """The program's parameters by the names its builder gives them."""
    m = cfg["model"]
    d, vocab, heads, a = (m["hidden_size"], m["vocab_size"],
                          m["num_attention_heads"], m["mla"])
    held, width = m["experts_held"], m["moe_intermediate_size"]
    shared = m["num_shared_experts"] * width
    nope, rope, v = a["qk_nope_head_dim"], a["qk_rope_head_dim"], a[
        "v_head_dim"]
    matrix, one = normal(cfg["init"]["normal_std"]), ("const", 1.0)
    spec = {"tok_embedding": ((vocab, d), matrix)}

    def layer(p, sparse):
        spec[p + "attn_norm"] = ((d,), one)
        spec[p + "q_a_proj"] = ((d, a["q_lora_rank"]), matrix)
        spec[p + "q_a_norm"] = ((a["q_lora_rank"],), one)
        spec[p + "kv_a_proj"] = ((d, a["kv_lora_rank"] + rope), matrix)
        spec[p + "kv_a_norm"] = ((a["kv_lora_rank"],), one)
        spec[p + "q_b_proj"] = ((a["q_lora_rank"], heads * (nope + rope)),
                                matrix)
        spec[p + "kv_b_proj"] = ((a["kv_lora_rank"], heads * (nope + v)),
                                 matrix)
        spec[p + "o_proj"] = ((heads * v, d), matrix)
        spec[p + "mlp_norm"] = ((d,), one)
        if not sparse:
            dense = m["intermediate_size"]
            spec[p + "mlp_gate"] = ((d, dense), matrix)
            spec[p + "mlp_up"] = ((d, dense), matrix)
            spec[p + "mlp_down"] = ((dense, d), matrix)
            return
        spec[p + "router"] = ((d, m["router_experts"]), matrix)
        spec[p + "experts_gate"] = ((held, d, width), matrix)
        spec[p + "experts_up"] = ((held, d, width), matrix)
        spec[p + "experts_down"] = ((held, width, d), matrix)
        spec[p + "shared_gate"] = ((d, shared), matrix)
        spec[p + "shared_up"] = ((d, shared), matrix)
        spec[p + "shared_down"] = ((shared, d), matrix)

    for i in range(m["num_hidden_layers"]):
        layer("layer%d." % i, _sparse(m, i))
    spec["final_norm"] = ((d,), one)
    spec["lm_head"] = ((d, vocab), matrix)
    if m.get("mtp"):
        spec[MTP + "enorm"] = ((d,), one)
        spec[MTP + "hnorm"] = ((d,), one)
        spec[MTP + "eh_proj"] = ((2 * d, d), matrix)
        layer(MTP, True)
        spec[MTP + "final_norm"] = ((d,), one)
    return spec


def state_specs(cfg):
    """Every sparse layer's balancing bias, the MTP module's too, 0 at the
    start."""
    m = cfg["model"]
    return {p + "expert_bias": ((m["router_experts"],), 0.0)
            for p, sparse in _prefixes(m) if sparse}


def step_flops(cfg, rows):
    """Model operations of one pre-training step (forward + backward = 3 x
    forward for a matmul). Per token and layer, the MTP module's among
    them: the five projections of latent attention (W_DQ, W_UQ, W_DKV,
    W_UKV, W_O); on the dense layer its gated MLP; on a sparse layer the
    router, the shared expert's gated MLP and the gated MLP of the token's
    pairs that fall on held experts (``work_moe.pairs_held``: the
    expectation, which the held experts' capacity equals at a factor of 1:
    ``held_pairs``). The attention over the keys a causal query sees at q.k
    N + R and values V (``work_mla.mla_attention_flops``); ``eh_proj``;
    the shared head over the vocabulary held, for every position and
    again for the T-1 that the MTP module predicts. Embedding look-ups,
    norms, rotations, softmax, top-k, sort, gathers and the bias update
    are not matmul work."""
    m = cfg["model"]
    d, seq, heads, a = (m["hidden_size"], m["seq_len"],
                        m["num_attention_heads"], m["mla"])
    tokens, width = rows * seq, m["moe_intermediate_size"]
    nope, rope, v = a["qk_nope_head_dim"], a["qk_rope_head_dim"], a[
        "v_head_dim"]
    layers = _prefixes(m)
    sparse = sum(s for _, s in layers)
    mla = 2 * (d * a["q_lora_rank"] + a["q_lora_rank"] * heads * (nope + rope)
               + d * (a["kv_lora_rank"] + rope)
               + a["kv_lora_rank"] * heads * (nope + v) + heads * v * d)
    per_token = len(layers) * mla
    per_token += (len(layers) - sparse) * 6 * d * m["intermediate_size"]
    per_token += sparse * (2 * d * m["router_experts"]
                           + 6 * d * m["num_shared_experts"] * width)
    head_tokens = tokens
    if m.get("mtp"):
        per_token += 2 * (2 * d) * d
        head_tokens += rows * (seq - 1)
    pairs = held_pairs(m, tokens)
    experts = sparse * sum(work_moe.grouped_matmul_flops(pairs, d, width))
    attention = len(layers) * sum(work_mla.mla_attention_flops(
        rows, heads, seq, nope + rope, v))
    return (3 * tokens * per_token + 3 * head_tokens * 2 * d * m["vocab_size"]
            + experts + attention)


def capacity(m, tokens):
    """The pairs a held expert takes of ``tokens`` at most (None: all)."""
    factor = m.get("expert_capacity_factor")
    if not factor:
        return None
    return math.ceil(round(
        tokens * factor * m["num_experts_per_tok"] / m["router_experts"], 6))


def held_pairs(m, tokens):
    """The pairs of an expert layer on the held experts in expectation,
    no more than their capacities allow."""
    pairs = work_moe.pairs_held(tokens, m["num_experts_per_tok"],
                                m["experts_held"], m["router_experts"])
    cap = capacity(m, tokens)
    return pairs if cap is None else min(pairs, m["experts_held"] * cap)


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
    rows, seq = batch["labels"].shape
    return {"tokens": rows * seq, "mtp_tokens": rows * (seq - 1)}


# -- the layers, for the batch x [rows, seq, d] --------------------------------

def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def rotate_published(x, theta):
    """x [rows, seq, heads, R] as the published modelling rotates it under
    ``rope_interleave``: de-interleaved (the even columns, then the odd),
    then x cos + [-x2, x1] sin over the halves at theta^(-2i/R), tables
    made in float64."""
    seq, r = x.shape[1], x.shape[3]
    x = x.reshape(x.shape[:3] + (r // 2, 2)).swapaxes(-1, -2).reshape(
        x.shape)
    inv = float(theta) ** (-2.0 * np.arange(r // 2, dtype=np.float64) / r)
    angle = np.arange(seq, dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(angle), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(angle), jnp.float32)[None, :, None, :]
    x1, x2 = x[..., :r // 2], x[..., r // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _mlp(mm, x, gate, up, down):
    return mm.dot(jax.nn.silu(mm.dot(x, gate)) * mm.dot(x, up), down)


def _attention(mm, m, p, pre, u):
    rows, seq, _ = u.shape
    heads, a, eps = m["num_attention_heads"], m["mla"], m["rms_norm_eps"]
    nope, r, v = a["qk_nope_head_dim"], a["qk_rope_head_dim"], a[
        "v_head_dim"]
    theta = m["rope_parameters"]["full_attention"]["rope_theta"]
    cq = _rms(mm.dot(u, p[pre + "q_a_proj"]), p[pre + "q_a_norm"], eps)
    q = mm.dot(cq, p[pre + "q_b_proj"]).reshape(rows, seq, heads, nope + r)
    ckv = mm.dot(u, p[pre + "kv_a_proj"])
    k_pe = rotate_published(ckv[..., None, a["kv_lora_rank"]:], theta)
    kv = mm.dot(_rms(ckv[..., :a["kv_lora_rank"]], p[pre + "kv_a_norm"],
                     eps), p[pre + "kv_b_proj"]).reshape(rows, seq, heads,
                                                         nope + v)
    q = jnp.concatenate([q[..., :nope],
                         rotate_published(q[..., nope:], theta)], -1)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
        k_pe, (rows, seq, heads, r))], -1)
    vals = kv[..., nope:]
    block = min(QUERY_BLOCK, seq)
    blocks = seq // block
    q = q.reshape(rows * blocks, block, heads, nope + r)
    key_pos = jnp.arange(seq)

    @jax.checkpoint
    def one(q_blk, row, first):
        scores = mm.einsum("qhd,khd->hqk", q_blk, k[row]) * (
            (nope + r) ** -0.5)
        seen = key_pos[None, :] <= (first + jnp.arange(block))[:, None]
        weights = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
        return mm.einsum("hqk,khd->qhd", weights, vals[row])

    o = jax.lax.map(lambda args: one(*args), (
        q, jnp.repeat(jnp.arange(rows), blocks),
        jnp.tile(jnp.arange(blocks) * block, rows)))
    return mm.dot(o.reshape(rows, seq, heads * v), p[pre + "o_proj"])


def experts(mm, m, p, pre, x, bias):
    """-> (shared expert + the held experts' part for the tokens x [N, d],
    the selections that fell on each router output [E] int32)."""
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

    @jax.checkpoint
    def one(gate, up, down, weight):
        return weight[:, None] * _mlp(mm, x, gate, up, down)

    out = _mlp(mm, x, p[pre + "shared_gate"], p[pre + "shared_up"],
               p[pre + "shared_down"])
    # (the sum is outside the checkpoint: its backward needs no partial sum)
    out, _ = jax.lax.scan(lambda acc, expert: (acc + one(*expert), None), out,
                          (p[pre + "experts_gate"], p[pre + "experts_up"],
                           p[pre + "experts_down"], share.T))
    return out, jnp.sum(mask, 0).astype(jnp.int32)


def _layer(mm, m, p, bias, pre, sparse, h):
    """-> (the layer's output, its router's loads or None on a dense one)."""
    eps = m["rms_norm_eps"]
    h = h + _attention(mm, m, p, pre, _rms(h, p[pre + "attn_norm"], eps))
    x = _rms(h, p[pre + "mlp_norm"], eps)
    if not sparse:
        return h + _mlp(mm, x, p[pre + "mlp_gate"], p[pre + "mlp_up"],
                        p[pre + "mlp_down"]), None
    f, load = experts(mm, m, p, pre, x.reshape(-1, x.shape[-1]), bias)
    return h + f.reshape(x.shape), load


def update_bias(bias, load, coeff):
    load = load.astype(jnp.float32)
    delta = coeff * jnp.sign(jnp.mean(load) - load)
    return bias + delta - jnp.mean(delta)


def _summed_cross_entropy(mm, m, p, norm, h, labels):
    """The cross-entropy summed over the rows of h [rows, n, d] against
    labels [rows, n], the head RMS(.; norm) Whead one row at a time."""
    @jax.checkpoint
    def row_loss(head, h_row, labels_row):
        logits = mm.dot(_rms(h_row, head[0], m["rms_norm_eps"]), head[1])
        return jnp.sum(jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
            logits, labels_row.astype(jnp.int32)[:, None], -1)[:, 0])

    loss, _ = jax.lax.scan(
        lambda acc, row: (acc + row_loss((p[norm], p["lm_head"]), *row),
                          None), jnp.float32(0.0), (h, labels))
    return loss


def block_loss(p, state, block, norm, cfg, mm):
    """The step's loss over the rows of ``block`` (the whole batch) and the
    state the step leaves: every sparse layer's bias moved by its loads."""
    m = cfg["model"]
    eps = m["rms_norm_eps"]
    labels = block["labels"]
    new_state = {}

    def run(pre, sparse, h):
        name = pre + "expert_bias"
        h, load = jax.checkpoint(
            lambda p_, h_: _layer(mm, m, p_, state.get(name), pre, sparse,
                                  h_))(p, h)
        if load is not None:
            new_state[name] = update_bias(state[name], load,
                                          m["load_balance_coeff"])
        return h

    h = p["tok_embedding"][block["ids"].astype(jnp.int32)]
    for pre, sparse in _prefixes(m):
        if pre != MTP:
            h = run(pre, sparse, h)
    loss = _summed_cross_entropy(mm, m, p, "final_norm", h, labels) / norm[
        "tokens"]
    if not m.get("mtp"):
        return loss, new_state
    e = p["tok_embedding"][labels.astype(jnp.int32)]
    joined = jnp.concatenate([_rms(e, p[MTP + "enorm"], eps),
                              _rms(h, p[MTP + "hnorm"], eps)], -1)
    h = run(MTP, True, mm.dot(joined, p[MTP + "eh_proj"]))
    mtp = _summed_cross_entropy(mm, m, p, MTP + "final_norm", h[:, :-1],
                                labels[:, 1:]) / norm["mtp_tokens"]
    return loss + m["mtp"]["loss_weight"] * mtp, new_state
