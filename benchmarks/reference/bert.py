"""Plain reference of the BERT pre-training step the program builds
(``paddle_tpu/models/bert.py`` with ``models/transformer.py``): post-LN
encoder, exact gelu, no bias on Q/K/V/O, masked-LM head (dense + gelu +
LayerNorm + vocabulary projection) weighted over all positions, tanh pooler
and next-sentence head, loss = MLM mean + NSP mean, dropout 0.

It started from ``tools/bert_probe.py`` (hand-written pure-JAX step) and
departs from it where the probe departs from the program: float32
throughout, sizes from the configuration, the program's parameter names,
the published initialisation (every matrix and embedding normal with the
configuration's ``init.normal_std``; LayerNorm 1/0; biases 0; without that
key the program's own default, Xavier uniform by shape) and key masking
by ``seq_lens``. It imports nothing of the program.

A row of the batch is independent of the others but for two whole-batch
normalisers (the sum of the mask weights, the row count), so the loss and
its gradient are summed over blocks of 2048 tokens (one row at 2048
positions, whose float32 scores are 201 MB a layer; eight rows would take
1.6 GB).
"""

import numpy as np

import jax
import jax.numpy as jnp

from benchmarks import work
from benchmarks.reference.common import normal, xavier_uniform

LN_EPS = 1e-5
BLOCK_TOKENS = 2048


def row_blocks(cfg):
    """Rows a block holds: 2048 tokens' worth."""
    return max(1, BLOCK_TOKENS // cfg["model"]["seq_len"])


def _names(cfg):
    """The program's parameter names, in the order its layers are built
    (``unique_name`` counts fc and layer_norm from 0 in a fresh process)."""
    m = cfg["model"]
    d, inner, vocab = m["d_model"], m["d_inner"], m["vocab_size"]
    fc, ln = iter(range(10 ** 6)), iter(range(10 ** 6))
    spec = {}

    std = cfg.get("init", {}).get("normal_std")

    def matrix(name, shape):
        spec[name] = (shape, normal(std) if std else xavier_uniform(shape))

    def dense(shape, bias):
        i = next(fc)
        matrix("fc_%d.w_0_0" % i, shape)
        if bias:
            spec["fc_%d.b_0_0" % i] = ((shape[1],), ("const", 0.0))
        return i

    def layer_norm():
        i = next(ln)
        spec["layer_norm_%d.w_0_0" % i] = ((d,), ("const", 1.0))
        spec["layer_norm_%d.b_0_0" % i] = ((d,), ("const", 0.0))
        return i

    matrix("word_embedding", (vocab, d))
    matrix("pos_embedding", (m["max_position"], d))
    matrix("sent_embedding", (m.get("type_vocab_size", 2), d))
    layout = {"emb_ln": layer_norm(), "layers": []}
    for _ in range(m["n_layers"]):
        layer = {"q": dense((d, d), False), "k": dense((d, d), False),
                 "v": dense((d, d), False), "o": dense((d, d), False)}
        layer["ln1"] = layer_norm()
        layer["ff1"] = dense((d, inner), True)
        layer["ff2"] = dense((inner, d), True)
        layer["ln2"] = layer_norm()
        layout["layers"].append(layer)
    layout["mlm"] = dense((d, d), True)
    layout["mlm_ln"] = layer_norm()
    layout["mlm_out"] = dense((d, vocab), True)
    layout["pool"] = dense((d, d), True)
    layout["nsp"] = dense((d, 2), True)
    return spec, layout


def param_specs(cfg):
    return _names(cfg)[0]


def state_specs(cfg):
    return {}


def step_flops(cfg, rows):
    """Model operations of one pre-training step (forward + backward = 3 x
    forward for a matmul): per token and layer the Q, K, V, O projections
    (8 d^2) and the feed-forward (4 d d_inner); the attention
    (``work.attention_flops``); the MLM dense (2 d^2) and the vocabulary
    projection (2 d V) over all positions. Embedding look-ups, LayerNorm,
    softmax and the pooled NSP head (one token a row) are not matmul work
    worth counting."""
    m = cfg["model"]
    d, inner, seq = m["d_model"], m["d_inner"], m["seq_len"]
    tokens = rows * seq
    dense = tokens * m["n_layers"] * (8 * d * d + 4 * d * inner)
    fwd, bwd = work.attention_flops(rows, m["n_heads"], seq,
                                    d // m["n_heads"])
    head = tokens * (2 * d * d + 2 * d * m["vocab_size"])
    return 3 * (dense + head) + m["n_layers"] * (fwd + bwd)


def first_gradient_state(name, cfg):
    """Where the program's optimizer keeps what gives the first gradient
    back: Adam's first moment after one step is (1 - beta1) * g."""
    return name + "_moment1_0", 1.0 / (1.0 - cfg["optimizer"].get("beta1", 0.9))


def make_batch(cfg, rows, rng):
    """Full-length sequences of uniform ids, 15% of the positions weighted
    into the masked-LM loss, a coin for the next-sentence label (the
    arithmetic of ``models.bert.make_fake_batch``, copied: the generator
    is part of the yardstick)."""
    m = cfg["model"]
    seq, vocab = m["seq_len"], m["vocab_size"]
    src = rng.integers(0, vocab, (rows, seq)).astype(np.int64)
    return {
        "src_ids": src,
        "pos_ids": np.tile(np.arange(seq, dtype=np.int64), (rows, 1)),
        "sent_ids": np.zeros((rows, seq), np.int64),
        "seq_lens": np.full((rows, 1), seq, np.int64),
        "mask_label": src.copy(),
        "mask_weight": (rng.random((rows, seq))
                        < cfg["traffic"].get("mask_frac", 0.15)
                        ).astype(np.float32),
        "ns_label": rng.integers(0, 2, (rows, 1)).astype(np.int64),
    }


def normalisers(batch):
    return {"mask_sum": jnp.sum(batch["mask_weight"]) + 1e-6,
            "rows": batch["ns_label"].shape[0]}


def _ln(x, p, i):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return ((x - mean) * jax.lax.rsqrt(var + LN_EPS)
            * p["layer_norm_%d.w_0_0" % i] + p["layer_norm_%d.b_0_0" % i])


def _dense(mm, x, p, i, bias=True):
    y = mm.dot(x, p["fc_%d.w_0_0" % i])
    return y + p["fc_%d.b_0_0" % i] if bias else y


def _cross_entropy(logits, label):
    lse = jax.nn.logsumexp(logits, -1)
    return lse - jnp.take_along_axis(logits, label[..., None], -1)[..., 0]


def _encoder_layer(mm, heads, p, layer, x, lens):
    rows, seq, d = x.shape
    dh = d // heads

    def split(i):
        y = _dense(mm, x, p, i, bias=False)
        return y.reshape(rows, seq, heads, dh).transpose(0, 2, 1, 3)

    q, k, v = split(layer["q"]), split(layer["k"]), split(layer["v"])
    scores = mm.einsum("bhqd,bhkd->bhqk", q, k) * (dh ** -0.5)
    keep = jnp.arange(seq)[None, None, None, :] < lens[:, None, None, None]
    weights = jax.nn.softmax(jnp.where(keep, scores, -jnp.inf), -1)
    ctx = mm.einsum("bhqk,bhkd->bhqd", weights, v)
    ctx = ctx.transpose(0, 2, 1, 3).reshape(rows, seq, d)
    x = _ln(x + _dense(mm, ctx, p, layer["o"], bias=False), p, layer["ln1"])
    f = jax.nn.gelu(_dense(mm, x, p, layer["ff1"]), approximate=False)
    return _ln(x + _dense(mm, f, p, layer["ff2"]), p, layer["ln2"])


def block_loss(p, state, block, norm, cfg, mm):
    """The part of the step's loss that the rows of ``block`` give."""
    heads = cfg["model"]["n_heads"]
    layout = _names(cfg)[1]
    ids = block["src_ids"].astype(jnp.int32)
    x = (p["word_embedding"][ids]
         + p["pos_embedding"][block["pos_ids"].astype(jnp.int32)]
         + p["sent_embedding"][block["sent_ids"].astype(jnp.int32)])
    x = _ln(x, p, layout["emb_ln"])
    lens = block["seq_lens"].reshape(-1).astype(jnp.int32)
    for layer in layout["layers"]:
        x = jax.checkpoint(
            lambda p_, x_, layer=layer: _encoder_layer(
                mm, heads, p_, layer, x_, lens))(p, x)
    h = jax.nn.gelu(_dense(mm, x, p, layout["mlm"]), approximate=False)
    logits = _dense(mm, _ln(h, p, layout["mlm_ln"]), p, layout["mlm_out"])
    mlm = _cross_entropy(logits, block["mask_label"].astype(jnp.int32))
    mlm = jnp.sum(mlm * block["mask_weight"]) / norm["mask_sum"]
    pooled = jnp.tanh(_dense(mm, x[:, 0], p, layout["pool"]))
    nsp = _cross_entropy(_dense(mm, pooled, p, layout["nsp"]),
                         block["ns_label"].reshape(-1).astype(jnp.int32))
    return mlm + jnp.sum(nsp) / norm["rows"], state
