"""BERT-base ceiling probe: a hand-written pure-JAX train step at the bench
configuration (batch 64, seq 128, bf16 activations, fp32 master weights,
Adam, MLM + NSP heads, dropout off) — the practical attainable number for
this model formulation on this chip, the BERT analog of
tools/resnet_probe.py.

Usage: python tools/bert_probe.py   (from the repo root, on the chip)
"""
import time

import numpy as np
import jax
import jax.numpy as jnp

V, MAXP = 30522, 512
D, L, H, FF = 768, 12, 12, 3072
B, T = 64, 128
DH = D // H


def init_params(rng):
    p = {}

    def nrm(key, *shape):
        return jnp.asarray(rng.randn(*shape) * 0.02, jnp.float32)

    p["wemb"] = nrm("wemb", V, D)
    p["pemb"] = nrm("pemb", MAXP, D)
    p["semb"] = nrm("semb", 2, D)
    p["emb_ln"] = (jnp.ones((D,)), jnp.zeros((D,)))
    for i in range(L):
        lp = {}
        for n in ("q", "k", "v", "o"):
            lp[n] = nrm(n, D, D)
        lp["ff1"], lp["ff1b"] = nrm("f1", D, FF), jnp.zeros((FF,))
        lp["ff2"], lp["ff2b"] = nrm("f2", FF, D), jnp.zeros((D,))
        lp["ln1"] = (jnp.ones((D,)), jnp.zeros((D,)))
        lp["ln2"] = (jnp.ones((D,)), jnp.zeros((D,)))
        p["layer%d" % i] = lp
    p["mlm_w"], p["mlm_b"] = nrm("mw", D, D), jnp.zeros((D,))
    p["mlm_ln"] = (jnp.ones((D,)), jnp.zeros((D,)))
    p["mlm_out"], p["mlm_ob"] = nrm("mo", D, V), jnp.zeros((V,))
    p["pool_w"], p["pool_b"] = nrm("pw", D, D), jnp.zeros((D,))
    p["nsp_w"], p["nsp_b"] = nrm("nw", D, 2), jnp.zeros((2,))
    return p


def ln(x, gb):
    g, b = gb
    x32 = x.astype(jnp.float32)
    m = jnp.mean(x32, -1, keepdims=True)
    v = jnp.mean(jnp.square(x32 - m), -1, keepdims=True)
    return ((x32 - m) * jax.lax.rsqrt(v + 1e-5) * g + b).astype(x.dtype)


def bf(x):
    return x.astype(jnp.bfloat16)


def forward(p, batch):
    ids, pos, sent, mlab, mw, nslab = batch
    x = (p["wemb"][ids] + p["pemb"][pos] + p["semb"][sent])
    x = bf(ln(x, p["emb_ln"]))
    for i in range(L):
        lp = p["layer%d" % i]
        q = (x @ bf(lp["q"])).reshape(B, T, H, DH).transpose(0, 2, 1, 3)
        k = (x @ bf(lp["k"])).reshape(B, T, H, DH).transpose(0, 2, 1, 3)
        v = (x @ bf(lp["v"])).reshape(B, T, H, DH).transpose(0, 2, 1, 3)
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * (DH ** -0.5)
        w = jax.nn.softmax(s.astype(jnp.float32), -1).astype(x.dtype)
        c = jnp.einsum("bhqk,bhkd->bhqd", w, v).transpose(0, 2, 1, 3)
        c = c.reshape(B, T, D) @ bf(lp["o"])
        x = bf(ln(x + c, lp["ln1"]))
        f = jax.nn.gelu(x @ bf(lp["ff1"]) + bf(lp["ff1b"]))
        f = f @ bf(lp["ff2"]) + bf(lp["ff2b"])
        x = bf(ln(x + f, lp["ln2"]))
    mh = ln(jax.nn.gelu(x @ bf(p["mlm_w"]) + bf(p["mlm_b"])), p["mlm_ln"])
    logits = (mh @ bf(p["mlm_out"]) + bf(p["mlm_ob"])).astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, -1)
    ll = jnp.take_along_axis(logits, mlab[..., None], -1)[..., 0]
    mlm = jnp.sum((lse - ll) * mw) / (jnp.sum(mw) + 1e-6)
    pooled = jnp.tanh(x[:, 0].astype(jnp.float32) @ p["pool_w"]
                      + p["pool_b"])
    nl = pooled @ p["nsp_w"] + p["nsp_b"]
    nsp = jnp.mean(jax.nn.logsumexp(nl, -1)
                   - jnp.take_along_axis(nl, nslab[:, None], -1)[:, 0])
    return mlm + nsp


def adam_update(p, g, m, v, t, lr=1e-4, b1=0.9, b2=0.999, eps=1e-8):
    m = jax.tree.map(lambda a, b: b1 * a + (1 - b1) * b, m, g)
    v = jax.tree.map(lambda a, b: b2 * a + (1 - b2) * jnp.square(b), v, g)
    bc1, bc2 = 1 - b1 ** t, 1 - b2 ** t
    p = jax.tree.map(
        lambda w, mm, vv: w - lr * (mm / bc1) / (jnp.sqrt(vv / bc2) + eps),
        p, m, v)
    return p, m, v


@jax.jit
def step(p, m, v, t, batch):
    loss, g = jax.value_and_grad(forward)(p, batch)
    p, m, v = adam_update(p, g, m, v, t)
    return p, m, v, t + 1, loss


def main():
    print("backend:", jax.default_backend())
    rng = np.random.RandomState(0)
    p = init_params(rng)
    m = jax.tree.map(jnp.zeros_like, p)
    v = jax.tree.map(jnp.zeros_like, p)
    t = jnp.float32(1)
    batch = (
        jnp.asarray(rng.randint(0, V, (B, T)), jnp.int32),
        jnp.asarray(np.tile(np.arange(T), (B, 1)), jnp.int32),
        jnp.zeros((B, T), jnp.int32),
        jnp.asarray(rng.randint(0, V, (B, T)), jnp.int32),
        jnp.asarray(rng.rand(B, T) < 0.15, jnp.float32),
        jnp.asarray(rng.randint(0, 2, (B,)), jnp.int32),
    )
    for _ in range(3):
        p, m, v, t, loss = step(p, m, v, t, batch)
    jax.device_get(loss)
    steps = 10
    t0 = time.perf_counter()
    for _ in range(steps):
        p, m, v, t, loss = step(p, m, v, t, batch)
    jax.device_get(loss)
    dt = time.perf_counter() - t0
    sps = B * steps / dt
    gflop = 6 * 110e6 * T / 1e9  # ~6*params*tokens fwd+bwd
    print("probe: %.1f samples/s  (~%.1f TFLOP/s, %.1f%% of 197 bf16 peak)"
          % (sps, sps * gflop / 1e3, sps * gflop / 1e3 / 197 * 100))


if __name__ == "__main__":
    main()
