"""ResNet-50 ceiling probe: hand-written pure-JAX train step at the bench
configuration (batch 512, bf16 activations, fp32 master weights) — the
attainable number for this formulation on this chip.

Variants:
  bare      : plain SGD, no BN running stats (round 2's probe definition)
  full      : momentum + L2 weight decay + BN running-stat updates — what
              the fluid program actually computes, the fair engine ceiling
  full-nhwc : `full` with channels-last activations (NHWC) and HWIO
              filters end-to-end — the layout question, answered on
              hardware rather than by folklore

Timing: 30 chained steps (params donated, so steps pipeline with a data
dependency) drained once — long enough that the one drain is a small
fraction of the window.

Usage: python tools/resnet_probe.py   (from the repo root, on the chip)
"""
import time
from functools import partial

import numpy as np
import jax
import jax.numpy as jnp

B = 512
DEPTHS = [3, 4, 6, 3]
WIDTHS = [256, 512, 1024, 2048]


def conv(x, w, stride=1, pad=None, nhwc=False):
    kh = w.shape[0] if nhwc else w.shape[2]
    p = (kh - 1) // 2 if pad is None else pad
    dn = ("NHWC", "HWIO", "NHWC") if nhwc else ("NCHW", "OIHW", "NCHW")
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), [(p, p), (p, p)],
        dimension_numbers=jax.lax.conv_dimension_numbers(
            x.shape, w.shape, dn))


def bn_apply(x, p, running, train, nhwc=False, momentum=0.9, eps=1e-5):
    scale, bias = p
    rm, rv = running
    axes = (0, 1, 2) if nhwc else (0, 2, 3)
    sh = (1, 1, 1, -1) if nhwc else (1, -1, 1, 1)
    x32 = x.astype(jnp.float32)
    if train:
        mean = jnp.mean(x32, axes)
        var = jnp.mean(jnp.square(x32), axes) - jnp.square(mean)
        new_running = (momentum * rm + (1 - momentum) * mean,
                       momentum * rv + (1 - momentum) * var)
    else:
        mean, var = rm, rv
        new_running = running
    y = (x32 - mean.reshape(sh)) * jax.lax.rsqrt(var.reshape(sh) + eps)
    y = y * scale.reshape(sh) + bias.reshape(sh)
    return y.astype(x.dtype), new_running


def init(rng, nhwc=False):
    params, bns = {}, {}

    def w(name, o, i, k):
        arr = rng.randn(o, i, k, k) * np.sqrt(2.0 / (i * k * k))
        if nhwc:
            arr = arr.transpose(2, 3, 1, 0)          # OIHW -> HWIO
        params[name] = jnp.asarray(arr, jnp.float32)

    def bn(name, c):
        params[name + "_bn"] = (jnp.ones((c,)), jnp.zeros((c,)))
        bns[name + "_bn"] = (jnp.zeros((c,)), jnp.ones((c,)))

    w("stem", 64, 3, 7); bn("stem", 64)
    cin = 64
    for si, (n, width) in enumerate(zip(DEPTHS, WIDTHS)):
        mid = width // 4
        for bi in range(n):
            pre = "s%db%d" % (si, bi)
            w(pre + "_1", mid, cin, 1); bn(pre + "_1", mid)
            w(pre + "_2", mid, mid, 3); bn(pre + "_2", mid)
            w(pre + "_3", width, mid, 1); bn(pre + "_3", width)
            if cin != width:
                w(pre + "_sc", width, cin, 1); bn(pre + "_sc", width)
            cin = width
    params["fc"] = jnp.asarray(rng.randn(2048, 1000) * 0.01, jnp.float32)
    params["fcb"] = jnp.zeros((1000,))
    return params, bns


def forward(params, bns, x, labels, train, nhwc=False):
    new_bns = {}

    def apply_bn(name, h):
        y, nr = bn_apply(h, params[name + "_bn"], bns[name + "_bn"], train,
                         nhwc)
        new_bns[name + "_bn"] = nr
        return y

    bf = lambda a: a.astype(jnp.bfloat16)
    h = bf(x)
    h = apply_bn("stem", conv(h, bf(params["stem"]), 2, nhwc=nhwc))
    h = jax.nn.relu(h)
    window = (1, 3, 3, 1) if nhwc else (1, 1, 3, 3)
    strides = (1, 2, 2, 1) if nhwc else (1, 1, 2, 2)
    pads = (((0, 0), (1, 1), (1, 1), (0, 0)) if nhwc
            else ((0, 0), (0, 0), (1, 1), (1, 1)))
    h = jax.lax.reduce_window(h, -jnp.inf, jax.lax.max, window, strides,
                              pads)
    cin = 64
    for si, (n, width) in enumerate(zip(DEPTHS, WIDTHS)):
        mid = width // 4
        for bi in range(n):
            pre = "s%db%d" % (si, bi)
            stride = 2 if (bi == 0 and si > 0) else 1
            idn = h
            y = jax.nn.relu(apply_bn(
                pre + "_1", conv(h, bf(params[pre + "_1"]), 1, nhwc=nhwc)))
            y = jax.nn.relu(apply_bn(
                pre + "_2", conv(y, bf(params[pre + "_2"]), stride,
                                 nhwc=nhwc)))
            y = apply_bn(pre + "_3", conv(y, bf(params[pre + "_3"]), 1,
                                          nhwc=nhwc))
            if cin != width:
                idn = apply_bn(
                    pre + "_sc", conv(h, bf(params[pre + "_sc"]), stride,
                                      nhwc=nhwc))
            h = jax.nn.relu(y + idn)
            cin = width
    h = jnp.mean(h.astype(jnp.float32), (1, 2) if nhwc else (2, 3))
    logits = h @ params["fc"] + params["fcb"]
    lse = jax.nn.logsumexp(logits, -1)
    ll = jnp.take_along_axis(logits, labels[:, None], -1)[:, 0]
    return jnp.mean(lse - ll), new_bns


@partial(jax.jit, static_argnames=("mode", "nhwc"),
         donate_argnums=(0, 1, 2))
def step(params, bns, vel, x, labels, mode="full", nhwc=False):
    (loss, new_bns), grads = jax.value_and_grad(
        forward, has_aux=True)(params, bns, x, labels, True, nhwc)
    lr = 0.1
    if mode == "bare":
        params = jax.tree.map(lambda w, g: w - lr * g, params, grads)
        return params, bns, vel, loss
    mom, wd = 0.9, 1e-4
    vel = jax.tree.map(lambda v, g, w: mom * v + g + wd * w,
                       vel, grads, params)
    params = jax.tree.map(lambda w, v: w - lr * v, params, vel)
    return params, new_bns, vel, loss


def run(mode, steps=30, warmup=3):
    nhwc = mode.endswith("-nhwc")
    base = mode.split("-")[0]
    rng = np.random.RandomState(0)
    params, bns = init(rng, nhwc)
    vel = jax.tree.map(jnp.zeros_like, params)
    shape = (B, 224, 224, 3) if nhwc else (B, 3, 224, 224)
    x = jnp.asarray(rng.randn(*shape), jnp.float32)
    labels = jnp.asarray(rng.randint(0, 1000, (B,)), jnp.int32)
    for _ in range(warmup):
        params, bns, vel, loss = step(params, bns, vel, x, labels,
                                      mode=base, nhwc=nhwc)
    jax.device_get(loss)
    t0 = time.perf_counter()
    for _ in range(steps):
        params, bns, vel, loss = step(params, bns, vel, x, labels,
                                      mode=base, nhwc=nhwc)
    jax.device_get(loss)
    return B * steps / (time.perf_counter() - t0)


if __name__ == "__main__":
    import sys

    modes = sys.argv[1:] or ["full", "full-nhwc", "full", "full-nhwc"]
    print("backend:", jax.default_backend())
    for mode in modes:
        print("%s probe: %.1f img/s" % (mode, run(mode)), flush=True)
