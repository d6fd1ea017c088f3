"""Plain reference of the ResNet training step the program builds
(``paddle_tpu/models/resnet.py`` ``resnet_imagenet`` with bottleneck
blocks): 7x7 stem, 3x3 max pool, four stages of bottlenecks whose first
block projects its shortcut, batch normalisation in training mode after
every convolution (biased batch variance, running statistics updated with
momentum 0.9 from values that carry no gradient), global average pool, a
biased classifier, mean cross-entropy, momentum SGD without weight decay.

It started from ``tools/resnet_probe.py`` and departs from it where the
probe departs from the program: float32 throughout, depth, classes and
image from the configuration, the program's parameter names in the order
its layers are built (a block makes its shortcut first), its initializers'
distributions (MSRA normal filters, Xavier uniform classifier, BN 1/0,
running mean 0 and variance 1), no weight decay. It imports nothing of
the program.

Batch statistics tie the rows of a batch together, so the step is not
summed over row blocks; every bottleneck is rematerialised in the backward
pass instead, so that only block inputs are kept in float32.
"""

import numpy as np

import jax
import jax.numpy as jnp

from benchmarks.reference.common import msra_normal, xavier_uniform

BN_EPS = 1e-5
BN_MOMENTUM = 0.9
STAGES = {50: (3, 4, 6, 3), 101: (3, 4, 23, 3), 152: (3, 8, 36, 3)}


def _layout(cfg):
    """Parameter and state specifications with the network's plan: every
    conv+BN pair is numbered as the program's ``unique_name`` numbers it."""
    m = cfg["model"]
    channels = cfg["input"]["image"][0]
    spec, state, count = {}, {}, iter(range(10 ** 6))

    def conv_bn(cin, cout, k):
        i = next(count)
        shape = (cout, cin, k, k)
        spec["conv2d_%d.w_0_0" % i] = (shape, msra_normal(shape))
        spec["batch_norm_%d.w_0_0" % i] = ((cout,), ("const", 1.0))
        spec["batch_norm_%d.b_0_0" % i] = ((cout,), ("const", 0.0))
        state["batch_norm_%d.mean_0" % i] = ((cout,), 0.0)
        state["batch_norm_%d.var_0" % i] = ((cout,), 1.0)
        return i

    plan = {"stem": conv_bn(channels, 64, 7), "blocks": []}
    cin = 64
    for stage, blocks in enumerate(STAGES[m["depth"]]):
        mid, out = 64 * 2 ** stage, 256 * 2 ** stage
        for b in range(blocks):
            stride = 2 if (b == 0 and stage > 0) else 1
            block = {"stride": stride, "short": None}
            if cin != out or stride != 1:
                block["short"] = conv_bn(cin, out, 1)
            block["c1"] = conv_bn(cin, mid, 1)
            block["c2"] = conv_bn(mid, mid, 3)
            block["c3"] = conv_bn(mid, out, 1)
            plan["blocks"].append(block)
            cin = out
    shape = (cin, m["class_num"])
    spec["fc_0.w_0_0"] = (shape, xavier_uniform(shape))
    spec["fc_0.b_0_0"] = ((m["class_num"],), ("const", 0.0))
    return spec, state, plan


def row_blocks(cfg):
    """Batch statistics: the whole batch at once."""
    return None


def param_specs(cfg):
    return _layout(cfg)[0]


def state_specs(cfg):
    return _layout(cfg)[1]


def step_flops(cfg, rows):
    """Model operations of one training step: every convolution and the
    classifier, forward plus both backward products (the stem has no input
    gradient: forward plus one). A multiply-add is two operations."""
    m = cfg["model"]
    channels, height, width = cfg["input"]["image"]

    def conv(cin, cout, k, h, w):
        return 2 * cin * cout * k * k * h * w

    h, w = (height + 1) // 2, (width + 1) // 2
    total = 2 * conv(channels, 64, 7, h, w)          # stem, stride 2
    h, w = (h + 1) // 2, (w + 1) // 2                # 3x3 max pool, stride 2
    cin = 64
    for i, blocks in enumerate(STAGES[m["depth"]]):
        mid, out = 64 * 2 ** i, 256 * 2 ** i
        for b in range(blocks):
            stride = 2 if (b == 0 and i > 0) else 1
            ho, wo = (h + stride - 1) // stride, (w + stride - 1) // stride
            block = conv(cin, mid, 1, h, w) + conv(mid, mid, 3, ho, wo) \
                + conv(mid, out, 1, ho, wo)
            if cin != out or stride != 1:
                block += conv(cin, out, 1, ho, wo)
            total += 3 * block
            cin, h, w = out, ho, wo
    total += 3 * 2 * cin * m["class_num"]
    return rows * total


def first_gradient_state(name, cfg):
    """Momentum's velocity after one step from zero is the gradient."""
    return name + "_velocity_0", 1.0


def make_batch(cfg, rows, rng):
    """Seeded standard-normal images and uniform labels."""
    image = tuple(cfg["input"]["image"])
    return {
        "img": rng.standard_normal((rows,) + image, dtype=np.float32),
        "label": rng.integers(0, cfg["model"]["class_num"],
                              (rows, 1)).astype(np.int64),
    }


def normalisers(batch):
    return {}


def _conv_bn(mm, p, state, new_state, i, x, stride, pad, relu):
    y = mm.conv(x, p["conv2d_%d.w_0_0" % i], stride, pad)
    mean = jnp.mean(y, (0, 2, 3))
    var = jnp.mean(jnp.square(y), (0, 2, 3)) - jnp.square(mean)
    for what, batch_value in (("mean", mean), ("var", var)):
        name = "batch_norm_%d.%s_0" % (i, what)
        new_state[name] = (BN_MOMENTUM * state[name] + (1.0 - BN_MOMENTUM)
                           * jax.lax.stop_gradient(batch_value))
    shape = (1, -1, 1, 1)
    y = (y - mean.reshape(shape)) * jax.lax.rsqrt(var.reshape(shape) + BN_EPS)
    y = (y * p["batch_norm_%d.w_0_0" % i].reshape(shape)
         + p["batch_norm_%d.b_0_0" % i].reshape(shape))
    return jax.nn.relu(y) if relu else y


def _bottleneck(mm, p, state, block, x):
    new_state = {}
    stride = block["stride"]
    short = x
    if block["short"] is not None:
        short = _conv_bn(mm, p, state, new_state, block["short"], x, stride,
                         0, relu=False)
    y = _conv_bn(mm, p, state, new_state, block["c1"], x, 1, 0, relu=True)
    y = _conv_bn(mm, p, state, new_state, block["c2"], y, stride, 1,
                 relu=True)
    y = _conv_bn(mm, p, state, new_state, block["c3"], y, 1, 0, relu=False)
    return jax.nn.relu(y + short), new_state


def block_loss(p, state, block, norm, cfg, mm):
    """The whole batch's loss and the running statistics after it."""
    plan = _layout(cfg)[2]
    new_state = {}
    x = _conv_bn(mm, p, state, new_state, plan["stem"], block["img"], 2, 3,
                 relu=True)
    x = jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, 1, 3, 3), (1, 1, 2, 2),
        ((0, 0), (0, 0), (1, 1), (1, 1)))
    for bottleneck in plan["blocks"]:
        x, stats = jax.checkpoint(
            lambda p_, x_, b=bottleneck: _bottleneck(mm, p_, state, b, x_))(
                p, x)
        new_state.update(stats)
    x = jnp.mean(x, (2, 3))
    logits = mm.dot(x, p["fc_0.w_0_0"]) + p["fc_0.b_0_0"]
    label = block["label"].reshape(-1).astype(jnp.int32)
    loss = jnp.mean(jax.nn.logsumexp(logits, -1)
                    - jnp.take_along_axis(logits, label[:, None], -1)[:, 0])
    return loss, new_state
