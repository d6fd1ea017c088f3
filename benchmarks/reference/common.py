"""What every plain reference shares: weights from the seed, the three
precisions a reference can be computed in, the optimizer updates, and
``follow`` — the first optimizer steps of a model, reduced to the readings
that ``benchmarks/compare.py`` holds the timed program to.

Nothing here imports the program (``paddle_tpu``): a reference is plain
``jax.numpy`` in float32 with matmuls at ``highest`` precision. A model
module beside this file gives

    param_specs(cfg)  -> {name: (shape, init)}      trainable leaves, in order
    state_specs(cfg)  -> {name: (shape, value)}     non-trainable state
    make_batch(cfg, rows, rng) -> {feed name: numpy array}
    normalisers(batch) -> dict      whole-batch sums a row block needs
    block_loss(params, state, block, norm, cfg, mm) -> (loss part, new state)
    row_blocks(cfg)   rows a block may hold (None: the whole batch at once)
    first_gradient_state(name, cfg) -> (the program's optimizer state that
                      gives a leaf's first gradient back, its factor)
    step_flops(cfg, rows) -> model operations of one optimizer step, which
                      ``step_mfu_pct`` divides (``benchmarks/work.py``)

and the names are the program's own parameter names, so that the weights
made here can be put into the program's scope before its first step.
"""

import functools
import json

import numpy as np

import jax
import jax.numpy as jnp

PRECISIONS = ("f32", "bf16", "fp8")


# -- weights and batches from the seed ---------------------------------------

def seed_key(seed):
    """A PRNG key from any whole number the driver may give (seeds run to a
    little over 2**31, and x64 is off)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed >> 20), seed & 0xFFFFF)


def batch_rng(seed, index):
    """The numpy generator of pool batch ``index``: every batch of a seed
    has a stream of its own, so a reference can remake batch 2 alone."""
    return np.random.Generator(np.random.PCG64([int(seed), int(index)]))


def _draw(key, shape, init):
    kind = init[0]
    if kind == "const":
        return jnp.full(shape, init[1], jnp.float32)
    if kind == "uniform":
        return jax.random.uniform(key, shape, jnp.float32, -init[1], init[1])
    if kind == "normal":
        return init[1] * jax.random.normal(key, shape, jnp.float32)
    raise ValueError("unknown init %r" % (init,))


def normal(std):
    return ("normal", float(std))


def xavier_uniform(shape):
    """Limit sqrt(6 / (fan_in + fan_out)) over the first two axes' fans,
    the default initializer of the program's fc and embedding layers."""
    receptive = int(np.prod(shape[2:])) if len(shape) > 2 else 1
    return ("uniform", float(np.sqrt(
        6.0 / (shape[0] * receptive + shape[1] * receptive))))


def msra_normal(shape):
    """Normal with std sqrt(2 / fan_in) for an OIHW filter."""
    return ("normal", float(np.sqrt(2.0 / (shape[1] * shape[2] * shape[3]))))


def init_params(specs, seed):
    """Every leaf on the device in one jitted call from the seed, float32
    (the type the program keeps its master weights in)."""
    names = list(specs)

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(names))
        return {n: _draw(k, tuple(specs[n][0]), specs[n][1])
                for n, k in zip(names, keys)}

    return make(seed_key(seed))


def init_state(specs):
    return {n: jnp.full(tuple(shape), value, jnp.float32)
            for n, (shape, value) in specs.items()}


# -- the precision a reference is computed in --------------------------------

def _to_bf16(x):
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _to_fp8(x):
    """float8 e4m3 (4 exponent bits, 3 mantissa bits, largest value 240)
    under one scale per tensor, the usual fp8 recipe. ``reduce_precision``
    and not a pair of ``astype``: XLA on the TPU removes a conversion to a
    narrower type and back as redundant (seen on the chip, PR 25: the
    round trip changed values by 5e-7), and rounds nothing."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 240.0
    return jax.lax.reduce_precision(
        jnp.clip(x / scale, -240.0, 240.0), exponent_bits=4,
        mantissa_bits=3) * scale


def _rounding(to):
    """Round a float32 tensor to a lower precision and back, forward and
    backward alike: the value is rounded on its way forward, and so is the
    gradient that comes back through it."""

    @jax.custom_vjp
    def rnd(x):
        return to(x)

    rnd.defvjp(lambda x: (to(x), None), lambda _, g: (to(g),))
    return rnd


_ROUND = {"f32": lambda x: x, "bf16": _rounding(_to_bf16),
          "fp8": _rounding(_to_fp8)}


class Matmuls:
    """The contractions of a reference at one precision, in float32
    containers at ``highest``. ``f32``: the reference proper. ``bf16``:
    operands and results rounded to bfloat16, float32 accumulation — what
    the configuration states (bf16 matmuls and activations), a second
    witness of what a sound step reads. ``fp8``: operands and results
    rounded to e4m3 — the control, the nearest precision below the
    configuration's, for operands and activations alike."""

    def __init__(self, precision):
        if precision not in PRECISIONS:
            raise ValueError("precision %r not in %r"
                             % (precision, PRECISIONS))
        self.precision = precision
        self.round = _ROUND[precision]

    def einsum(self, spec, a, b):
        return self.round(jnp.einsum(
            spec, self.round(a), self.round(b),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32))

    def dot(self, x, w):
        return self.einsum("...i,io->...o", x, w)

    def conv(self, x, w, stride, pad):
        return self.round(jax.lax.conv_general_dilated(
            self.round(x), self.round(w), (stride, stride),
            [(pad, pad), (pad, pad)],
            dimension_numbers=("NCHW", "OIHW", "NCHW"),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32))


# -- optimizers, as the configuration states them ----------------------------

def adam_update(params, grads, opt, t, hyper):
    lr, b1, b2, eps = (hyper["lr"], hyper.get("beta1", 0.9),
                       hyper.get("beta2", 0.999), hyper.get("epsilon", 1e-8))
    m = {n: b1 * opt["m"][n] + (1.0 - b1) * grads[n] for n in params}
    v = {n: b2 * opt["v"][n] + (1.0 - b2) * jnp.square(grads[n])
         for n in params}
    lr_t = lr * jnp.sqrt(1.0 - b2 ** t) / (1.0 - b1 ** t)
    new = {n: params[n] - lr_t * m[n] / (jnp.sqrt(v[n]) + eps)
           for n in params}
    return new, {"m": m, "v": v}


def momentum_update(params, grads, opt, t, hyper):
    lr, mu = hyper["lr"], hyper.get("momentum", 0.9)
    vel = {n: mu * opt["v"][n] + grads[n] for n in params}
    return {n: params[n] - lr * vel[n] for n in params}, {"v": vel}


UPDATES = {"adam": adam_update, "momentum": momentum_update}


def zeros_like_opt(kind, params):
    def zeros():
        return {n: jnp.zeros_like(p) for n, p in params.items()}

    return {"m": zeros(), "v": zeros()} if kind == "adam" else {"v": zeros()}


# -- the first steps, reduced to readings ------------------------------------

def leaf_norms(tree):
    return {n: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for n, x in tree.items()}


def _blocks(batch, rows_per_block):
    rows = next(iter(batch.values())).shape[0]
    if not rows_per_block or rows_per_block >= rows:
        return None
    assert rows % rows_per_block == 0, (rows, rows_per_block)
    n = rows // rows_per_block
    return {k: v.reshape((n, rows_per_block) + v.shape[1:])
            for k, v in batch.items()}


_STEPS = {}


def make_step(model, cfg, precision):
    """One jitted optimizer step of ``model``: loss and gradients summed
    over row blocks (so that the float32 activations of one block fit
    beside the weights), then the configuration's update. One jitted
    function a model, configuration and precision, so that a process that
    follows many seeds traces it once."""
    key = (model.__name__, precision, json.dumps(
        [cfg["model"], cfg["optimizer"], cfg.get("input")], sort_keys=True))
    if key not in _STEPS:
        _STEPS[key] = _make_step(model, cfg, precision)
    return _STEPS[key]


def _make_step(model, cfg, precision):
    mm = Matmuls(precision)
    hyper = cfg["optimizer"]
    update = UPDATES[hyper["type"]]

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(params, opt, state, batch, t):
        norm = model.normalisers(batch)
        blocks = _blocks(batch, model.row_blocks(cfg))

        def part(p, block):
            return model.block_loss(p, state, block, norm, cfg, mm)

        grad_fn = jax.value_and_grad(part, has_aux=True)
        if blocks is None:
            (loss, new_state), grads = grad_fn(params, batch)
        else:
            def body(carry, block):
                (l, _), g = grad_fn(params, block)
                return (carry[0] + l,
                        jax.tree.map(jnp.add, carry[1], g)), None

            zero = (jnp.float32(0.0),
                    jax.tree.map(jnp.zeros_like, params))
            (loss, grads), _ = jax.lax.scan(body, zero, blocks)
            new_state = state
        new_params, new_opt = update(params, grads, opt, t, hyper)
        return new_params, new_opt, new_state, loss, leaf_norms(grads)

    return step


def follow(model, cfg, rows, seed, steps=3, precision="f32", keep_rows=None):
    """Drive the reference from the seed through its first ``steps`` steps
    on pool batches 0, 1, 2, ... and return what is compared:

        losses        one per step
        grad_norms    {leaf: norm of the first step's gradient}
        change_norms  {leaf: norm of the change after the last step},
                      non-trainable state (running statistics) included

    ``keep_rows`` feeds only the first rows of every batch, the mean taken
    over those: the planted fault "half of the batch left out"."""
    specs, sspecs = model.param_specs(cfg), model.state_specs(cfg)
    step = make_step(model, cfg, precision)
    with jax.default_matmul_precision("highest"):
        params = init_params(specs, seed)
        state = init_state(sspecs)
        start = {n: jnp.copy(x) for n, x in {**params, **state}.items()}
        opt = zeros_like_opt(cfg["optimizer"]["type"], params)
        losses, grad_norms = [], None
        for i in range(steps):
            batch = model.make_batch(cfg, rows, batch_rng(seed, i))
            if keep_rows:
                batch = {k: v[:keep_rows] for k, v in batch.items()}
            batch = {k: jnp.asarray(v) for k, v in batch.items()}
            params, opt, state, loss, gn = step(
                params, opt, state, batch, jnp.float32(i + 1))
            losses.append(float(loss))
            if i == 0:
                grad_norms = {n: float(x) for n, x in gn.items()}
        end = {**params, **state}
        change = jax.jit(lambda a, b: leaf_norms(
            {n: a[n] - b[n] for n in a}))(end, start)
        change_norms = {n: float(x) for n, x in change.items()}
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": change_norms}
