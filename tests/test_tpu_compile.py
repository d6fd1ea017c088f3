"""Compile the main path's kernels for a TPU that is described, not
attached: what the chip's compiler refuses (a slice off the tiling, a
kernel over its VMEM, a custom call under a checked ``shard_map``) fails
here, on the CPU, at no chip time. Nothing runs, so nothing here says
anything about results or speed.

The topology is described inside a fixture, never at import: only one
process may hold the TPU library, every xdist worker imports this file,
and only the worker that runs it may load the library. All compiles happen
in this process, and in this one file, for the same reason.
"""

import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from paddle_tpu.kernels.flash_attention import (
    _LSE_LANES, _bwd_fused_fits, _flash_backward, dispatch_attention_lse,
    flash_attention_raw_lse, pick_block, pick_bwd_blocks)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever keeps it undescribed
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)
    # an entry compiled for a described device cannot be read back without
    # one: keep these compiles out of the persistent cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def _attention_args(shape, sharding, masked, lse_sharding=None,
                    lens_sharding=None, dtype=jnp.bfloat16):
    """Shapes of one attention call in the layouts the fused_attention op
    and its grad op hand the kernels: one [B, H, T, D] activation (q, k,
    v, out and the cotangent alike), the saved logsumexp, the lengths."""
    B, H, T, D = shape
    act = jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    lse = jax.ShapeDtypeStruct((B * H, T, _LSE_LANES), jnp.float32,
                               sharding=lse_sharding or sharding)
    lens = (jax.ShapeDtypeStruct((B,), jnp.int32,
                                 sharding=lens_sharding or sharding)
            if masked else None)
    return act, lse, lens


# (shape, causal, masked by seq_lens, dropout rate): the BERT seq-2048 step
# chip_smoke.py trains, its seq-4096 sibling, a causal (NMT decoder) shape,
# and the longest context the committed block table lists
def _longest_seq():
    from tools.flash_block_sweep import DEFAULT_SEQS

    return max(DEFAULT_SEQS)


ATTENTION_CASES = {
    "bert-seq2048": ((4, 12, 2048, 64), False, True, 0.1),
    "bert-seq4096": ((8, 12, 4096, 64), False, True, 0.1),
    "causal-seq2048": ((4, 12, 2048, 64), True, False, 0.0),
    "longest-table-seq": (None, False, True, 0.1),
}


def _case(name):
    shape, causal, masked, rate = ATTENTION_CASES[name]
    if shape is None:
        shape = (1, 12, _longest_seq(), 64)
    return shape, causal, masked, rate


@pytest.mark.parametrize("name", list(ATTENTION_CASES))
def test_flash_forward_compiles(one_chip, name):
    shape, causal, masked, rate = _case(name)
    act, _, lens = _attention_args(shape, one_chip, masked)
    blk = pick_block(shape[2], jnp.bfloat16)

    def fwd(q, k, v, lens_):
        return flash_attention_raw_lse(q, k, v, lens_, 7, causal,
                                       shape[3] ** -0.5, rate, blk, blk,
                                       False)

    hlo = _compile(fwd, act, act, act, lens)
    assert hlo.count("tpu_custom_call") == 1


@pytest.mark.parametrize("form", ["fused", "split"])
@pytest.mark.parametrize("name", list(ATTENTION_CASES))
def test_flash_backward_compiles(one_chip, monkeypatch, name, form):
    """``_flash_backward`` as ``fused_attention_grad`` calls it, in both
    forms: the one kernel (dQ, dK and dV from a single pass, with the
    table's blocks and the VMEM limit it asks for: an overrun shows here)
    and the dQ and dK/dV pair, which every shape can be steered to from a
    test. The longest sequence of the table is the one shape here whose
    dQ accumulator the reckoning refuses: left to itself it must compile
    as the pair."""
    import sys

    shape, causal, masked, rate = _case(name)
    T = shape[2]
    act, lse, lens = _attention_args(shape, one_chip, masked)
    blk = pick_block(T, jnp.bfloat16)
    fits = _bwd_fused_fits(
        T, shape[3], jnp.bfloat16,
        *pick_bwd_blocks(T, T, jnp.bfloat16, (blk, blk)), rate)
    assert fits == (T < _longest_seq())
    if form == "split":
        monkeypatch.setattr(
            sys.modules["paddle_tpu.kernels.flash_attention"],
            "_bwd_fused_fits", lambda *a: False)

    def bwd(q, k, v, out, lse_, g, lens_):
        return _flash_backward(
            q, k, v, out, lse_, g, None, lens_, None, 7, causal,
            shape[3] ** -0.5, rate, blk, blk, False)

    hlo = _compile(bwd, act, act, act, act, lse, act, lens)
    assert hlo.count("tpu_custom_call") == (
        1 if form == "fused" and fits else 2)


def test_flash_backward_compiles_float32(one_chip):
    """The table's float32 row at 2048 positions, with dropout: the fused
    backward at the pair swept for it, wider than the forward's block."""
    shape, T = (4, 12, 2048, 64), 2048
    act, lse, lens = _attention_args(shape, one_chip, True,
                                     dtype=jnp.float32)
    blk = pick_block(T, jnp.float32)
    assert pick_bwd_blocks(T, T, jnp.float32, (blk, blk)) != (blk, blk)

    def bwd(q, k, v, out, lse_, g, lens_):
        return _flash_backward(
            q, k, v, out, lse_, g, None, lens_, None, 7, False,
            shape[3] ** -0.5, 0.1, blk, blk, False)

    hlo = _compile(bwd, act, act, act, act, lse, act, lens)
    assert hlo.count("tpu_custom_call") == 1


def test_flash_custom_calls_are_named_by_the_lowering_scope(one_chip):
    """Under the lowering's ``pt.<op>.<block>_<idx>`` scope the kernels'
    custom calls (one forward, one backward at 2048 positions) are
    instructions named by that scope, which is what the benchmark's
    flash readers match in a trace. A ``name=`` on the ``pallas_call``
    would take that place (``%flash_fwd.1``: tried in PR 26), so the
    calls carry none."""
    shape = (8, 12, 2048, 64)
    T = shape[2]
    act, lse, lens = _attention_args(shape, one_chip, True)
    blk = pick_block(T, jnp.bfloat16)

    def step(q, k, v, lse_, g, lens_):
        with jax.named_scope("pt.fused_attention.0_17"):
            out, _ = flash_attention_raw_lse(
                q, k, v, lens_, 7, False, shape[3] ** -0.5, 0.0, blk, blk,
                False)
        with jax.named_scope("pt.fused_attention_grad.0_476"):
            return _flash_backward(
                q, k, v, out, lse_, g, None, lens_, None, 7, False,
                shape[3] ** -0.5, 0.0, blk, blk, False)

    hlo = _compile(step, act, act, act, lse, act, lens)
    from paddle_tpu.observability.opprof import (hlo_op_map,
                                                 instruction_name)

    tags, _ = hlo_op_map(hlo)
    calls = [instruction_name(line.strip().removeprefix("ROOT "))
             for line in hlo.splitlines() if "tpu_custom_call" in line]
    assert len(calls) == 2, calls
    assert sum(n.startswith("pt.fused_attention.0_17") for n in calls) == 1
    assert sum(n.startswith("pt.fused_attention_grad.0_476")
               for n in calls) == 1
    assert {tags[n] for n in calls} == {
        "pt.fused_attention.0_17", "pt.fused_attention_grad.0_476"}


@pytest.mark.parametrize("which", ["forward", "backward"])
def test_flash_in_shard_map_compiles_for_a_mesh(topo, monkeypatch, which):
    """The regression test for the unchecked ``shard_map`` wrap: the
    Pallas dispatch and the direct backward under ``spmd_lowering`` on a
    dp=2 x tp=2 mesh of the described chips — the path every mesh run
    with long sequences takes on the chip, which no CPU run reaches."""
    import sys

    from paddle_tpu.kernels.flash_attention import flash_backward_spmd
    from paddle_tpu.parallel.mesh import spmd_lowering

    # the dispatch asks the default backend whether to interpret the
    # kernels; this process's is the CPU, the compile's target is not
    monkeypatch.setattr(sys.modules["paddle_tpu.kernels.flash_attention"],
                        "_on_tpu", lambda: True)
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("dp", "tp"))
    shape = (8, 12, 2048, 64)
    act, lse, lens = _attention_args(
        shape, NamedSharding(mesh, P("dp", "tp", None, None)), True,
        lse_sharding=NamedSharding(mesh, P()),
        lens_sharding=NamedSharding(mesh, P("dp")))
    blk = pick_block(shape[2], jnp.bfloat16)

    def fwd(q, k, v, lens_):
        return dispatch_attention_lse(q, k, v, seq_lens=lens_,
                                      dropout_rate=0.1, seed=7,
                                      force_pallas=True, raw_lse=True)

    def bwd(q, k, v, out, lse_, g, lens_):
        return flash_backward_spmd(q, k, v, out, lse_, g, lens_, 7, False,
                                   shape[3] ** -0.5, 0.1, blk, blk, False)

    with spmd_lowering(mesh, ("dp",)):
        if which == "forward":
            hlo = _compile(fwd, act, act, act, lens)
        else:
            hlo = _compile(bwd, act, act, act, act, lse, act, lens)
    assert hlo.count("tpu_custom_call") == 1


def test_s8_convolution_compiles(one_chip):
    """The native branch of ``quantized_conv2d`` (ops/quant_ops.py) at a
    ResNet-50 width: s8 x s8 -> s32, NCHW/OIHW, 3x3."""
    x = jax.ShapeDtypeStruct((8, 256, 14, 14), jnp.int8, sharding=one_chip)
    w = jax.ShapeDtypeStruct((256, 256, 3, 3), jnp.int8, sharding=one_chip)

    def conv(x_, w_):
        dn = jax.lax.conv_dimension_numbers(x_.shape, w_.shape,
                                            ("NCHW", "OIHW", "NCHW"))
        return jax.lax.conv_general_dilated(
            x_, w_, window_strides=(1, 1), padding=[(1, 1), (1, 1)],
            dimension_numbers=dn, preferred_element_type=jnp.int32)

    hlo = _compile(conv, x, w)
    assert "s32[8,256,14,14]" in hlo and "convolution(" in hlo


def test_s8_matmul_compiles(one_chip):
    """The native branch of ``quantized_matmul``: ResNet-50's classifier,
    [8, 2048] x [2048, 1000], s8 x s8 -> s32."""
    x = jax.ShapeDtypeStruct((8, 2048), jnp.int8, sharding=one_chip)
    y = jax.ShapeDtypeStruct((2048, 1000), jnp.int8, sharding=one_chip)

    hlo = _compile(
        lambda x_, y_: jax.lax.dot(x_, y_,
                                   preferred_element_type=jnp.int32), x, y)
    assert "s32[8,1000]" in hlo


# -- the decoder cells' kernels: grouped-query heads, a window, head sizes
# 128 and 64

DECODER = dict(rows=2, q_heads=32, kv_heads=4, seq=4096, dim=128)
# (positions, window, key/value heads, head size) of the decoder cells'
# attention layers: the first cell's 4096 under 1024 and under none, the
# second's 3072 under 2048 and under none, the third's 8192 causal under
# none on 8 key/value heads of 64; (tokens, d, width, experts a token,
# experts held) of their expert layers
ATTENTION_SHAPES = {"window": (4096, 1024, 4, 128),
                    "full": (4096, None, 4, 128),
                    "s3072-window2048": (3072, 2048, 4, 128),
                    "s3072-full": (3072, None, 4, 128),
                    "s8192-full-kv8-d64": (8192, None, 8, 64),
                    "s4096-full-kv2": (4096, None, 2, 128)}
EXPERT_SHAPES = {"d2304-w896": (8192, 2304, 896, 8, 16),
                 "d2048-w1024": (6144, 2048, 1024, 8, 16),
                 "d2048-w1536": (16384, 2048, 1536, 4, 8)}
# the non-gated relu2 expert layer's (nemotron_twotower_30b_a3b): [8192 x 6
# -> 49152, 2688] rows, 8 experts of width 1856, no multiple of 128 lanes
RELU2_SHAPES = {"d2688-w1856": (8192, 2688, 1856, 6, 8)}


def _decoder_args(one_chip, seq=None, kv_heads=None, dim=None):
    b, t = DECODER["rows"], seq or DECODER["seq"]
    d = dim or DECODER["dim"]
    q = jax.ShapeDtypeStruct((b, DECODER["q_heads"], t, d), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((b, kv_heads or DECODER["kv_heads"], t, d),
                              jnp.bfloat16, sharding=one_chip)
    lse = jax.ShapeDtypeStruct((b * DECODER["q_heads"], t, _LSE_LANES),
                               jnp.float32, sharding=one_chip)
    return q, kv, lse


@pytest.mark.parametrize("shape", list(ATTENTION_SHAPES))
def test_grouped_query_forward_compiles(one_chip, shape):
    seq, window, kv_heads, dim = ATTENTION_SHAPES[shape]
    q, kv, _ = _decoder_args(one_chip, seq, kv_heads, dim)
    blk = pick_block(seq, jnp.bfloat16)

    def fwd(q_, k_, v_):
        return flash_attention_raw_lse(
            q_, k_, v_, None, 0, True, dim ** -0.5, 0.0, blk, blk, False,
            window)

    assert _compile(fwd, q, kv, kv).count("tpu_custom_call") == 1


@pytest.mark.parametrize("form", ["fused", "split"])
@pytest.mark.parametrize("shape", list(ATTENTION_SHAPES))
def test_grouped_query_backward_compiles(one_chip, monkeypatch, shape,
                                         form):
    """Both backward forms at [2, 32/4, positions, 128] and [2, 32/8, 8192,
    64] bf16: by the shapes this attention takes the one kernel (its
    [positions, head] dQ accumulator fits; 8192 positions at head size 64
    are the last that do: twice as many take the pair); dK/dV come out per
    K/V head."""
    import sys

    seq, window, kv_heads, dim = ATTENTION_SHAPES[shape]
    q, kv, lse = _decoder_args(one_chip, seq, kv_heads, dim)
    blk = pick_block(seq, jnp.bfloat16)
    assert _bwd_fused_fits(seq, dim, jnp.bfloat16, blk, blk)
    assert not _bwd_fused_fits(2 * 8192, 64, jnp.bfloat16, blk, blk)
    if form == "split":
        monkeypatch.setattr(
            sys.modules["paddle_tpu.kernels.flash_attention"],
            "_bwd_fused_fits", lambda *a: False)

    def bwd(q_, k_, v_, out, lse_, g):
        return _flash_backward(
            q_, k_, v_, out, lse_, g, None, None, None, 0, True,
            dim ** -0.5, 0.0, blk, blk, False, window)

    assert _compile(bwd, q, kv, kv, q, lse, q).count("tpu_custom_call") == (
        1 if form == "fused" else 2)
    dq, dk, dv = jax.eval_shape(bwd, q, kv, kv, q, lse, q)
    assert dq.shape == q.shape and dk.shape == dv.shape == kv.shape


@pytest.mark.parametrize("shape", list(EXPERT_SHAPES))
def test_grouped_matmuls_compile(one_chip, monkeypatch, shape):
    """The expert MLP's three products and their gradients at the decoder
    cells' shapes ([65536, 2304] rows, 16 experts of width 896; [49152,
    2048] rows, 16 of width 1024; [65536, 2048] rows, 8 of width 1536):
    nine megablox kernels, each at the tiling ``_tilings`` reckons for
    it."""
    import sys

    from paddle_tpu.kernels import grouped_matmul as gm

    monkeypatch.setattr(sys.modules["paddle_tpu.kernels.grouped_matmul"],
                        "_on_tpu", lambda: True)
    tokens, d, width, k, held = EXPERT_SHAPES[shape]
    rows = jax.ShapeDtypeStruct((tokens * k, d), jnp.bfloat16,
                                sharding=one_chip)
    up = jax.ShapeDtypeStruct((held, d, width), jnp.bfloat16,
                              sharding=one_chip)
    down = jax.ShapeDtypeStruct((held, width, d), jnp.bfloat16,
                                sharding=one_chip)
    sizes = jax.ShapeDtypeStruct((held,), jnp.int32, sharding=one_chip)

    def loss(rows_, gate_, up_, down_, sizes_):
        hidden = (jax.nn.silu(gm.grouped_matmul(rows_, gate_, sizes_))
                  * gm.grouped_matmul(rows_, up_, sizes_))
        out = gm.grouped_matmul(hidden, down_, sizes_)
        return jnp.sum(out[:1024].astype(jnp.float32))

    hlo = _compile(jax.value_and_grad(loss, argnums=(0, 1, 2, 3)), rows, up,
                   up, down, sizes)
    assert hlo.count("tpu_custom_call") == 9


def _expert_mlp_hlo(one_chip, rows, d, width, held):
    """One layer's ``moe_expert_mlp``, forward and its own backward,
    masters in float32 as the step holds them, compiled for the chip."""
    from paddle_tpu.ops import moe_ops

    def arg(shape_, dtype):
        return jax.ShapeDtypeStruct(shape_, dtype, sharding=one_chip)

    up = arg((held, d, width), jnp.float32)

    def loss(rows_, weight_, gate_, up_, down_, sizes_):
        gate_, up_, down_ = (w.astype(rows_.dtype)
                             for w in (gate_, up_, down_))
        out = moe_ops._expert_mlp(rows_, weight_, sizes_, gate_, up_, down_)
        return jnp.sum(out[:1024].astype(jnp.float32))

    return _compile(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4)),
                    arg((rows, d), jnp.bfloat16), arg((rows,), jnp.float32),
                    up, up, arg((held, width, d), jnp.float32),
                    arg((held,), jnp.int32))


@pytest.mark.parametrize("shape", list(EXPERT_SHAPES))
def test_the_expert_mlp_backward_compiles(one_chip, monkeypatch, shape):
    """One layer's ``moe_expert_mlp`` with its own backward at the decoder
    cells' shapes: 10 kernels (three products and the gate forward; the
    down projection's transposed product, the gate's transpose, ONE kernel
    for the rows' gradient from gate and up, three weights' gradients), no
    ``add`` over the ``[R, d]`` buffer (autodiff's ``add_any`` of two
    rounded partial sums is gone) and, since PR 36, no pass of XLA's over
    the ``[R, w]`` buffer: every array of that shape is a kernel's."""
    import sys

    from paddle_tpu.ops import moe_ops

    monkeypatch.setattr(sys.modules["paddle_tpu.kernels.grouped_matmul"],
                        "_on_tpu", lambda: True)
    tokens, d, width, k, held = EXPERT_SHAPES[shape]
    rows = tokens * k
    assert moe_ops.gm.pair_by_kernel(rows, d, width)
    assert moe_ops.gm.gate_by_kernel(rows, width)
    hlo = _expert_mlp_hlo(one_chip, rows, d, width, held)
    assert hlo.count("tpu_custom_call") == 10
    assert not re.search(r"= bf16\[%d,%d\]\S* add\(" % (rows, d), hlo)
    made = set(re.findall(r"= bf16\[%d,%d\]\S* ([a-z-]+)\(" % (rows, width),
                          hlo))
    assert "custom-call" in made and not made & {
        "fusion", "convert", "multiply", "logistic"}, made
    assert "jit(silu)" not in hlo and "exponential(" not in hlo


def test_the_gate_keeps_xlas_form_off_the_lanes(one_chip, monkeypatch):
    """A width that is no multiple of 128 lanes: the predicate refuses the
    gate's kernels, and XLA's ``silu_gate`` and its transpose compile between
    the grouped products as before PR 36."""
    import sys

    from paddle_tpu.ops import moe_ops

    monkeypatch.setattr(sys.modules["paddle_tpu.kernels.grouped_matmul"],
                        "_on_tpu", lambda: True)
    rows, d, width, held = 4096, 256, 200, 4
    assert moe_ops.gm.gate_by_kernel(rows, 256)
    assert not moe_ops.gm.gate_by_kernel(rows, width)
    hlo = _expert_mlp_hlo(one_chip, rows, d, width, held)
    assert "jit(silu)" in hlo and "exponential(" in hlo
    assert "jit(_gate)" not in hlo


# -- the rotation of Q and K (ops/nn_ops.py rotary_embedding)

ROTATIONS = {
    "s4096-d128-yarn": (4096, 4, 128, {
        "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
        "original_max_position_embeddings": 8192}),
    "s8192-d64-default": (8192, 8, 64, {
        "rope_type": "default", "rope_theta": 1000000})}


@pytest.mark.parametrize("shape", list(ROTATIONS))
@pytest.mark.parametrize("which", ["q", "k"])
def test_the_rotation_is_one_pass_each_way(one_chip, which, shape):
    """What the chip's compiler makes of x*cos + (x @ R)*sin at the
    decoder cells' shapes (heads of 128 at 4096 positions; heads of 64,
    half a lane tile, at 8192), forward and cotangent: one output fusion,
    the [D, D] product with the multiply-add as its epilogue, and no
    float32 copy of the tensor or of its halves between instructions (the
    sliced form left five passes with both; PERF.md, Findings PR 32)."""
    from paddle_tpu.ops.nn_ops import _rotation

    seq, kv_heads, d, rope = ROTATIONS[shape]
    q, kv, _ = _decoder_args(one_chip, seq, kv_heads, d)
    x = q if which == "q" else kv
    rotate = _rotation(seq, d, x.dtype, rope)

    def cotangent(x_, g):
        return jax.vjp(rotate, x_)[1](g)[0]

    for fn, args in ((rotate, (x,)), (cotangent, (x, x))):
        hlo = _compile(fn, *args)
        entry = hlo[hlo.index("\nENTRY "):].splitlines()
        wide = "f32[%s," % ",".join(str(n) for n in x.shape[:3])
        assert not [line for line in entry if " = " + wide in line]
        (matrix,) = [line.split(" = ")[0].strip() for line in entry
                     if " = bf16[%d,%d]" % (d, d) in line
                     and " constant(" in line]
        (reader,) = [line for line in entry if " fusion(" in line
                     and re.search(re.escape(matrix) + "[,)]", line)]
        assert "kind=kOutput" in reader
        got = jax.eval_shape(fn, *args)
        assert (got.shape, got.dtype) == (x.shape, x.dtype)


# -- the gated short convolution (ops/nn_ops.py gated_short_conv): XLA's, no
# kernel

@pytest.mark.parametrize("which", ["forward", "backward"])
def test_gated_short_conv_compiles_without_a_float32_copy(one_chip, which):
    """The op and its gradient at [2, 8192, 6144] bf16 with 3 taps: what
    the chip's compiler makes of the shifted multiply-adds holds no
    float32 tensor as wide as the input (the cast of all of z that a
    slice-after-cast form leaves: 805 MB written and read again), and the
    forward none of [2, 8192, 2048] either: one pass from z to the
    result."""
    from paddle_tpu.ops.nn_ops import _gated_short_conv

    z = jax.ShapeDtypeStruct((2, 8192, 6144), jnp.bfloat16,
                             sharding=one_chip)
    out = jax.ShapeDtypeStruct((2, 8192, 2048), jnp.bfloat16,
                               sharding=one_chip)
    w = jax.ShapeDtypeStruct((2048, 3), jnp.float32, sharding=one_chip)

    def backward(z_, w_, g):
        return jax.vjp(_gated_short_conv, z_, w_)[1](g)

    fn, args = ((_gated_short_conv, (z, w)) if which == "forward"
                else (backward, (z, w, out)))
    hlo = _compile(fn, *args)
    entry = hlo[hlo.index("\nENTRY "):].splitlines()
    assert not [line for line in entry if " = f32[2,8192,6144]" in line]
    got = jax.eval_shape(fn, *args)
    if which == "forward":
        assert not [line for line in entry if " = f32[2,8192,2048]" in line]
        assert (got.shape, got.dtype) == (out.shape, out.dtype)
        assert "tpu_custom_call" not in hlo
    else:
        assert [(g.shape, g.dtype) for g in got] == [
            (z.shape, z.dtype), (w.shape, w.dtype)]


# -- the expert layer's row movements (kernels/row_permute.py)

@pytest.mark.parametrize("shape", list(EXPERT_SHAPES) + list(RELU2_SHAPES))
@pytest.mark.parametrize("which", ["expand", "reduce_float32",
                                   "reduce_bfloat16"])
def test_row_permute_compiles(one_chip, which, shape):
    """Both directions at the decoder cells' shapes, [8192 x 8 -> 65536,
    2304] and [6144 x 8 -> 49152, 2048] bf16 with 16 experts held and
    [16384 x 4 -> 65536, 2048] with 8, at the module's tile and chunk: one
    custom call each, the visit list round it in XLA."""
    from paddle_tpu.kernels import row_permute as rp

    tokens, d, _, k, held = dict(EXPERT_SHAPES, **RELU2_SHAPES)[shape]
    order = jax.ShapeDtypeStruct((tokens * k,), jnp.int32, sharding=one_chip)
    counts = jax.ShapeDtypeStruct((held,), jnp.int32, sharding=one_chip)
    if which == "expand":
        src = jax.ShapeDtypeStruct((tokens, d), jnp.bfloat16,
                                   sharding=one_chip)
        fn = lambda x, o, c: rp.expand(x, o, c, k)
        out = (tokens * k, d), jnp.bfloat16
    else:
        src = jax.ShapeDtypeStruct((tokens * k, d), jnp.bfloat16,
                                   sharding=one_chip)
        dtype = jnp.dtype(which.split("_")[1])
        fn = lambda r, o, c: rp.reduce(r, o, c, k, dtype)
        out = (tokens, d), dtype
    assert _compile(fn, src, order, counts).count("tpu_custom_call") == 1
    got = jax.eval_shape(fn, src, order, counts)
    assert (got.shape, got.dtype) == out


# -- the scalars of the routing (ops/moe_ops.py moe_router, moe_dispatch)

ROUTERS = {"d2304-w896": ("softmax", False, 64),
           "d2048-w1024": ("sigmoid", True, 128),
           "d2048-w1536": ("sigmoid", True, 64)}


@pytest.mark.parametrize("which", ["forward", "gradient"])
@pytest.mark.parametrize("op", ["router", "dispatch"])
@pytest.mark.parametrize("shape", list(EXPERT_SHAPES))
def test_the_routing_moves_no_single_scalar(one_chip, monkeypatch, shape, op,
                                            which):
    """``moe_router`` and ``moe_dispatch``, forward and gradient, at the
    decoder cells' shapes (8,192 x 2304 -> 64 softmax outputs, 8 a token,
    16 held; 6,144 x 2048 -> 128 sigmoid outputs ranked with a bias, 8, 16;
    16,384 x 2048 -> 64 with a bias, 4, 8): the chip's executable holds no
    ``gather`` and no ``scatter``. When it did (until PR 38: the chosen
    scores by ``take_along_axis``, the rows' weights by ``weight[PairOf
    Row]``, their transposes a scatter-add and ``g[RowOfPair]``), each ran
    one element at a time, 6-10 ns an element and up to 17 the
    scatter-add: 0.31-0.66 ms a gather and 0.43-1.1 a scatter-add over
    49,152-65,536 pairs, four of them a layer (three where no bias ranks),
    5.6-11.8 ms of a 154-229 ms step (PERF.md, Findings PR 38), where a
    sort of as many keys takes 0.04-0.08 ms and the compare over the
    outputs fuses with what reads it."""
    import sys

    from paddle_tpu.ops import moe_ops
    from test_moe_ops import _ctx

    monkeypatch.setattr(sys.modules["paddle_tpu.kernels.row_permute"],
                        "_on_tpu", lambda: True)
    tokens, d, _, k, held = EXPERT_SHAPES[shape]
    score_func, biased, experts = ROUTERS[shape]

    def arg(shape_, dtype):
        return jax.ShapeDtypeStruct(shape_, dtype, sharding=one_chip)

    if op == "router":
        args = [arg((tokens, d), jnp.bfloat16), arg((d, experts),
                                                    jnp.float32)]
        if biased:
            args.append(arg((experts,), jnp.float32))

        def fn(x, w, *bias):
            outs = moe_ops.moe_router(
                _ctx("moe_router"),
                {"X": [x], "Weight": [w], "Bias": list(bias)},
                {"k": k, "score_func": score_func, "route_scale": 2.826,
                 "norm_eps": 1e-6})
            return {name: v[0] for name, v in outs.items()}

        def loss(x, w, *bias):
            return jnp.sum(fn(x, w, *bias)["TopkWeight"] ** 2)
    else:
        args = [arg((tokens, d), jnp.bfloat16), arg((tokens, k),
                                                    jnp.float32),
                arg((tokens, k), jnp.int32)]

        def fn(x, weight, ids):
            outs = moe_ops.moe_dispatch(
                _ctx("moe_dispatch"),
                {"X": [x], "TopkWeight": [weight], "TopkIds": [ids]},
                {"experts_held": held})
            return {name: v[0] for name, v in outs.items()}

        def loss(x, weight, ids):
            outs = fn(x, weight, ids)
            return (jnp.sum(outs["RowWeight"] ** 2)
                    + jnp.sum(outs["Rows"].astype(jnp.float32) ** 2))
    hlo = _compile(fn if which == "forward"
                   else jax.grad(loss, argnums=(0, 1)), *args)
    made = set(re.findall(r" ([a-z][a-z-]*)\(", hlo))
    assert "sort" in made and "fusion" in made
    assert not made & {"gather", "scatter"}, made & {"gather", "scatter"}
    if op == "dispatch":    # the rows are the kernel's, both ways
        assert hlo.count("tpu_custom_call") == (1 if which == "forward"
                                                else 2)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_one_row_of_a_2d_hbm_ref_is_still_refused(one_chip, dtype):
    """Why ``row_permute`` moves whole tiles: a DMA of single rows of a 2-D
    array, as a row gather would issue them, does not compile for this
    chip. When a later jax lifts the refusal this test says so, and a row
    gather over the live tiles becomes worth a sweep."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def kernel(idx_ref, src_hbm, out_ref, sem):
        def one(r, carry):
            copy = pltpu.make_async_copy(
                src_hbm.at[pl.ds(idx_ref[r], 1)], out_ref.at[pl.ds(r, 1)],
                sem)
            copy.start()
            copy.wait()
            return carry

        jax.lax.fori_loop(0, 8, one, 0)

    def gather(idx, src):
        return pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct((8, src.shape[1]), src.dtype),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=(1,),
                in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
                out_specs=pl.BlockSpec((8, src.shape[1]),
                                       lambda i, idx: (0, 0)),
                scratch_shapes=[pltpu.SemaphoreType.DMA(())]))(idx, src)

    with pytest.raises(Exception, match="aligned to tiling"):
        _compile(gather,
                 jax.ShapeDtypeStruct((8,), jnp.int32, sharding=one_chip),
                 jax.ShapeDtypeStruct((8192, 2304), dtype,
                                      sharding=one_chip))


# -- the Mamba-2 mixer's ops (ops/ssm_ops.py) and the relu2 expert MLP
# (ops/moe_ops.py), at the nemotron_twotower_30b_a3b cell's widths: the scan
# by two kernels of its own (kernels/ssd_scan.py) or in its plain form, the
# rest XLA's

def test_the_relu2_expert_mlp_backward_compiles(one_chip, monkeypatch):
    """One layer's relu2 ``moe_expert_mlp`` with its own backward at
    [49152, 2688] rows, 8 experts of width 1856 (no multiple of 128 lanes:
    megablox pads the last column tile): 6 kernels (two products forward;
    the down projection's and the up projection's transposed products and
    both weights' gradients), the gate XLA's."""
    import sys

    from paddle_tpu.ops import moe_ops

    monkeypatch.setattr(sys.modules["paddle_tpu.kernels.grouped_matmul"],
                        "_on_tpu", lambda: True)
    tokens, d, width, k, held = RELU2_SHAPES["d2688-w1856"]
    rows = tokens * k

    def arg(shape_, dtype):
        return jax.ShapeDtypeStruct(shape_, dtype, sharding=one_chip)

    def loss(rows_, weight_, up_, down_, sizes_):
        up_, down_ = (w.astype(rows_.dtype) for w in (up_, down_))
        out = moe_ops._relu2_expert_mlp(rows_, weight_, sizes_, up_, down_)
        return jnp.sum(out[:1024].astype(jnp.float32))

    hlo = _compile(jax.value_and_grad(loss, argnums=(0, 1, 2, 3)),
                   arg((rows, d), jnp.bfloat16), arg((rows,), jnp.float32),
                   arg((held, d, width), jnp.float32),
                   arg((held, width, d), jnp.float32),
                   arg((held,), jnp.int32))
    assert hlo.count("tpu_custom_call") == 6


SSM = dict(rows=2, seq=4096, heads=64, head_dim=64, groups=8, state=128,
           chunk=128, taps=4)


def _ssm_args(one_chip):
    s = SSM
    x = jax.ShapeDtypeStruct((s["rows"], s["seq"], s["heads"],
                              s["head_dim"]), jnp.bfloat16,
                             sharding=one_chip)
    dt = jax.ShapeDtypeStruct(x.shape[:3], jnp.float32, sharding=one_chip)
    per_head = jax.ShapeDtypeStruct((s["heads"],), jnp.float32,
                                    sharding=one_chip)
    bc = jax.ShapeDtypeStruct((s["rows"], s["seq"], s["groups"],
                               s["state"]), jnp.bfloat16, sharding=one_chip)
    return x, dt, per_head, bc


# a float32 array whose last two axes are a chunk's [Q, Q]: the plain
# form's decays, M and dM
CHUNK_SQUARES = re.compile(r"f32\[(\d+,)*128,128\]")


@pytest.mark.parametrize("which", ["forward", "backward"])
def test_the_chunked_scan_compiles(one_chip, which):
    """The plain form of ``ssd_scan`` (what float32 programs and sizes
    the kernels do not tile run) at [2, 4096, 64 heads of 64] with B and C
    [2, 4096, 8, 128] bf16 and chunks of 128, forward and its own
    backward: no kernel, its float32 ``[..., Q, Q]`` decays through HBM,
    bf16 out for bf16 in, float32 for the float32 leaves, and the
    backward keeps within 2 GB of temporaries."""
    from paddle_tpu.ops.ssm_ops import _ssd_scan

    x, dt, per_head, bc = _ssm_args(one_chip)

    def forward(x_, dt_, a_, b_, c_, d_):
        return _ssd_scan(x_, dt_, a_, b_, c_, d_, SSM["chunk"])

    def backward(x_, dt_, a_, b_, c_, d_, g):
        return jax.vjp(forward, x_, dt_, a_, b_, c_, d_)[1](g)

    args = (x, dt, per_head, bc, bc, per_head)
    fn, args = ((forward, args) if which == "forward"
                else (backward, args + (x,)))
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" not in compiled.as_text()
    assert CHUNK_SQUARES.search(compiled.as_text())
    assert compiled.memory_analysis().temp_size_in_bytes < 2e9
    got = jax.eval_shape(fn, *args)
    if which == "forward":
        assert (got.shape, got.dtype) == (x.shape, x.dtype)
    else:
        assert [(g.shape, g.dtype) for g in got] == [
            (a.shape, a.dtype) for a in args[:-1]]


def test_the_chunked_scan_kernels_compile(one_chip, monkeypatch):
    """The same scan where the TPU takes the two kernels: the forward op's
    call and its replay inside the grad op's ``jax.vjp`` (one jitted
    function, so that XLA merges their custom calls) and the backward,
    two Mosaic calls in all, and no float32 ``[..., Q, Q]`` array left in
    the optimized HLO: the decays and products stay in VMEM."""
    from paddle_tpu.kernels import ssd_scan as kernel

    monkeypatch.setattr(kernel, "_on_tpu", lambda: True)
    x, dt, per_head, bc = _ssm_args(one_chip)
    assert kernel.by_kernel(x, bc, SSM["chunk"])

    def scan(*a):
        return kernel.scan(*a, SSM["chunk"])

    def step(x_, dt_, a_, b_, c_, d_, g):
        y = scan(x_, dt_, a_, b_, c_, d_)
        return y, jax.vjp(scan, x_, dt_, a_, b_, c_, d_)[1](g)

    args = (x, dt, per_head, bc, bc, per_head, x)
    hlo = _compile(step, *args)
    assert hlo.count("tpu_custom_call") == 2
    assert "ssd_scan_fwd" in hlo and "ssd_scan_bwd" in hlo
    assert not CHUNK_SQUARES.search(hlo), CHUNK_SQUARES.search(hlo).group()
    y, grads = jax.eval_shape(step, *args)
    assert (y.shape, y.dtype) == (x.shape, x.dtype)
    assert [(g.shape, g.dtype) for g in grads] == [
        (a.shape, a.dtype) for a in args[:-1]]


# a float32 array of the gated norm's whole [rows, I]: what XLA's form and
# its autodiff keep and pass between fusions
NORM_F32 = re.compile(r"f32\[(2,4096|8192),4096\]")


def test_the_gated_norm_kernels_compile(one_chip, monkeypatch):
    """``gated_rms_norm`` at the cell's [2, 4096, 4096] bf16 in 8 groups
    of 512 where the TPU takes the two kernels: the forward op's call and
    its replay inside the grad op's ``jax.vjp`` merged, and the backward,
    two Mosaic calls in all within their VMEM limit, and no float32
    [rows, I] array left in the optimized HLO (the residuals are X, Z and
    the [8, 8192] float32 rstd)."""
    from paddle_tpu.kernels import gated_norm as kernel

    monkeypatch.setattr(kernel, "_on_tpu", lambda: True)
    x = jax.ShapeDtypeStruct((SSM["rows"], SSM["seq"],
                              SSM["heads"] * SSM["head_dim"]), jnp.bfloat16,
                             sharding=one_chip)
    scale = jax.ShapeDtypeStruct(x.shape[-1:], jnp.float32, sharding=one_chip)
    assert kernel.by_kernel(x, x, scale, SSM["groups"])

    def norm(*a):
        return kernel.norm(*a, SSM["groups"], 1e-5)

    def step(x_, z_, s_, g):
        y = norm(x_, z_, s_)
        return y, jax.vjp(norm, x_, z_, s_)[1](g)

    hlo = _compile(step, x, x, scale, x)
    assert hlo.count("tpu_custom_call") == 2
    assert "gated_norm_fwd" in hlo and "gated_norm_bwd" in hlo
    assert not NORM_F32.search(hlo), NORM_F32.search(hlo).group()
    assert "f32[8,8192]" in hlo
    y, grads = jax.eval_shape(step, x, x, scale, x)
    assert (y.shape, y.dtype) == (x.shape, x.dtype)
    assert [(g.shape, g.dtype) for g in grads] == [
        (a.shape, a.dtype) for a in (x, x, scale)]


def _lowered_cell_step(topo, monkeypatch, config, rows, counters):
    """A cell's step (its configuration's own sizes, bf16) lowered for a
    described v5e with the kernels' call sites seeing the TPU -> (the
    lowered text, what each of ``counters`` counted meanwhile)."""
    import importlib
    import json

    import paddle_tpu.fluid as fluid
    from paddle_tpu import observability as obs
    from paddle_tpu.core.types import convert_dtype_to_np
    from paddle_tpu.models import decoder_lm

    for name in ("flash_attention", "grouped_matmul", "row_permute",
                 "ssd_scan", "gated_norm"):
        monkeypatch.setattr(importlib.import_module(
            "paddle_tpu.kernels." + name), "_on_tpu", lambda: True)
    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmarks", "configs",
            config + ".json")) as f:
        cfg = json.load(f)
    obs.set_enabled(True)
    before = [obs.counter_value(n) for n in counters]
    with fluid.unique_name.guard():
        main, _, handle = decoder_lm.get_model(batch_size=rows, lr=1e-4,
                                               **cfg["model"])
    fluid.contrib.mixed_precision.enable_bf16(main)
    exe, block = fluid.Executor(), main.desc.block(0)
    seq = cfg["model"]["seq_len"]
    names, values = exe.engine._coerce_feed(block, {
        "ids": np.zeros((rows, seq), np.int64),
        "labels": np.zeros((rows, seq), np.int64)})
    step = exe.engine.get_compiled(main.desc, 0, names, values,
                                   [handle["loss"].name], False, True, True,
                                   1)
    one_chip = SingleDeviceSharding(topo.devices[0])

    def shape(name):
        var = block.find_var_recursive(name)
        return jax.ShapeDtypeStruct(tuple(var.shape),
                                    convert_dtype_to_np(var.dtype),
                                    sharding=one_chip)

    text = step.jitted.lower(
        [jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=one_chip)
         for v in values],
        [shape(n) for n in step.mutated_names],
        [shape(n) for n in step.readonly_names],
        (np.uint32(0), np.uint32(1))).as_text()
    exe.close()
    return text, {n: obs.counter_value(n) - b
                  for n, b in zip(counters, before)}


def test_the_cells_step_takes_the_scan_kernels(topo, monkeypatch):
    """The ``nemotron_twotower_30b_a3b`` cell's step lowered for a
    described v5e: its three mixers' scans take the kernels both ways,
    ``ssm.scan_kernel`` / ``ssm.scan_xla`` 6 / 0 (0 / 6 on the CPU:
    ``tests/test_nemotron.py``), and their gated norms too,
    ``ssm.gated_norm_kernel`` / ``ssm.gated_norm_xla`` 3 / 0 (0 / 3)."""
    text, counted = _lowered_cell_step(
        topo, monkeypatch, "nemotron_twotower_30b_a3b", 2,
        ("ssm.scan_kernel", "ssm.scan_xla", "ssm.gated_norm_kernel",
         "ssm.gated_norm_xla", "flash.split_v_calls"))
    assert counted == {"ssm.scan_kernel": 6, "ssm.scan_xla": 0,
                       "ssm.gated_norm_kernel": 3, "ssm.gated_norm_xla": 0,
                       "flash.split_v_calls": 0}
    assert "ssd_scan_fwd" in text and "ssd_scan_bwd" in text
    assert "gated_norm_fwd" in text and "gated_norm_bwd" in text


def test_the_latent_attention_step_takes_the_flash_kernels(topo,
                                                           monkeypatch):
    """The ``joyai_llm_flash`` cell's step (1 x 4096, bf16) lowered for a
    described v5e: 5 layers and the multi-token-prediction module, each
    with latent attention, take the Pallas flash kernels at q.k 192 / v
    128, 6 forward and 6 one-kernel backward calls (``flash.bwd_fused``;
    the pair is not needed at 4096 positions); q_pe and k_pe of each are
    rotated by interleaved pairs."""
    text, counted = _lowered_cell_step(
        topo, monkeypatch, "joyai_llm_flash", 1,
        ("flash.split_v_calls", "flash.bwd_fused", "flash.bwd_split",
         "rope.interleaved", "attn.mla_layers", "decoder.mtp_depth"))
    assert counted == {"flash.split_v_calls": 12, "flash.bwd_fused": 6,
                       "flash.bwd_split": 0, "rope.interleaved": 12,
                       "attn.mla_layers": 6, "decoder.mtp_depth": 1}
    assert text.count("tpu_custom_call") >= 12


def test_the_latent_moe_step_takes_the_kernels(topo, monkeypatch):
    """The ``nemotron3_super_120b_a12b`` cell's step (1 x 4096, bf16)
    lowered for a described v5e: the six LatentMoE layers (five and the
    MTP module's) move their 1,024-wide latent rows into and out of a
    buffer of 1,536 rows (the 8 held experts' capped 1,408 pairs in whole
    tiles) by the row kernel, four call sites each; the five Mamba
    mixers' scans and gated norms take their kernels at 16 heads of 64 in
    one group; the two attentions (4 query heads on 1) the one-kernel
    flash backward."""
    text, counted = _lowered_cell_step(
        topo, monkeypatch, "nemotron3_super_120b_a12b", 1,
        ("moe.permute_kernel", "moe.permute_xla", "ssm.scan_kernel",
         "ssm.scan_xla", "ssm.gated_norm_kernel", "ssm.gated_norm_xla",
         "flash.bwd_fused", "flash.bwd_split", "moe.latent_layers",
         "decoder.mtp_layers", "moe.relu2_layers"))
    assert counted == {"moe.permute_kernel": 24, "moe.permute_xla": 0,
                       "ssm.scan_kernel": 10, "ssm.scan_xla": 0,
                       "ssm.gated_norm_kernel": 5, "ssm.gated_norm_xla": 0,
                       "flash.bwd_fused": 2, "flash.bwd_split": 0,
                       "moe.latent_layers": 6, "decoder.mtp_layers": 2,
                       "moe.relu2_layers": 6}
    assert "tensor<1536x1024xbf16>" in text


@pytest.mark.parametrize("form", ["forward", "fused", "split"])
def test_flash_at_a_values_width_of_its_own_compiles(one_chip, monkeypatch,
                                                     form):
    """The cell's attention, [1, 32, 4096] with q and k 192 wide and v 128,
    causal, bf16: the forward kernel, the one-kernel backward (which the
    reckoning admits at this length) and the dQ + dK/dV pair each compile
    to their custom calls, the output and dV 128 wide."""
    import sys

    T = 4096
    q = jax.ShapeDtypeStruct((1, 32, T, 192), jnp.bfloat16, sharding=one_chip)
    v = jax.ShapeDtypeStruct((1, 32, T, 128), jnp.bfloat16, sharding=one_chip)
    _, lse, _ = _attention_args((1, 32, T, 192), one_chip, False)
    blk = pick_block(T, jnp.bfloat16)
    assert _bwd_fused_fits(T, 192, jnp.bfloat16,
                           *pick_bwd_blocks(T, T, jnp.bfloat16, (blk, blk)),
                           dv=128)
    if form == "split":
        monkeypatch.setattr(sys.modules["paddle_tpu.kernels.flash_attention"],
                            "_bwd_fused_fits", lambda *a: False)

    def forward(q_, k_, v_):
        return flash_attention_raw_lse(q_, k_, v_, None, 0, True,
                                       192 ** -0.5, 0.0, blk, blk, False)

    def backward(q_, k_, v_, o_, lse_, g_):
        return _flash_backward(q_, k_, v_, o_, lse_, g_, None, None, None, 0,
                               True, 192 ** -0.5, 0.0, blk, blk, False)

    if form == "forward":
        fn, args = forward, (q, q, v)
        shapes = [(1, 32, T, 128), (1, 32, T, _LSE_LANES)]
    else:
        fn, args = backward, (q, q, v, v, lse, v)
        shapes = [(1, 32, T, 192), (1, 32, T, 192), (1, 32, T, 128)]
    hlo = _compile(fn, *args)
    assert hlo.count("tpu_custom_call") == (2 if form == "split" else 1)
    assert [tuple(x.shape) for x in jax.eval_shape(fn, *args)] == shapes


def _mosaic_kernels(text):
    """The Mosaic kernels of a lowered text's TPU custom calls, printed
    without source locations (which move whenever a line above a kernel
    does), each followed by the rest of its configuration."""
    import base64
    import json

    from jax._src.lib.mlir import ir

    out = []
    for config in re.findall(r'backend_config = "([^"]*)"', text):
        config = json.loads(config.replace("\\22", '"'))["custom_call_config"]
        with ir.Context() as ctx:
            ctx.allow_unregistered_dialects = True
            module = ir.Module.parse(base64.b64decode(config.pop("body")))
            out.append(module.operation.get_asm(enable_debug_info=False))
        out.append(json.dumps(config, sort_keys=True))
    return "\n".join(out)


# (B, Hq, Hkv, T, D, causal, masked by seq_lens, window): the flash calls of
# the accepted cells' steps, and the digest of their kernels. BERT's (not
# causal, key lengths) as lowered at a5a425e, before V could have a width of
# its own; the causal cells' as lowered since their live tiles are sorted
# into classes (full tiles without the mask, diagonal and window-edge tiles
# in row bands), which changes each causal kernel's body
ACCEPTED_FLASH = {
    "bert_base_s2048": ((8, 12, 12, 2048, 64, False, True, None),
                        "8d6993e9dfdc66d1"),
    "mellum2_12b.window": ((2, 32, 4, 4096, 128, True, False, 1024),
                           "a31b33901d3edd5e"),
    "mellum2_12b.full": ((2, 32, 4, 4096, 128, True, False, None),
                         "d64a53be2dcff014"),
    "trinity_mini.window": ((2, 32, 4, 3072, 128, True, False, 2048),
                            "0ea2668f470f4244"),
    "trinity_mini.full": ((2, 32, 4, 3072, 128, True, False, None),
                          "cb1e5f9ec37a76f1"),
    "lfm2_24b_a2b": ((2, 32, 8, 8192, 64, True, False, None),
                     "2e162388878e4d4e"),
    "nemotron_twotower_30b_a3b": ((2, 32, 2, 4096, 128, True, False, None),
                                  "9bde517fb49c76b1"),
}


@pytest.mark.parametrize("cell", list(ACCEPTED_FLASH))
def test_the_accepted_cells_flash_kernels_are_lowered_as_before(one_chip,
                                                                cell):
    """Forward and backward at each accepted cell's shapes, bf16, as the
    fused_attention ops call them: the Mosaic kernels in their custom
    calls are those pinned above (BERT's as lowered before V could be
    narrower than Q and K)."""
    import hashlib

    (b, hq, hkv, t, d, causal, masked, window), digest = ACCEPTED_FLASH[cell]
    q = jax.ShapeDtypeStruct((b, hq, t, d), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((b, hkv, t, d), jnp.bfloat16,
                              sharding=one_chip)
    _, lse, lens = _attention_args((b, hq, t, d), one_chip, masked)
    block = pick_block(t, jnp.bfloat16)

    def forward(q_, k_, v_, lens_):
        return flash_attention_raw_lse(q_, k_, v_, lens_, 0, causal,
                                       d ** -0.5, 0.0, block, block, False,
                                       window)

    def backward(q_, k_, v_, o_, lse_, g_, lens_):
        return _flash_backward(q_, k_, v_, o_, lse_, g_, None, lens_, None,
                               0, causal, d ** -0.5, 0.0, block, block,
                               False, window)

    kernels = (
        _mosaic_kernels(jax.jit(forward).lower(q, kv, kv, lens).as_text())
        + "\n" + _mosaic_kernels(jax.jit(backward).lower(
            q, kv, kv, q, lse, q, lens).as_text()))
    assert hashlib.sha256(kernels.encode()).hexdigest()[:16] == digest


@pytest.mark.parametrize("which", ["forward", "backward"])
def test_the_mixer_conv_compiles(one_chip, which):
    """``ssm_conv1d`` at [2, 4096, 6144] bf16 with 4 taps and a bias,
    forward and its own backward: no kernel, the input's dtype out."""
    from paddle_tpu.ops.ssm_ops import _ssm_conv1d

    channels = SSM["heads"] * SSM["head_dim"] + 2 * SSM["groups"] * SSM[
        "state"]
    x = jax.ShapeDtypeStruct((SSM["rows"], SSM["seq"], channels),
                             jnp.bfloat16, sharding=one_chip)
    w = jax.ShapeDtypeStruct((channels, SSM["taps"]), jnp.float32,
                             sharding=one_chip)
    b = jax.ShapeDtypeStruct((channels,), jnp.float32, sharding=one_chip)

    def backward(x_, w_, b_, g):
        return jax.vjp(_ssm_conv1d, x_, w_, b_)[1](g)

    fn, args = ((_ssm_conv1d, (x, w, b)) if which == "forward"
                else (backward, (x, w, b, x)))
    assert "tpu_custom_call" not in _compile(fn, *args)
    got = jax.eval_shape(fn, *args)
    if which == "forward":
        assert (got.shape, got.dtype) == (x.shape, x.dtype)
    else:
        assert [(g.shape, g.dtype) for g in got] == [
            (a.shape, a.dtype) for a in args[:-1]]
