"""A mixture-of-experts layer as four ops, for a chip that holds some of
the experts (``experts_held`` of ``num_experts``, from ``expert_offset``):

    moe_router      scores over ALL experts in float32 (softmax, or a
                    sigmoid each), top-k (by score + a bias that takes no
                    gradient, where one is given), weights from the scores
                    alone, renormalised over the k chosen, times a scale
    moe_bias_update the balancing bias as state: after the backward, once a
                    step, from the loads the router counted
    moe_dispatch    the token-expert pairs that fall on held experts,
                    sorted by expert, their token rows (and weights)
                    gathered into a buffer; second output: tokens each
                    held expert received
    moe_expert_mlp  down(w * silu(gate(x)) * up(x)) per expert: three
                    grouped matmuls over the buffer with the gate
                    between them, and a backward of its own
                    (``_expert_mlp``): five more, the rows' gradient from
                    gate and up in one of them, and the gate's transpose
    moe_combine     each token's sum over its held pairs' rows

Dropless: the buffer has a row for every pair (tokens x k; a token's k
experts are distinct and up to all of them may be held here), so no
routing can overflow it; the grouped matmuls visit only the rows in use.
What the experts held elsewhere would add is left out: on one chip there
is no exchange and nothing stands in for it. A token's weights are
renormalised over all k of its experts, held or not.

The pair's weight multiplies the row before the down projection (which is
linear, so the sum is the weighted combine): the combine's backward then
needs nothing saved, and the weight's gradient falls out of the MLP's
backward over ``width`` columns instead of ``d``.

The token <-> buffer traffic is four movements a layer, two shapes:
``_rows_of_tokens`` (a row's token: ``moe_dispatch`` forward,
``moe_combine`` backward) and ``_sums_of_rows`` (a token's sum over its
pairs' rows: ``moe_combine`` forward, ``moe_dispatch`` backward). On the
TPU, for bf16 rows of whole lanes and whole tiles, both are the Pallas
kernel of ``kernels/row_permute.py``: whole tiles fetched, the permutation
inside a tile a 0/1 product on the MXU, only the tiles in use visited.
Everywhere else (the CPU, float32 programs, odd sizes) both are XLA gathers
(a row's token one way, a pair's row the other), never a scatter-add: each
pair has one row, so the inverse permutation is known.

**The scalars of a pair** (its weight, its weight's gradient, its score)
are moved by no gather and no scatter either, on any backend: the chip does
a gather or a scatter of single elements one element at a time, 6-10 ns a
gathered and up to 17 a scatter-added one: 0.3-0.7 and 0.4-1.1 ms for the
49,152-65,536 pairs of a layer, where a sort of as many keys takes
0.04-0.08 (PERF.md, Findings PR 38). A permutation of scalars rides
in a sort: ``moe_dispatch``'s sort by expert carries the flattened
``TopkWeight`` as a payload, so ``RowWeight`` comes out in row order (until
PR 38 ``weight[PairOfRow]``), and the weights' gradient goes back to pair
order as the payload of a sort by ``PairOfRow`` (``g[RowOfPair]``):
``_by_expert``. A choice of k of E scores is a compare over the E outputs:
``moe_router`` ranks with ``top_k`` for the ids alone and takes the scores
by ``sum_e where(ids == e, probs, 0)`` (``take_along_axis``, or ``top_k``'s
own values, whose transpose is a scatter-add into [tokens, E]); the
gradient is the dense mirror, each entry one term or none: ``_chosen``.

Which form of a row movement a call site was lowered as is counted
(``moe.permute_kernel`` / ``moe.permute_xla``), and so are the forms of the
expert MLP's two-pair
product (``moe.gmm_pair_kernel`` / ``moe.gmm_pair_xla``: one call site a
layer, counted where the backward is traced) and of its gate
(``moe.gate_kernel`` / ``moe.gate_xla``: two a layer, the forward's
counted by the op's lowering, the transpose where the backward is traced;
where the kernels run, the gauges ``moe.gate_tiles`` and, a step,
``moe.gate_tiles_live``: tiles in the buffer and tiles visited). Unused
rows of a grouped matmul's result are unspecified, so whatever leaves the
buffer is picked with ``where``, never by a product with zero; what a
kernel writes into the buffer's unused tiles, or leaves there, is
unspecified too.
"""

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from paddle_tpu.core.registry import register_no_grad_op, register_op
from paddle_tpu.kernels import grouped_matmul as gm
from paddle_tpu.kernels import row_permute
from paddle_tpu.ops.common import amp_cast, lowered_into_a_step, single


@register_op("moe_router", no_grad_inputs=("Bias",))
def moe_router(ctx, ins, attrs):
    """X [..., d], Weight [d, E], optionally Bias [E] -> TopkWeight [N, k]
    float32, TopkIds [N, k] int32 and, where a Bias is given, Load [E]
    int32: how many of the N * k selections fell on each router output,
    held here or not. ``score_func`` ``softmax`` (the default): softmax
    over all E; ``sigmoid``: a sigmoid each. The k are the largest of score
    + Bias (the bias ranks and takes no gradient); their weights are the
    scores alone, renormalised over the k (a sigmoid router's over their
    sum + ``norm_eps``, 1e-20 unless given) and times ``route_scale``.
    Logits and scores in float32 whatever the program's precision: the
    top-k is a discontinuity, and a rounded score flips it."""
    from paddle_tpu import observability as obs

    x, w, bias = single(ins, "X"), single(ins, "Weight"), single(ins, "Bias")
    k = int(attrs["k"])
    sigmoid = attrs.get("score_func", "softmax") == "sigmoid"
    x2 = x.reshape(-1, x.shape[-1]).astype(jnp.float32)
    logits = jnp.dot(x2, w.astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)
    if sigmoid:
        probs = jax.nn.sigmoid(logits)
    else:
        probs = jax.nn.softmax(logits, axis=-1)
    _, ids = lax.top_k(
        lax.stop_gradient(probs if bias is None else probs + bias), k)
    top = _chosen(probs, ids)
    if sigmoid:     # (sigmoids can all be small; a softmax's top-k cannot)
        top = top / (jnp.sum(top, axis=-1, keepdims=True)
                     + float(attrs.get("norm_eps", 1e-20)))
    else:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    scale = float(attrs.get("route_scale", 1.0))
    if scale != 1.0:
        top = top * scale
    outs = {"TopkWeight": [top], "TopkIds": [ids.astype(jnp.int32)]}
    if bias is not None:
        load = jnp.sum(ids.reshape(-1)[:, None] == jnp.arange(
            probs.shape[-1], dtype=ids.dtype)[None, :], axis=0,
            dtype=jnp.int32)
        outs["Load"] = [load]
    if lowered_into_a_step(ctx, "moe_router") and obs.enabled():
        if sigmoid:
            obs.inc("moe.router_sigmoid")
        if bias is not None:
            jax.debug.callback(_publish_router_load, load)
    return outs


def _is_chosen(ids, experts):
    """[N, k, E] bool: router output e is token t's j-th choice."""
    return ids[:, :, None] == lax.broadcasted_iota(jnp.int32,
                                                   (1, 1, experts), 2)


@jax.custom_vjp
def _chosen(probs, ids):
    """top[t, j] = probs[t, ids[t, j]], by a compare over the E outputs and
    a sum (one term and zeros), not ``take_along_axis``: the module's
    docstring says why. The backward keeps ``ids`` alone."""
    return jnp.sum(jnp.where(_is_chosen(ids, probs.shape[1]),
                             probs[:, None, :], 0), axis=2)


def _chosen_fwd(probs, ids):
    # (an empty array carries E and the scores' dtype to the backward)
    return _chosen(probs, ids), (ids, jnp.zeros((0, probs.shape[1]),
                                                probs.dtype))


def _chosen_bwd(res, g):
    ids, like = res
    # a token's k choices are distinct: each entry gets one term or none,
    # which is what the gather's transpose, a scatter-add, wrote
    return (jnp.sum(jnp.where(_is_chosen(ids, like.shape[1]),
                              g[:, :, None], 0), axis=1).astype(like.dtype),
            None)


_chosen.defvjp(_chosen_fwd, _chosen_bwd)


def _publish_router_load(load):
    """Per step, under the ``metrics`` flag: the busiest router output's
    selections over the mean, over ALL outputs (``moe.load_max_over_mean``
    is over the experts held)."""
    from paddle_tpu import observability as obs

    load = np.asarray(load)
    obs.set_gauge("moe.router_load_max_over_mean",
                  float(load.max() / max(load.mean(), 1e-9)))


@register_no_grad_op("moe_bias_update", inplace_map={"BiasOut": "Bias"})
def moe_bias_update(ctx, ins, attrs):
    """Bias [E] float32, Load [E] -> BiasOut [E]: the auxiliary-loss-free
    balancing of Wang et al. (arXiv:2408.15664), the mean taken out:
    ``delta = coeff * sign(mean(load) - load)``, ``delta -= mean(delta)``,
    ``bias += delta``. State that the step updates itself, not through the
    optimizer; the op carries ``op_role`` Optimize, so that it runs once a
    step after the backward and the next step selects with what it left."""
    from paddle_tpu import observability as obs

    bias = single(ins, "Bias")
    load = single(ins, "Load").astype(jnp.float32)
    delta = float(attrs["coeff"]) * jnp.sign(jnp.mean(load) - load)
    if lowered_into_a_step(ctx, "moe_bias_update"):
        obs.inc("moe.bias_updates")
    return {"BiasOut": [bias + (delta - jnp.mean(delta)).astype(bias.dtype)]}


def _held(ids, attrs):
    """(pair is held here [N, k] bool, its local expert [N, k] int32)."""
    local = ids - int(attrs.get("expert_offset", 0))
    return (local >= 0) & (local < int(attrs["experts_held"])), local


def _by_kernel(like, row_of_pair):
    """Whether ``row_permute`` moves rows like ``like`` ([., d]) for these
    tokens x k pairs: decided by what the call site can see, not by a flag."""
    tokens, k = row_of_pair.shape
    return row_permute.applies(tokens, tokens * k, like.shape[1], like.dtype)


def _count_form(by_kernel):
    from paddle_tpu import observability as obs

    obs.inc("moe.permute_kernel" if by_kernel else "moe.permute_xla")


def _live(counts, rows):
    return jnp.arange(rows, dtype=jnp.int32) < jnp.sum(counts)


def _rows_of_tokens_xla(x, pair_of_row, counts, k):
    return jnp.where(_live(counts, pair_of_row.shape[0])[:, None],
                     x[pair_of_row // k], 0)


def _rows_of_tokens(x, pair_of_row, counts, row_of_pair):
    """rows[r] = x[token of row r] where the row is in use (else 0, or
    unspecified in a tile the kernel never visits)."""
    k = row_of_pair.shape[1]
    if _by_kernel(x, row_of_pair):
        return row_permute.expand(x, pair_of_row, counts, k)
    return _rows_of_tokens_xla(x, pair_of_row, counts, k)


def _sums_of_rows_xla(rows, row_of_pair, pair_held, dtype):
    return jnp.sum(jnp.where(pair_held[..., None], rows[row_of_pair], 0)
                   .astype(jnp.float32), axis=1).astype(dtype)


def _sums_of_rows(rows, row_of_pair, pair_held, pair_of_row, counts, dtype):
    """out[t] = the float32 sum of the rows of t's held pairs, as ``dtype``:
    by the inverse permutation, no scatter-add."""
    if _by_kernel(rows, row_of_pair):
        return row_permute.reduce(rows, pair_of_row, counts,
                                  row_of_pair.shape[1], dtype)
    return _sums_of_rows_xla(rows, row_of_pair, pair_held, dtype)


@jax.custom_vjp
def _by_expert(key, weight):
    """(PairOfRow [R] int32, each row's pair's weight [R]): the pairs in
    the order of a stable sort by ``key``, their weights carried through
    the sort as its payload instead of gathered after it. The pair's index
    is the low digits of what is sorted (``key * R + pair``: the caller
    sees to it that this fits int32), so the keys are distinct and the sort
    need be neither stable nor carry an iota of its own: the chip's
    compiler is slow on sorts, and a stable one of (key, iota, weight)
    added 16-25 s to a step's 31-54 s of compile where this form adds 3-14
    (this sandbox's CPU, the three decoder steps for a described v5e; on
    the chip's host either form read 7-22 s more than the parent, the two
    never on one machine: PERF.md, Findings PR 38). Backward: the rows'
    gradient back in pair order by one sort more (``PairOfRow`` is a
    permutation of 0 .. R-1, so sorted by it, position p holds the row of
    pair p)."""
    rows = key.shape[0]
    digits, in_rows = lax.sort(
        (key * rows + lax.iota(jnp.int32, rows), weight), num_keys=1)
    return digits % rows, in_rows


def _by_expert_fwd(key, weight):
    pair_of_row, in_rows = _by_expert(key, weight)
    return (pair_of_row, in_rows), pair_of_row


def _by_expert_bwd(pair_of_row, g):
    return None, lax.sort((pair_of_row, g[1]), num_keys=1)[1]


_by_expert.defvjp(_by_expert_fwd, _by_expert_bwd)


@jax.custom_vjp
def _to_rows(x, weight, pair_of_row, counts, row_of_pair, pair_held):
    """(rows[r] = x[token of row r], ``weight[r]``, the row's pair's)
    where the row is in use, else 0."""
    return (_rows_of_tokens(x, pair_of_row, counts, row_of_pair),
            jnp.where(_live(counts, pair_of_row.shape[0]), weight, 0))


def _to_rows_fwd(x, weight, pair_of_row, counts, row_of_pair, pair_held):
    return (_to_rows(x, weight, pair_of_row, counts, row_of_pair, pair_held),
            (pair_of_row, counts, row_of_pair, pair_held))


def _to_rows_bwd(res, g):
    pair_of_row, counts, row_of_pair, pair_held = res
    g_rows, g_weight = g
    _count_form(_by_kernel(g_rows, row_of_pair))
    dx = _sums_of_rows(g_rows, row_of_pair, pair_held, pair_of_row, counts,
                       g_rows.dtype)
    # (a dead row's gradient is unspecified: selected out, never multiplied)
    return (dx, jnp.where(_live(counts, pair_of_row.shape[0]), g_weight, 0),
            None, None, None, None)


_to_rows.defvjp(_to_rows_fwd, _to_rows_bwd)


@register_op("moe_dispatch", no_grad_inputs=("TopkIds",))
def moe_dispatch(ctx, ins, attrs):
    """X [N, d], TopkWeight and TopkIds [N, k] -> Rows [N*k, d] (the held
    pairs' token rows, sorted by expert, in the program's compute dtype),
    Counts [experts_held] int32 (tokens each held expert received),
    RowWeight [N*k] float32 (each row's pair's weight), RowOfPair [N, k]
    and PairOfRow [N*k] int32 (the permutation and its inverse; pair
    ``t * k + j`` is token ``t``'s ``j``-th expert)."""
    from paddle_tpu import observability as obs

    x, ids = single(ins, "X"), single(ins, "TopkIds")
    held_n = int(attrs["experts_held"])
    n, k = ids.shape
    held, local = _held(ids, attrs)
    # held pairs first, by expert; the others after them in any order
    key = jnp.where(held, local, held_n).reshape(-1)
    if (held_n + 1) * n * k > 2 ** 31:
        raise ValueError(
            "moe_dispatch: %d pairs on %d held experts: a pair's index and "
            "its expert do not fit one int32 sort key" % (n * k, held_n))
    order, weight = _by_expert(key, single(ins, "TopkWeight").reshape(-1))
    row_of_pair = jnp.argsort(order).astype(jnp.int32).reshape(n, k)
    counts = jnp.sum(
        key[:, None] == jnp.arange(held_n, dtype=jnp.int32)[None, :],
        axis=0, dtype=jnp.int32)
    rows, row_weight = _to_rows(amp_cast(x), weight, order, counts,
                                row_of_pair, held)
    if lowered_into_a_step(ctx, "moe_dispatch") and obs.enabled():
        by_kernel = _by_kernel(rows, row_of_pair)
        _count_form(by_kernel)
        obs.inc("moe.layers")
        obs.set_gauge("moe.buffer_rows", n * k)
        obs.set_gauge("moe.experts_held", held_n)
        jax.debug.callback(
            _publish_load, counts,
            row_permute.visits(order, counts, k, n, False)[0]
            if by_kernel else None)
    return {"Rows": [rows], "Counts": [counts], "RowWeight": [row_weight],
            "RowOfPair": [row_of_pair], "PairOfRow": [order]}


def _publish_load(counts, visits):
    """Per step, under the ``metrics`` flag: pairs that fell on held
    experts, the busiest held expert's load over the mean and, where the
    kernel moves the rows, the (tile, chunk) visits of one movement."""
    from paddle_tpu import observability as obs

    counts = np.asarray(counts)
    if visits is not None:
        obs.set_gauge("moe.permute_visits", int(np.asarray(visits)[0]))
    obs.set_gauge("moe.pairs_held", int(counts.sum()))
    obs.set_gauge("moe.load_max_over_mean",
                  float(counts.max() / max(counts.mean(), 1e-9)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _expert_mlp(rows, row_weight, counts, wg, wu, wd, interpret=False):
    """down(w * silu(gate(x)) * up(x)) per expert, with a backward of its
    own: forward three grouped matmuls and the gate between them; backward
    the down projection's transposed product, the gate's transpose, the
    rows' gradient from gate and up in ONE kernel that adds both products
    in its float32 accumulator (autodiff would round each to the rows'
    dtype and add them in a pass over all buffer rows), and three weights'
    gradients. The gate and its transpose are the kernels over the live
    row tiles where ``gate_by_kernel``, else XLA's: ``gm.silu_gate`` and
    ``jax.vjp`` of it over all rows. Every reader of their results goes
    by ``counts`` (the grouped products) or selects the dead rows out with
    ``where`` (``_to_rows_bwd``, of the weights' gradient). The weights
    arrive in the rows' dtype (the caller casts the masters, so the cast's
    transpose rides in whatever reads the gradient)."""
    return _expert_mlp_fwd(rows, row_weight, counts, wg, wu, wd,
                           interpret)[0]


def _expert_mlp_fwd(rows, row_weight, counts, wg, wu, wd, interpret):
    gate = gm.grouped_matmul(rows, wg, counts, interpret)
    up = gm.grouped_matmul(rows, wu, counts, interpret)
    if gm.gate_by_kernel(*gate.shape, interpret):
        hidden = gm.gated(gate, up, row_weight, counts, interpret)
    else:
        hidden = gm.silu_gate(gate, up, row_weight)
    return (gm.grouped_matmul(hidden, wd, counts, interpret),
            (rows, row_weight, counts, wg, wu, wd, gate, up, hidden))


def _expert_mlp_bwd(interpret, res, g):
    from paddle_tpu import observability as obs

    rows, row_weight, counts, wg, wu, wd, gate, up, hidden = res
    obs.inc("moe.gmm_pair_kernel" if gm.pair_by_kernel(
        rows.shape[0], wg.shape[1], wg.shape[2], interpret)
        else "moe.gmm_pair_xla")
    by_kernel = gm.gate_by_kernel(*gate.shape, interpret)
    obs.inc("moe.gate_kernel" if by_kernel else "moe.gate_xla")
    d_hidden = gm.grouped_matmul_t(g, wd, counts, interpret)
    if by_kernel:
        d_gate, d_up, d_weight = gm.gated_t(gate, up, row_weight, d_hidden,
                                            counts, interpret)
    else:
        d_gate, d_up, d_weight = jax.vjp(gm.silu_gate, gate, up,
                                         row_weight)[1](d_hidden)
    d_rows = gm.grouped_matmul_pair_t(d_gate, d_up, wg, wu, counts,
                                      interpret)
    return (d_rows, d_weight, None,
            gm.grouped_weight_gradient(rows, d_gate, counts, interpret),
            gm.grouped_weight_gradient(rows, d_up, counts, interpret),
            gm.grouped_weight_gradient(hidden, g, counts, interpret))


_expert_mlp.defvjp(_expert_mlp_fwd, _expert_mlp_bwd)


@register_op("moe_expert_mlp", no_grad_inputs=("Counts",))
def moe_expert_mlp(ctx, ins, attrs):
    """Rows [R, d] sorted by expert, RowWeight [R], Counts [G], GateWeight
    / UpWeight [G, d, w], DownWeight [G, w, d] -> Out [R, d]: the gated
    SiLU MLP of each row's expert, times the row's weight. Rows past
    sum(Counts) are unspecified."""
    from paddle_tpu import observability as obs

    rows, wg, wu, wd = amp_cast(single(ins, "Rows"),
                                single(ins, "GateWeight"),
                                single(ins, "UpWeight"),
                                single(ins, "DownWeight"))
    counts = single(ins, "Counts")
    if lowered_into_a_step(ctx, "moe_expert_mlp"):
        by_kernel = gm.gate_by_kernel(rows.shape[0], wg.shape[2])
        obs.inc("moe.gate_kernel" if by_kernel else "moe.gate_xla")
        if by_kernel and obs.enabled():
            tile = gm.gate_tile_rows(rows.shape[0], wg.shape[2],
                                     rows.dtype.itemsize)
            obs.set_gauge("moe.gate_tiles", rows.shape[0] // tile)
            jax.debug.callback(_publish_gate_tiles,
                               gm.live_tiles(counts, tile))
    return {"Out": [_expert_mlp(rows, single(ins, "RowWeight"), counts, wg,
                                wu, wd)]}


def _publish_gate_tiles(tiles):
    """Per step, under the ``metrics`` flag: the row tiles the gate's
    kernels visit, of ``moe.gate_tiles`` in the buffer."""
    from paddle_tpu import observability as obs

    obs.set_gauge("moe.gate_tiles_live", int(tiles))


@jax.custom_vjp
def _sum_of_rows(rows, row_of_pair, pair_held, pair_of_row, counts):
    """out[t] = sum of the rows of t's held pairs, float32."""
    return _sums_of_rows(rows, row_of_pair, pair_held, pair_of_row, counts,
                         jnp.float32)


def _sum_fwd(rows, row_of_pair, pair_held, pair_of_row, counts):
    return (_sum_of_rows(rows, row_of_pair, pair_held, pair_of_row, counts),
            # (an empty array carries the rows' dtype to the backward)
            (pair_of_row, counts, row_of_pair,
             jnp.zeros((0,), rows.dtype)))


def _sum_bwd(res, g):
    pair_of_row, counts, row_of_pair, like = res
    # a row's gradient is its token's, in the rows' dtype (the cast and the
    # movement commute)
    g = g.astype(like.dtype)
    _count_form(_by_kernel(g, row_of_pair))
    return (_rows_of_tokens(g, pair_of_row, counts, row_of_pair), None, None,
            None, None)


_sum_of_rows.defvjp(_sum_fwd, _sum_bwd)


@register_op("moe_combine",
             no_grad_inputs=("TopkIds", "RowOfPair", "PairOfRow", "Counts"))
def moe_combine(ctx, ins, attrs):
    """Rows [N*k, d] (the expert MLP's weighted result), TopkIds,
    RowOfPair, PairOfRow, Counts -> Out [N, d] float32: every token's sum
    over the pairs held here (zero where none is)."""
    rows, row_of_pair = single(ins, "Rows"), single(ins, "RowOfPair")
    held, _ = _held(single(ins, "TopkIds"), attrs)
    if lowered_into_a_step(ctx, "moe_combine"):
        _count_form(_by_kernel(rows, row_of_pair))
    return {"Out": [_sum_of_rows(rows, row_of_pair, held,
                                 single(ins, "PairOfRow"),
                                 single(ins, "Counts"))]}
