"""Rows between the token order and the expert-sorted buffer, a tile at a
time on the MXU: the movement round ``grouped_matmul``'s products.

``moe_dispatch`` sorts the token-expert pairs by held expert with a stable
sort, so inside one expert's segment of the buffer the rows stand in
ascending token order: the rows of one buffer tile (``_TILE_ROWS`` rows)
come from a contiguous range of tokens, and a tile shares rows with only a
few token chunks (``_CHUNK_ROWS`` tokens). Both directions walk the list of
such (buffer tile, token chunk) *visits* and do the permutation inside a
visit as a product with a 0/1 matrix built in the kernel,
``P[t, r] = (token of buffer row r == t)``:

    expand   rows[tile]  = sum over its visits of  P^T . x[chunk]
             (a token's row into each of its pairs' buffer rows: every
             output row has exactly one 1, so with bf16 operands and float32
             accumulation the result is the source row bit for bit)
    reduce   out[chunk]  = sum over its visits of  P . rows[tile]
             (a token's sum over its pairs' rows, accumulated in float32)

Whole aligned blocks are fetched through ``BlockSpec`` index maps that read
the scalar-prefetched list; no row is moved on its own (Mosaic refuses a
one-row slice of a 2-D HBM ref: ``tests/test_tpu_compile.py`` keeps the
refusal).

**Dead rows.** Rows past ``sum(counts)`` belong to no pair. ``expand``
writes zeros into the dead rows of a tile that has live ones and leaves
wholly dead tiles unwritten: their content is unspecified, as
``grouped_matmul``'s is. ``reduce`` may be handed NaN there: it selects the
dead rows out of the tile with ``where`` before the product (a product with
zero would keep the NaN), and a token chunk no pair falls in reads zero.

**The list and its bound.** Tile ``b`` and expert ``e`` overlap in a piece
of consecutive rows whose tokens ascend, so the chunks the piece shares a
row with lie between its first row's and its last row's. Consecutive pieces
of one expert lie in ranges that overlap in at most one chunk, so an expert
with ``p`` pieces makes at most ``chunks - 1 + p`` visits; tiles and
segments are both runs of consecutive rows, so there are at most ``tiles +
experts - 1`` pieces in all: never more than ``experts * chunks + tiles``
visits however the router routes (1,024 at 8,192 tokens, 65,536 rows and 16
held experts, of which 630 to 770 are used). ``reduce`` visits every chunk
at least once, ``chunks`` more. The grid is as long as the list is used,
not as its bound. The same set of pairs is listed in two orders, by tile
for ``expand`` and by chunk for ``reduce``, so that the visits that
accumulate into one output block are consecutive. The list is made with
compares, sums and small products (``_where_true``), not ``nonzero``. The
cost follows tokens x experts held, not the pairs: the unused part of a
dropless buffer costs nothing.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# buffer rows of a tile and tokens of a chunk; read on a v5e at the
# benchmark's shapes with tools/moe_permute_sweep.py (PERF.md, Findings PR 30)
_TILE_ROWS = 128
_CHUNK_ROWS = 256


def _on_tpu():
    try:
        return jax.default_backend() == "tpu"
    except Exception:  # pragma: no cover
        return False


def applies(tokens, rows, d, dtype, tile=_TILE_ROWS, chunk=_CHUNK_ROWS):
    """Whether the kernel can move these rows: on the TPU, bf16 rows (a 0/1
    product of float32 data would round it to bf16), whole lanes, whole
    tiles and chunks. Everything else keeps XLA's gathers."""
    return (_on_tpu() and dtype == jnp.bfloat16 and d % 128 == 0
            and rows % tile == 0 and tokens % chunk == 0)


def _plan(pair_of_row, counts, k, tokens, tile, chunk):
    """What both directions need of a routing: (token of each buffer row
    [1, R] int32, -1 on dead rows; live rows [1] int32; which tiles share a
    row with which chunks [tiles, chunks] bool). From ``moe_dispatch``'s
    ``PairOfRow`` (pair ``t * k + j`` of each row) and ``Counts``."""
    rows = pair_of_row.shape[0]
    live = jnp.sum(counts, dtype=jnp.int32)
    token = jnp.where(jnp.arange(rows, dtype=jnp.int32) < live,
                      pair_of_row // k, -1)
    # (a dead row's -1 floors to chunk -1, which is no chunk)
    share = jnp.any(
        (token // chunk).reshape(rows // tile, 1, tile)
        == jnp.arange(tokens // chunk, dtype=jnp.int32)[None, :, None],
        axis=2)
    return token[None, :], live[None], share


def visit_bound(tiles, chunks, experts):
    """The list's static length (the module's docstring derives it)."""
    return experts * chunks + tiles


def _where_true(mask, size):
    """(row [size], column [size]) of a 2-D mask's True entries in
    row-major order, zeros past the last. With compares, sums and two small
    products only: a TPU does ``nonzero``'s scatter, and a gather of single
    elements, one element at a time (a list made that way took 0.25-0.65 ms
    of a movement's 1.0-1.5: PERF.md, Findings PR 30). Counts stay under
    2**24, exact in float32."""
    rows, cols = mask.shape
    ones = mask.astype(jnp.float32)
    in_row = jnp.sum(ones, axis=1)
    ends = jnp.cumsum(in_row)
    i = jnp.arange(size, dtype=jnp.float32)
    row = jnp.minimum(jnp.sum(ends[None, :] <= i[:, None], axis=1,
                              dtype=jnp.int32), rows - 1)
    of_row = (row[:, None] == jnp.arange(rows, dtype=jnp.int32)[None, :]
              ).astype(jnp.float32)
    # each True entry's rank in its row, from 1, and the visit's own
    rank_in_row = jnp.where(mask, jnp.cumsum(ones, axis=1), 0)
    ranks = jnp.dot(of_row, rank_in_row, precision=lax.Precision.HIGHEST)
    rank = i - jnp.dot(of_row, ends - in_row,
                       precision=lax.Precision.HIGHEST) + 1
    col = jnp.sum(jnp.where(ranks == rank[:, None],
                            jnp.arange(cols, dtype=jnp.int32)[None, :], 0),
                  axis=1)
    return row, col


def _visits(share, experts, by_chunk):
    tiles, chunks = share.shape
    bound = visit_bound(tiles, chunks, experts)
    if by_chunk:
        share = share.at[0].set(share[0] | ~jnp.any(share, axis=0))
        chunk_of, tile_of = _where_true(share.T, bound + chunks)
    else:
        tile_of, chunk_of = _where_true(share, bound)
    return jnp.sum(share, dtype=jnp.int32)[None], tile_of, chunk_of


def visits(pair_of_row, counts, k, tokens, by_chunk, tile=_TILE_ROWS,
           chunk=_CHUNK_ROWS):
    """(visits used [1], tile of each visit, chunk of each visit), in tile
    order or, ``by_chunk``, in chunk order with every chunk visited."""
    share = _plan(pair_of_row, counts, k, tokens, tile, chunk)[2]
    return _visits(share, counts.shape[0], by_chunk)


def _kernel(by_chunk, tile, chunk, used_ref, tile_ref, chunk_ref, live_ref,
            token_ref, src_ref, out_ref, *scratch):
    """One visit: ``acc += P^T . src`` (expand) or ``P . src`` (reduce). The
    accumulator is the float32 output block itself, else a scratch that is
    cast into it at the block's last visit."""
    acc_ref = scratch[0] if scratch else out_ref
    i, used = pl.program_id(0), used_ref[0]
    block_ref = chunk_ref if by_chunk else tile_ref
    here = block_ref[i]

    @pl.when((i == 0) | (block_ref[jnp.maximum(i - 1, 0)] != here))
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    src = src_ref[...]
    if by_chunk:
        row = tile_ref[i] * tile + lax.broadcasted_iota(jnp.int32, src.shape,
                                                        0)
        src = jnp.where(row < live_ref[0], src, 0)
    token = chunk_ref[i] * chunk + lax.broadcasted_iota(
        jnp.int32, (chunk, tile), 0)
    onehot = (token_ref[...] == token).astype(src.dtype)
    acc_ref[...] += lax.dot_general(
        onehot, src, (((1 if by_chunk else 0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    if scratch:
        @pl.when((i == used - 1)
                 | (block_ref[jnp.minimum(i + 1, used - 1)] != here))
        def _store():
            out_ref[...] = acc_ref[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6, 7, 8, 9))
def _move(src, pair_of_row, counts, k, tokens, out_dtype, by_chunk, tile,
          chunk, interpret):
    """One movement. Jitted for its cache: a step's sixteen call sites are
    three distinct movements, traced and lowered once each."""
    token, live, share = _plan(pair_of_row, counts, k, tokens, tile, chunk)
    used, tile_of, chunk_of = _visits(share, counts.shape[0], by_chunk)
    d = src.shape[1]

    def tile_index(i, used, tile_of, chunk_of, live):
        return tile_of[i], 0

    def chunk_index(i, used, tile_of, chunk_of, live):
        return chunk_of[i], 0

    src_spec, out_spec = (pl.BlockSpec((tile, d), tile_index),
                          pl.BlockSpec((chunk, d), chunk_index))
    if not by_chunk:
        src_spec, out_spec = out_spec, src_spec
    out_rows = tokens if by_chunk else pair_of_row.shape[0]
    return pl.pallas_call(
        functools.partial(_kernel, by_chunk, tile, chunk),
        out_shape=jax.ShapeDtypeStruct((out_rows, d), out_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            in_specs=[
                pl.BlockSpec((1, tile), lambda i, used, tile_of, chunk_of,
                             live: (0, tile_of[i])),
                src_spec],
            out_specs=out_spec,
            grid=(used[0],),
            scratch_shapes=(
                [] if out_dtype == jnp.float32 else
                [pltpu.VMEM(out_spec.block_shape, jnp.float32)])),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(used, tile_of, chunk_of, live, token, src)


def expand(x, pair_of_row, counts, k, tile=_TILE_ROWS, chunk=_CHUNK_ROWS,
           interpret=False):
    """``x`` [N, d] bf16 and ``moe_dispatch``'s ``PairOfRow`` [R] and
    ``Counts`` -> rows [R, d]: ``rows[r] = x[pair_of_row[r] // k]`` on live
    rows."""
    return _move(x, pair_of_row, counts, k, x.shape[0], jnp.dtype(x.dtype),
                 False, tile, chunk, interpret)


def reduce(rows, pair_of_row, counts, k, out_dtype, tile=_TILE_ROWS,
           chunk=_CHUNK_ROWS, interpret=False):
    """``rows`` [R, d] bf16 -> out [R // k, d] in ``out_dtype``: each token's
    float32 sum over the live rows of its pairs."""
    return _move(rows, pair_of_row, counts, k, rows.shape[0] // k,
                 jnp.dtype(out_dtype), True, tile, chunk, interpret)
