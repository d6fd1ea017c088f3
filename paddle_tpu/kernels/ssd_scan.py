"""The Mamba-2 chunked selective scan (``ops/ssm_ops.py`` ``ssd_scan``) and
its backward as two Pallas kernels, for x [B, T, H, P], Δ [B, T, H]
float32, B and C [B, T, G, N], R = H / G heads a group, chunks of Q.

**Grid.** Both kernels walk (row, group, chunk), the chunk axis sequential
(``"arbitrary"``): the forward from a row's first chunk to its last, the
backward from its last to its first. One visit holds one group's R heads
of one chunk:

- x's ``[Q, R·P]`` tile (a group's heads are contiguous in x's
  ``[B, T, H·P]`` view), in the backward y's cotangent's too;
- the group's ``[Q, N]`` tiles of B and C;
- Δ's ``[R, Q]`` tile, from a ``[B, G, R, T]`` layout that puts the
  positions on the lanes.

**VMEM residents.** Inside a visit, and never in HBM, are made: s, the
running sum of Δ A over the chunk (a product with a triangle of ones at
float32 precision), C·Bᵀ once for the group, and, for each head, the
masked decays exp(s_i − s_j), the product M = (C·Bᵀ) ∘ decay and all that
reads them. A visit walks the group's 128-wide tiles of heads (128 / P
heads each) in a ``fori_loop``, unrolled. The forward keeps a tile as x
has it, ``[Q, 128]``: a head's Δ and s are its column spread over its
lanes, and what is made for one head is kept on its lanes by a select.
The backward works a tile transposed, ``[128, Q]``: a head's Δ and s are
its row spread over its sublanes, and a head's sums are sums over its
sublanes. The state crosses from chunk to chunk in a float32 ``[N, R·P]``
VMEM scratch (transposed, so that a head's decay scales lanes), reset at
a row's first chunk; the backward carries the adjoint of the state a
chunk ends, ``[R·P, N]``, as ``ssm_ops._carry(..., reverse=True)`` does.

- ``_forward``: y = (M u) + exp(s) C·S_prev + D x with u = Δ x, and the
  state S = exp(s_Q) S_prev + Σ_j exp(s_Q − s_j) u_j ⊗ B_j. y is written
  once, in x's dtype; the float32 state entering each chunk
  (``[B, L, H·P, N]``) is the backward's residual.
- ``_backward``: dx, dΔ, dB and dC inside the visit. A visit holds a
  whole group, so the group's dB and dC are complete when written. The
  sums for A_log and D are partials a (row, group), reduced over [H]
  outside.

**Rounding points** are ``ssm_ops._ssd_fwd`` / ``_ssd_bwd``'s: Δ, A, s,
the decays, the states, their adjoints and every accumulation are
float32; the MXU operands are rounded to the activation dtype exactly
where the plain form rounds them: M, u, exp(s_Q − s_j) u, the states
before C·S, exp(s) gy, the adjoint states, and the summed dM ∘ decay. No
``exp`` takes a positive argument: s_i − s_j is masked before the ``exp``.

The plain form is the oracle (``tests/test_ssd_kernel.py``) and runs
where these do not (``by_kernel``). Both calls are jitted, as
``grouped_matmul._gate`` is: the forward op's call and its replay inside
the grad op (``jax.vjp`` of the lowering) then lower to one function, XLA
merges the two custom calls, and the three mixers of a step trace each
kernel once a shape.
"""
import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
_LANES = 128
_HIGHEST = lax.Precision.HIGHEST
_NN = (((1,), (0,)), ((), ()))      # a [m, k] · b [k, n] -> [m, n]
_NT = (((1,), (1,)), ((), ()))      # a [m, k] · b [n, k] -> [m, n]
_TN = (((0,), (0,)), ((), ()))      # a [k, m] · b [k, n] -> [m, n]


def _on_tpu():
    try:
        return jax.default_backend() == "tpu"
    except Exception:  # pragma: no cover
        return False


def tiles(heads, head_dim, groups, state, chunk):
    """Whether the kernels tile a scan of ``heads`` heads of ``head_dim``
    in ``groups`` groups, states of ``state`` and chunks of ``chunk``:
    whole lanes for a chunk's positions (Δ's tile puts them on the
    lanes), for the state and for a group's heads, and whole heads, of
    whole sublane tiles, in a lane tile."""
    return (heads % groups == 0 and chunk % _LANES == 0
            and state % _LANES == 0 and _LANES % head_dim == 0
            and head_dim % 8 == 0 and heads // groups * head_dim % _LANES == 0)


def by_kernel(x, b, chunk):
    """Whether an ``ssd_scan`` call site on x [B, T, H, P] and B [B, T, G,
    N] takes the kernels: the TPU, bf16 activations and sizes that
    ``tiles``. Everything else keeps the plain form: the CPU, float32
    programs, odd sizes. Decided by what the call site sees, no flag."""
    return (_on_tpu() and x.dtype == jnp.bfloat16 and b.dtype == x.dtype
            and tiles(x.shape[2], x.shape[3], b.shape[2], b.shape[3],
                      chunk))


def _dot(a, b, dims=_NN, precision=None, wide=False):
    if wide:
        # interpreted on the CPU, whose runtime multiplies bf16 operands in
        # some layouts only: in float32 the products are the same, exactly
        a, b = a.astype(F32), b.astype(F32)
    return lax.dot_general(a, b, dims, precision=precision,
                           preferred_element_type=F32)


def _iota(shape, axis):
    return lax.broadcasted_iota(jnp.int32, shape, axis)


class _Chunk:
    """What both kernels read of a visit's chunk: Δ and s [R, Q] (s also
    in the ``rows`` scratch, for one head's row at a traced index), s as
    columns [Q, R], and the causal masks."""

    def __init__(self, a_ref, dt_ref, rows, head_dim):
        per, q = dt_ref.shape
        self.per, self.q, self.p = per, q, head_dim
        self.count = _LANES // head_dim
        self.dt = dt_ref[...]
        upper = (_iota((q, q), 0) <= _iota((q, q), 1)).astype(F32)
        self.s = _dot(self.dt * a_ref[...], upper, precision=_HIGHEST)
        rows[...] = self.s
        self.rows = rows
        self.s_cols = self.s.T
        self.below = _iota((q, q), 0) >= _iota((q, q), 1)

    def column(self, cols, head):
        """[Q, 1]: head ``head``'s column of ``cols`` [Q, R]."""
        return jnp.sum(jnp.where(_iota((1, self.per), 1) == head, cols, 0.0),
                       axis=1, keepdims=True)

    def decay(self, head, col=None):
        """exp(s_i − s_j) for j <= i, 0 above, [Q, Q], for head ``head``
        of the group (``col``: its s as a column, where already made):
        masked before the ``exp``."""
        if col is None:
            col = self.column(self.s_cols, head)
        row = self.rows[pl.ds(head, 1), :]
        return jnp.exp(jnp.where(self.below, col - row, -jnp.inf))

    def heads(self, t, axis):
        """(j, head, mask) for the heads of tile ``t``: the mask [1, 128]
        (``axis`` 1) or [128, 1] (``axis`` 0) of the head's lanes or
        sublanes."""
        shape = (1, _LANES) if axis else (_LANES, 1)
        of = lax.shift_right_logical(_iota(shape, axis),
                                     self.p.bit_length() - 1)
        return [(j, t * self.count + j, of == j) for j in range(self.count)]


def _lanes(t):
    return pl.ds(pl.multiple_of(t * _LANES, _LANES), _LANES)


def _fwd_kernel(a_ref, d_ref, x_ref, dt_ref, b_ref, c_ref, y_ref, st_ref,
                state, rows, *, head_dim, wide):
    cd = x_ref.dtype
    dot = functools.partial(_dot, wide=wide)

    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    b, c = b_ref[...], c_ref[...]
    v = _Chunk(a_ref, dt_ref, rows, head_dim)
    q = v.q
    cb, dt_cols, b_t = dot(c, b, _NT), v.dt.T, b.T

    def tile(t, carry):
        at = _lanes(t)
        heads = v.heads(t, 1)
        dt = s = None
        s_cols = []
        for j, head, mine in heads:     # each head's column on its lanes
            dt_h = v.column(dt_cols, head)
            s_cols.append(v.column(v.s_cols, head))
            dt = (jnp.broadcast_to(dt_h, (q, _LANES)) if dt is None
                  else jnp.where(mine, dt_h, dt))
            s = (jnp.broadcast_to(s_cols[-1], (q, _LANES)) if s is None
                 else jnp.where(mine, s_cols[-1], s))
        x32 = x_ref[:, at].astype(F32)
        u = dt * x32
        s_end = s[q - 1:q, :]
        prev = state[:, at]
        st_ref[at, :] = prev.T
        y = jnp.exp(s) * dot(c, prev.astype(cd))
        u_c = u.astype(cd)
        for j, head, mine in heads:
            m = (cb * v.decay(head, s_cols[j])).astype(cd)
            y = y + jnp.where(mine, dot(m, u_c), 0.0)
        y = y + d_ref[:, at] * x32
        y_ref[:, at] = y.astype(y_ref.dtype)
        w_end = jnp.exp(s_end - s)
        state[:, at] = (jnp.exp(s_end) * prev
                        + dot(b_t, (w_end * u).astype(cd)))
        return carry

    lax.fori_loop(0, x_ref.shape[1] // _LANES, tile, 0, unroll=True)


def _bwd_kernel(a_ref, d_ref, x_ref, dt_ref, b_ref, c_ref, st_ref, gy_ref,
                dx_ref, ddt_ref, db_ref, dc_ref, dalog_ref, dd_ref, ends,
                rows, dt_rows, db_acc, dc_acc, dcb_acc, ds_cols, ds_rows,
                ddt_rows, *, head_dim, wide):
    """One chunk, visited from the row's last to its first: ``ends``
    holds the adjoint of the state this chunk ends in (0 for the last),
    and leaves the adjoint of the state it starts from for the next
    visit. A tile is worked transposed: [128 (head, p), Q]."""
    cd = x_ref.dtype
    dot = functools.partial(_dot, wide=wide)

    @pl.when(pl.program_id(2) == 0)
    def _():
        ends[...] = jnp.zeros_like(ends)
        dalog_ref[...] = jnp.zeros_like(dalog_ref)
        dd_ref[...] = jnp.zeros_like(dd_ref)

    for acc in (db_acc, dc_acc, dcb_acc, ds_cols, ds_rows, ddt_rows):
        acc[...] = jnp.zeros_like(acc)
    b, c = b_ref[...], c_ref[...]
    v = _Chunk(a_ref, dt_ref, rows, head_dim)
    dt_rows[...] = v.dt
    q, per, p = v.q, v.per, v.p
    cb = dot(c, b, _NT)
    last = _iota((1, q), 1) == q - 1
    head_col = _iota((1, per), 1)

    def spread(rows_ref, heads):
        """[128, Q]: each sublane its head's row of ``rows_ref``."""
        out = None
        for j, head, mine in heads:
            row = rows_ref[pl.ds(head, 1), :]
            out = (jnp.broadcast_to(row, (_LANES, q)) if out is None
                   else jnp.where(mine, row, out))
        return out

    def per_head(vals, heads):
        """Σ over each head's sublanes of [128, Q] -> [(head, [1, Q])]."""
        return [(head, jnp.sum(vals[j * p:(j + 1) * p], axis=0,
                               keepdims=True)) for j, head, _ in heads]

    def tile(t, carry):
        at = _lanes(t)
        heads = v.heads(t, 0)
        dt, s = spread(dt_rows, heads), spread(rows, heads)
        x32 = x_ref[:, at].astype(F32)
        gy32 = gy_ref[:, at].astype(F32)
        dd_ref[:, at] += jnp.sum(gy32 * x32, axis=0, keepdims=True)
        x32, gy32 = x32.T, gy32.T
        gy = gy32.astype(cd)
        u = dt * x32
        u_c = u.astype(cd)
        s_end = s[:, q - 1:q]
        exp_s, w_end, decay_end = (jnp.exp(s), jnp.exp(s_end - s),
                                   jnp.exp(s_end))
        egy = (exp_s * gy32).astype(cd)
        end, prev = ends[at, :], st_ref[at, :]
        end_c, prev_c = end.astype(cd), prev.astype(cd)
        # S_next = exp(s_Q) S_prev + Σ_j exp(s_Q − s_j) u_j ⊗ B_j
        gb = dot(end_c, b, _NT)
        du = w_end * gb
        db_acc[...] += dot((w_end * u).astype(cd), end_c, _TN)
        by_end = gb * u * w_end
        # across chunks: exp(s_i) C_i · S_prev
        across = exp_s * gy32 * dot(prev_c, c, _NT) - by_end
        at_end = decay_end * jnp.sum(end * prev, axis=1, keepdims=True)
        for (head, ds_h), (_, end_h), (_, st_h) in zip(
                per_head(across, heads), per_head(by_end, heads),
                per_head(jnp.broadcast_to(at_end, (_LANES, q)), heads)):
            ds_rows[pl.ds(head, 1), :] += ds_h + jnp.where(
                last, jnp.sum(end_h, axis=1, keepdims=True) + st_h, 0.0)
        dc_acc[...] += dot(egy, prev_c, _TN)
        ends[at, :] = dot(egy, c) + decay_end * end
        # within chunks: M u
        for j, head, mine in heads:
            decay = v.decay(head)
            m = cb * decay
            dm = dot(jnp.where(mine, gy, 0), u_c, _TN)
            du = du + jnp.where(mine, dot(gy, m.astype(cd)), 0.0)
            dcb_acc[...] += dm * decay
            prod = dm * m
            ds_cols[...] += jnp.where(head_col == head, jnp.sum(
                prod, axis=1, keepdims=True), 0.0)
            ds_rows[pl.ds(head, 1), :] -= jnp.sum(prod, axis=0,
                                                  keepdims=True)
        dx_ref[:, at] = (dt * du + d_ref[at, :] * gy32).T.astype(
            dx_ref.dtype)
        for head, row in per_head(du * x32, heads):
            ddt_rows[pl.ds(head, 1), :] += row
        return carry

    lax.fori_loop(0, x_ref.shape[1] // _LANES, tile, 0, unroll=True)
    dcb = dcb_acc[...].astype(cd)
    db_ref[...] = (db_acc[...] + dot(dcb, c, _TN)).astype(db_ref.dtype)
    dc_ref[...] = (dc_acc[...] + dot(dcb, b)).astype(dc_ref.dtype)
    # s is the running sum of Δ A: its transpose is the sum from the end
    lower = (_iota((q, q), 0) >= _iota((q, q), 1)).astype(F32)
    da = dot(ds_cols[...].T + ds_rows[...], lower, precision=_HIGHEST)
    ddt_ref[...] = ddt_rows[...] + da * a_ref[...]
    dalog_ref[...] += da * v.dt


def _layouts(x, dt, b, c, chunk):
    """x [B, T', H·P], Δ [B, G, R, T'], B and C [B, T', G·N] with T' the
    sequence padded to whole chunks (zeros: Δ = 0 carries the state
    through unchanged)."""
    rows, t, heads, p = x.shape
    groups, n = b.shape[2], b.shape[3]
    pad = [(0, 0), (0, (-t) % chunk)]
    x3 = jnp.pad(x.reshape(rows, t, heads * p), pad + [(0, 0)])
    b3, c3 = (jnp.pad(v.reshape(rows, t, groups * n), pad + [(0, 0)])
              for v in (b, c))
    dt4 = jnp.pad(dt.astype(F32), pad + [(0, 0)]).reshape(
        rows, -1, groups, heads // groups).transpose(0, 2, 3, 1)
    return x3, dt4, b3, c3


def _per_head(a_log, d, groups, p):
    """A [H] and [G, R, 1], and D on every lane (sublane) of its head
    [1, H·P] ([H·P, 1]), float32."""
    heads = a_log.shape[0]
    a = -jnp.exp(a_log.astype(F32))
    d = jnp.repeat(d.astype(F32), p)
    return a, a.reshape(groups, heads // groups, 1), d[None, :], d[:, None]


def _specs(groups, heads, p, n, chunk, chunks, reverse):
    per = heads // groups

    def at(l):
        return chunks - 1 - l if reverse else l

    return dict(
        a=pl.BlockSpec((None, per, 1), lambda i, g, l: (g, 0, 0)),
        d=pl.BlockSpec((1, per * p), lambda i, g, l: (0, g)),
        d_col=pl.BlockSpec((per * p, 1), lambda i, g, l: (g, 0)),
        x=pl.BlockSpec((None, chunk, per * p),
                       lambda i, g, l: (i, at(l), g)),
        dt=pl.BlockSpec((None, None, per, chunk),
                        lambda i, g, l: (i, g, 0, at(l))),
        bc=pl.BlockSpec((None, chunk, n), lambda i, g, l: (i, at(l), g)),
        states=pl.BlockSpec((None, None, per * p, n),
                            lambda i, g, l: (i, at(l), g, 0)),
        per_lane=pl.BlockSpec((None, None, per, chunk),
                              lambda i, g, l: (i, g, 0, 0)),
        per_column=pl.BlockSpec((None, 1, per * p),
                                lambda i, g, l: (i, 0, g)))


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))


def _cost(rows, t, heads, p, groups, n, chunk, itemsize, backward):
    """Operations and bytes of one call for XLA's scheduler: the products
    of the chunked form on whole lane tiles (the backward's about three
    times as many) and every argument and result once."""
    tokens = rows * t
    flops = tokens * (2 * chunk * groups * n + 2 * chunk * heads * _LANES
                      + 4 * heads * p * n)
    acts = tokens * (heads * p + 2 * groups * n) * itemsize + tokens * heads * 4
    states = rows * (t // chunk) * heads * p * n * 4
    ys = tokens * heads * p * itemsize
    if backward:
        return pl.CostEstimate(flops=3 * flops,
                               transcendentals=tokens * heads * chunk,
                               bytes_accessed=2 * acts + ys + states)
    return pl.CostEstimate(flops=flops, transcendentals=tokens * heads * chunk,
                           bytes_accessed=acts + ys + states)


@functools.partial(jax.jit, static_argnums=(6, 7))
def _forward(x, dt, a_log, b, c, d, chunk, interpret):
    """(y [B, T, H, P] in x's dtype, the float32 states entering each
    chunk [B, L, H·P, N])."""
    rows, t, heads, p = x.shape
    groups, n = b.shape[2], b.shape[3]
    x3, dt4, b3, c3 = _layouts(x, dt, b, c, chunk)
    _, a3, d_lanes, _ = _per_head(a_log, d, groups, p)
    chunks = x3.shape[1] // chunk
    spec = _specs(groups, heads, p, n, chunk, chunks, False)
    per = heads // groups
    y, states = pl.pallas_call(
        functools.partial(_fwd_kernel, head_dim=p, wide=interpret),
        out_shape=(jax.ShapeDtypeStruct(x3.shape, x.dtype),
                   jax.ShapeDtypeStruct((rows, chunks, heads * p, n), F32)),
        grid=(rows, groups, chunks),
        in_specs=[spec["a"], spec["d"], spec["x"], spec["dt"], spec["bc"],
                  spec["bc"]],
        out_specs=(spec["x"], spec["states"]),
        scratch_shapes=[pltpu.VMEM((n, per * p), F32),
                        pltpu.VMEM((per, chunk), F32)],
        compiler_params=_params(),
        cost_estimate=_cost(rows, chunks * chunk, heads, p, groups, n, chunk,
                            x.dtype.itemsize, False),
        interpret=interpret, name="ssd_scan_fwd",
    )(a3, d_lanes, x3, dt4, b3, c3)
    return y[:, :t].reshape(x.shape), states


@functools.partial(jax.jit, static_argnums=(8, 9))
def _backward(x, dt, a_log, b, c, d, states, gy, chunk, interpret):
    """The six gradients (x, Δ, A_log, B, C, D) from the inputs, the
    forward's states and y's cotangent."""
    rows, t, heads, p = x.shape
    groups, n = b.shape[2], b.shape[3]
    per = heads // groups
    x3, dt4, b3, c3 = _layouts(x, dt, b, c, chunk)
    gy3 = _layouts(gy.astype(x.dtype), dt, b, c, chunk)[0]
    a, a3, _, d_col = _per_head(a_log, d, groups, p)
    chunks = x3.shape[1] // chunk
    spec = _specs(groups, heads, p, n, chunk, chunks, True)
    dx, ddt, db, dc, dalog, dd = pl.pallas_call(
        functools.partial(_bwd_kernel, head_dim=p, wide=interpret),
        out_shape=(jax.ShapeDtypeStruct(x3.shape, x.dtype),
                   jax.ShapeDtypeStruct(dt4.shape, F32),
                   jax.ShapeDtypeStruct(b3.shape, b.dtype),
                   jax.ShapeDtypeStruct(c3.shape, c.dtype),
                   jax.ShapeDtypeStruct((rows, groups, per, chunk), F32),
                   jax.ShapeDtypeStruct((rows, 1, heads * p), F32)),
        grid=(rows, groups, chunks),
        in_specs=[spec["a"], spec["d_col"], spec["x"], spec["dt"],
                  spec["bc"], spec["bc"], spec["states"], spec["x"]],
        out_specs=(spec["x"], spec["dt"], spec["bc"], spec["bc"],
                   spec["per_lane"], spec["per_column"]),
        scratch_shapes=[pltpu.VMEM((per * p, n), F32),     # ends
                        pltpu.VMEM((per, chunk), F32),     # s, by rows
                        pltpu.VMEM((per, chunk), F32),     # Δ, by rows
                        pltpu.VMEM((chunk, n), F32),       # dB
                        pltpu.VMEM((chunk, n), F32),       # dC
                        pltpu.VMEM((chunk, chunk), F32),   # Σ dM ∘ decay
                        pltpu.VMEM((chunk, per), F32),     # ds, by columns
                        pltpu.VMEM((per, chunk), F32),     # ds, by rows
                        pltpu.VMEM((per, chunk), F32)],    # dΔ from x
        compiler_params=_params(),
        cost_estimate=_cost(rows, chunks * chunk, heads, p, groups, n, chunk,
                            x.dtype.itemsize, True),
        interpret=interpret, name="ssd_scan_bwd",
    )(a3, d_col, x3, dt4, b3, c3, states, gy3)
    da_log = jnp.sum(dalog, axis=(0, 3)).reshape(heads) * a
    dd = jnp.sum(dd.reshape(rows, heads, p), axis=(0, 2))
    ddt = ddt.transpose(0, 3, 1, 2).reshape(rows, -1, heads)[:, :t]
    return (dx[:, :t].reshape(x.shape), ddt.astype(dt.dtype),
            da_log.astype(a_log.dtype),
            db[:, :t].reshape(b.shape), dc[:, :t].reshape(c.shape),
            dd.astype(d.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _scan(x, dt, a_log, b, c, d, chunk, interpret):
    # the same call as the residuals' below, so that XLA merges the two
    return _forward(x, dt, a_log, b, c, d, chunk, interpret)[0]


def _scan_fwd(x, dt, a_log, b, c, d, chunk, interpret):
    y, states = _forward(x, dt, a_log, b, c, d, chunk, interpret)
    return y, (x, dt, a_log, b, c, d, states)


def _scan_bwd(chunk, interpret, res, gy):
    from paddle_tpu import observability as obs

    obs.inc("ssm.scan_kernel")
    return _backward(*res, gy, chunk, interpret)


_scan.defvjp(_scan_fwd, _scan_bwd)


def scan(x, dt, a_log, b, c, d, chunk, interpret=False):
    """``ssm_ops._ssd_scan`` by the kernels, for a call site where
    ``by_kernel``: x [B, T, H, P], Δ [B, T, H] float32, A_log and D [H],
    B and C [B, T, G, N] in x's dtype -> y [B, T, H, P] in x's dtype, with
    the backward its kernel's. ``interpret`` runs them in Pallas's
    interpret mode off the TPU (tests), in x's dtype whatever it is."""
    assert tiles(x.shape[2], x.shape[3], b.shape[2], b.shape[3], chunk), (
        x.shape, b.shape, chunk)
    return _scan(x, dt, a_log, b, c, d, chunk, interpret)
