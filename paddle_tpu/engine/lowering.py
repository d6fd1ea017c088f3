"""Block -> pure JAX function lowering.

This is the TPU-native replacement for the reference's per-op interpreter hot
loop (reference: paddle/fluid/framework/executor.cc:397-456) and its per-op
CUDA kernels: the whole block between feed and fetch is traced once into a
single jittable function, XLA fuses and schedules it, and the executable is
cached by (program, shapes) key — following the seam the reference itself
proves with its nGraph engine (reference:
paddle/fluid/operators/ngraph/ngraph_engine.cc:109-160), generalized so the
*whole block* is the captured interval.

Gradient ops (``*_grad``) produced by ``append_backward`` are lowered
generically via ``jax.vjp`` of the forward op's lowering — per-op handwritten
grad kernels (the bulk of the reference's operators/ directory) are replaced
by autodiff of the lowering itself.
"""

import contextlib

import numpy as np

import jax
import jax.numpy as jnp

from paddle_tpu import observability as obs
from paddle_tpu.core.registry import OpRegistry, LowerContext
from paddle_tpu.core.types import convert_dtype_to_np
from paddle_tpu.observability import opprof as _opprof

# Ops that are pure host-side markers and skipped during tracing.
_SKIP_OPS = frozenset({"feed", "fetch"})

# Attrs that are engine-internal plumbing, stripped before calling lowerings.
_INTERNAL_ATTR_PREFIX = "__"


def clean_attrs(attrs):
    return {k: v for k, v in attrs.items() if not k.startswith(_INTERNAL_ATTR_PREFIX)}


class BlockProgram:
    """Analyzed form of one block: which vars are inputs (feeds + state read),
    which are outputs (fetches + state written)."""

    def __init__(self, block, feed_names, fetch_names, scope_var_names,
                 extra_state_outputs=(), extra_live_vars=()):
        self.block = block
        self.feed_names = list(feed_names)
        self.fetch_names = list(fetch_names)

        all_ops = [op for op in block.ops if op.type not in _SKIP_OPS]

        # Dead-code elimination over the block's dataflow (the XLA-native
        # analog of the reference's program pruning, framework/prune.cc /
        # io.py:862): an op is live iff it feeds a fetch target, writes a
        # persistable var (param/optimizer-state/BN-stat side effect), or has
        # no outputs at all (pure side effect). Fetching `pred` from a
        # for_test clone therefore no longer demands `label` nor computes the
        # loss subgraph.
        def _is_persistable(name):
            vd = block.find_var_recursive(name)
            return vd is not None and vd.persistable

        # extra_live_vars: liveness-only roots (no output slot) — the
        # remat lowering keeps the loss-computing ops alive with these
        # even when nothing in the explicit grad chain reads the loss
        live_vars = (set(self.fetch_names) | set(extra_state_outputs)
                     | set(extra_live_vars))
        live_flags = [False] * len(all_ops)
        for i in range(len(all_ops) - 1, -1, -1):
            op = all_ops[i]
            outs = [n for n in op.output_arg_names() if n != EMPTY_VAR_NAME]
            live = (
                not outs
                or any(n in live_vars for n in outs)
                or any(_is_persistable(n) for n in outs)
            )
            if live:
                live_flags[i] = True
                for n in op.input_arg_names():
                    if n != EMPTY_VAR_NAME:
                        live_vars.add(n)
        self.ops = [op for i, op in enumerate(all_ops) if live_flags[i]]

        feed_set = set(self.feed_names)
        written = set()
        state_in = []  # vars read before written, provided by scope
        state_in_set = set()
        for op in self.ops:
            for name in op.input_arg_names():
                if (
                    name != EMPTY_VAR_NAME
                    and name not in written
                    and name not in feed_set
                    and name not in state_in_set
                ):
                    state_in.append(name)
                    state_in_set.add(name)
            for name in op.output_arg_names():
                written.add(name)

        # A fetch of a var no live op writes (e.g. fetching a parameter
        # directly to inspect it) is served from the scope like other state.
        for name in self.fetch_names:
            if (
                name not in written
                and name not in feed_set
                and name not in state_in_set
            ):
                state_in.append(name)
                state_in_set.add(name)

        # Outputs: every persistable var written + anything fetched + explicit
        # extras (e.g. params the caller wants synced even if only aliased).
        state_out = []
        seen = set()
        for op in self.ops:
            for name in op.output_arg_names():
                if name in seen:
                    continue
                vd = block.find_var_recursive(name)
                if vd is not None and vd.persistable:
                    state_out.append(name)
                    seen.add(name)
        for name in extra_state_outputs:
            if name not in seen:
                state_out.append(name)
                seen.add(name)

        self.state_in_names = state_in
        self.state_out_names = state_out

        # Missing state vars must be provided by the scope at run time; the
        # executor validates and errors like the reference's
        # "holder should not be null" enforce.
        self.needs_rng = any(
            OpRegistry.has(_base_type(op.type)) and _op_needs_rng(op)
            for op in self.ops
        )


def _base_type(op_type):
    return op_type[: -len("_grad")] if op_type.endswith("_grad") else op_type


def _op_needs_rng(op):
    base = _base_type(op.type)
    if not OpRegistry.has(base):
        return False
    return OpRegistry.get(base).needs_rng


def lower_block(block_program, is_test=False, executor=None, amp=False,
                grad_shardings=None, grad_bucket_bytes=0, prov=None):
    """Returns fn(feeds: list, state_in: list, rng_key) ->
    (fetches: list, state_out: list).

    ``grad_shardings`` ({grad name: NamedSharding}, ZeRO-1 path only)
    pins each parameter gradient to its dp shard right where the
    backward chain binds it, turning the partitioner's all-reduce into
    a reduce-scatter to the update's owning rank. With
    ``grad_bucket_bytes`` > 0 the constrained grads are additionally
    grouped, in backward production order, into buckets of roughly
    that many bytes, each full bucket fenced with
    ``jax.lax.optimization_barrier`` — XLA may then launch an earlier
    bucket's reduction while later backward ops still compute, instead
    of one end-of-step reduction wave. Neither mechanism changes a
    single collective count or payload; only scheduling freedom moves.
    """
    from paddle_tpu.core.registry import amp_scope
    from paddle_tpu.core.selected_rows import SelectedRows

    block = block_program.block
    feed_names = block_program.feed_names
    state_in_names = block_program.state_in_names
    grad_shardings = grad_shardings or {}
    if obs.enabled():
        # op counts of what actually lowers (post-DCE) vs the raw block —
        # the trace-size numbers the transform pipeline moves
        obs.observe("lower.ops", len(block_program.ops))
        obs.observe("lower.block_ops",
                    len([o for o in block.ops if o.type not in _SKIP_OPS]))

    def fn(feed_values, state_values, rng_key):
        env = {}
        for name, val in zip(feed_names, feed_values):
            env[name] = val
        for name, val in zip(state_in_names, state_values):
            env[name] = val

        pending, pending_bytes = [], [0]

        def _flush_bucket():
            if not pending:
                return
            fenced = jax.lax.optimization_barrier(
                tuple(env[n] for n in pending))
            for n, v in zip(pending, fenced):
                env[n] = v
            del pending[:]
            pending_bytes[0] = 0

        def _constrain_grads(op):
            # ZeRO-1 reduce-scatter constraint point: re-applied at
            # every op that (re)binds a planned grad name, so renames
            # through clip/regularizer tails stay covered
            for name in op.output_arg_names():
                sh = grad_shardings.get(name)
                val = env.get(name)
                if sh is None or val is None \
                        or isinstance(val, SelectedRows):
                    continue
                env[name] = jax.lax.with_sharding_constraint(val, sh)
                if grad_bucket_bytes > 0:
                    pending.append(name)
                    pending_bytes[0] += (
                        int(val.size) * val.dtype.itemsize)
                    if pending_bytes[0] >= grad_bucket_bytes:
                        _flush_bucket()

        with amp_scope(amp):
            for op_index, op in enumerate(block_program.ops):
                run_op(op, block, env, rng_key, op_index, is_test, executor,
                       prov=prov)
                if grad_shardings:
                    _constrain_grads(op)
            _flush_bucket()

        # SelectedRows sparse grads are an intra-block representation;
        # anything crossing the jit boundary (user fetches, persisted
        # state) is densified, like the reference's GetFetchVariable
        # materializing SelectedRows into a tensor.
        from paddle_tpu.core.selected_rows import densify

        fetches = [densify(env[n]) for n in block_program.fetch_names]
        state_out = [densify(env[n]) for n in block_program.state_out_names]
        return fetches, state_out

    return fn


# Positional placeholder for absent gradient inputs: keeps multi-var slots
# aligned with the forward op's outputs (see backward.py) without a real var.
EMPTY_VAR_NAME = "@EMPTY@"


def run_op(op, block, env, rng_key, op_index, is_test, executor=None,
           prov=None):
    """Execute one op desc symbolically into env.

    With ``prov`` (a dict, opprof provenance collection) the lowering
    runs inside ``jax.named_scope(pt.<type>.<block>_<idx>)`` so XLA
    op_metadata carries the framework-op identity through fusion, and
    the tag -> OpDesc binding is recorded for the attribution join.
    named_scope is metadata-only: the emitted computation is
    bit-identical either way (tests/test_opprof.py asserts it).

    While spans are live the lowering is the span ``op:<type>`` (``idx``
    = ``<block>_<index>``, ``role`` by ``opprof.role_phase``, the device
    join's rule). This function runs under JAX's trace, and an
    executable's first call is a cache-miss seam (``compile``), so the
    seconds of set-up's tracing are recorded by Fluid op whatever is
    switched on, a grad op's ``jax.vjp`` replay inside its own span. A
    retrace outside a seam, with nothing switched on, records nothing."""
    ins = {}
    for slot, names in op.inputs.items():
        vals = []
        for n in names:
            if n == EMPTY_VAR_NAME:
                vals.append(None)
            elif n in env:
                vals.append(env[n])
            else:
                raise KeyError(
                    "Op %s input %s[%d] references uninitialized variable "
                    "%r (reference semantics: PADDLE_ENFORCE input var "
                    "holder)" % (op.type, slot, len(vals), n)
                )
        ins[slot] = vals
    block_idx = getattr(block, "idx", 0)
    if prov is not None:
        tag = _opprof.provenance_tag(op.type, block_idx, op_index)
        prov[tag] = op
        scope = jax.named_scope(tag)
    else:
        scope = contextlib.nullcontext()
    # obs.span's own check, made before the arguments are
    span = obs.tracer.span(
        "op:" + op.type, idx="%d_%d" % (block_idx, op_index),
        role=_opprof.role_phase(op.attrs.get("op_role", 0)),
    ) if obs.spans_live() else obs.NULL_BLOCK
    with span, scope:
        if op.type.endswith("_grad") and not OpRegistry.has(op.type):
            outs = _lower_grad_op(op, block, ins, rng_key, is_test)
        else:
            info = OpRegistry.get(op.type)
            ctx = LowerContext(
                op, block, rng_key=rng_key, op_index=_rng_id(op, op_index),
                is_test=is_test, executor=executor,
            )
            outs = info.lower(ctx, ins, clean_attrs(op.attrs))

    _bind_outputs(op, outs, env)


def _rng_id(op, op_index):
    # Stable per-op RNG stream id so a *_grad op re-derives the same mask the
    # forward op used (replaces the reference's saved dropout Mask output).
    return int(op.attrs.get("__rng_id__", op_index))


def _bind_outputs(op, outs, env):
    for slot, names in op.outputs.items():
        vals = outs.get(slot, [])
        for i, name in enumerate(names):
            if i < len(vals) and vals[i] is not None:
                env[name] = vals[i]


def _lower_grad_op(op, block, ins, rng_key, is_test):
    """Generic gradient lowering via jax.vjp of the forward lowering."""
    fwd_type = _base_type(op.type)
    info = OpRegistry.get(fwd_type)
    fwd_input_slots = op.attrs.get("__fwd_inputs__")
    fwd_output_slots = op.attrs.get("__fwd_outputs__")
    if fwd_input_slots is None or fwd_output_slots is None:
        raise RuntimeError(
            "grad op %s missing forward slot metadata" % op.type
        )

    attrs = clean_attrs(op.attrs)
    fwd_ins = {s: ins.get(s, []) for s in fwd_input_slots}
    rng_id = _rng_id(op, 0)

    def forward(fin):
        ctx = LowerContext(op, block, rng_key=rng_key, op_index=rng_id,
                           is_test=is_test)
        out = info.lower(ctx, fin, attrs)
        # Only differentiable (float) outputs participate in the vjp.
        return {
            s: [v for v in out.get(s, [])]
            for s in fwd_output_slots
        }

    primals, vjp_fn = jax.vjp(forward, fwd_ins)

    # Build cotangent pytree matching primals: provided grads where the grad
    # op has them, zeros elsewhere.
    cotangents = {}
    for s in fwd_output_slots:
        slot_primals = primals[s]
        grads = ins.get(s + "@GRAD", [])
        cvals = []
        for i, p in enumerate(slot_primals):
            if not jnp.issubdtype(p.dtype, jnp.inexact):
                # an integer output (indices, counts) has no gradient
                cvals.append(np.zeros(p.shape, jax.dtypes.float0))
            elif i < len(grads) and grads[i] is not None:
                cvals.append(
                    jnp.asarray(grads[i], dtype=p.dtype).reshape(p.shape)
                )
            else:
                cvals.append(jnp.zeros_like(p))
        cotangents[s] = cvals
    (in_grads,) = vjp_fn(cotangents)

    outs = {}
    for s in fwd_input_slots:
        gvals = in_grads.get(s, [])
        cleaned = []
        for g in gvals:
            # int inputs produce float0 tangents -> no gradient
            if g is not None and hasattr(g, "dtype") and g.dtype == jax.dtypes.float0:
                cleaned.append(None)
            else:
                cleaned.append(g)
        outs[s + "@GRAD"] = cleaned
    return outs


def lower_block_remat(block_program, n_segments, is_test=False,
                      executor=None, amp=False, prov=None):
    """Rematerialized training-step lowering: the forward segment runs as
    a chain of ``jax.checkpoint`` blocks and the parameter gradients come
    from ``jax.value_and_grad`` of that chain instead of the program's
    explicit ``*_grad`` ops — so only segment-boundary activations
    survive from forward to backward and everything inside a segment is
    recomputed on demand. This is the TPU-native descendant of the
    reference's memory optimization passes (reference:
    framework/details/memory_optimize_pass.cc and
    transpiler/memory_optimization_transpiler.py, which reuse buffers by
    lifetime analysis): under XLA the buffer reuse itself is automatic,
    so the lever that remains is trading recompute FLOPs for backward
    activation MEMORY — which is what bounds long-context batch sizes
    and conv-net peak batch.

    Numerics: the Backward segment appended by ``append_backward`` is
    pure autodiff (clip/regularizer/optimizer ops all carry the
    Optimize role), and every registered grad lowering is the analytic
    derivative of its forward lowering, so differentiating the composed
    forward produces the same gradients the explicit chain does (the
    parity tests assert it). Sparse (SelectedRows) gradients densify.
    The Optimize-role tail runs unchanged on the bound ``p@GRAD`` vars.

    Not supported (raises ``NotImplementedError``): programs fetching
    gradients of intermediate (non-feed, non-state) vars, and programs
    whose optimizer consumes backward-written vars that are not
    ``<var>@GRAD``.
    """
    import jax

    from paddle_tpu.core.registry import amp_scope
    from paddle_tpu.core.selected_rows import densify
    from paddle_tpu.framework import OpRole

    block = block_program.block
    feed_names = block_program.feed_names
    state_in_names = block_program.state_in_names

    TAIL_ROLES = OpRole.Optimize | OpRole.RPC | OpRole.Dist | OpRole.LRSched
    fwd_ops, bwd_ops, tail_ops = [], [], []
    for i, op in enumerate(block_program.ops):
        role = int(op.attrs.get("op_role", 0))
        if role & OpRole.Backward:
            bwd_ops.append((i, op))
        elif role & TAIL_ROLES:
            tail_ops.append((i, op))
        else:
            fwd_ops.append((i, op))
    if not bwd_ops:
        raise NotImplementedError(
            "remat lowering requires a training program (no Backward-role "
            "ops found); run test/inference programs without remat")

    # the losses: append_backward marks each chain seed
    losses, bwd_real = [], []
    for i, op in bwd_ops:
        if op.attrs.get("__is_loss_grad__"):
            gname = next(n for n in op.output_arg_names()
                         if n != EMPTY_VAR_NAME)
            losses.append((gname[: -len("@GRAD")],
                           float(op.attrs.get("value", 1.0))))
        else:
            bwd_real.append((i, op))
    if not losses:
        raise NotImplementedError(
            "remat lowering found no @GRAD seed op (calc_gradient-style "
            "programs are not supported)")

    bwd_written = set()
    for _, op in bwd_real:
        bwd_written.update(
            n for n in op.output_arg_names() if n != EMPTY_VAR_NAME)
    tail_read = set()
    for _, op in tail_ops:
        tail_read.update(
            n for n in op.input_arg_names() if n != EMPTY_VAR_NAME)
    fetch_set = set(block_program.fetch_names)

    # persistable side effects inside the (skipped) backward segment have
    # no remat equivalent — refuse rather than silently serve stale state
    bwd_persist = sorted(set(block_program.state_out_names) & bwd_written)
    if bwd_persist:
        raise NotImplementedError(
            "remat: backward-role ops write persistable vars %s; the "
            "remat lowering replaces the explicit backward chain and "
            "cannot replay those side effects" % bwd_persist)

    needed_grads = sorted((tail_read | fetch_set) & bwd_written)
    feed_set, state_set = set(feed_names), set(state_in_names)
    diff_names = []
    for g in needed_grads:
        if not g.endswith("@GRAD"):
            raise NotImplementedError(
                "remat: optimizer/fetch consumes backward var %r that is "
                "not a gradient" % g)
        p = g[: -len("@GRAD")]
        if p not in feed_set and p not in state_set:
            raise NotImplementedError(
                "remat: gradient of intermediate var %r requested; only "
                "parameter/feed gradients survive the remat lowering" % p)
        diff_names.append(p)

    fwd_written = set()
    for _, op in fwd_ops:
        fwd_written.update(
            n for n in op.output_arg_names() if n != EMPTY_VAR_NAME)
    state_out_set = set(block_program.state_out_names)
    aux_names = sorted(
        (tail_read | fetch_set | state_out_set | {l for l, _ in losses})
        & fwd_written)

    # contiguous segments; boundary vars = reads-from-outside per segment
    nseg = max(1, min(int(n_segments), len(fwd_ops)))
    bounds = [len(fwd_ops) * s // nseg for s in range(nseg + 1)]
    segments = [fwd_ops[bounds[s]: bounds[s + 1]] for s in range(nseg)]
    aux_left = set(aux_names)
    seg_descs = []  # (ops, in_names, out_names)
    produced_before = feed_set | state_set
    for s, seg in enumerate(segments):
        writes, reads = [], []
        wset, rset = set(), set()
        for _, op in seg:
            for n in op.input_arg_names():
                if (n != EMPTY_VAR_NAME and n not in wset
                        and n not in rset and n in produced_before):
                    reads.append(n)
                    rset.add(n)
            for n in op.output_arg_names():
                if n != EMPTY_VAR_NAME and n not in wset:
                    writes.append(n)
                    wset.add(n)
        later_reads = set()
        for later in segments[s + 1:]:
            for _, op in later:
                later_reads.update(op.input_arg_names())
        outs = [n for n in writes if n in later_reads or n in aux_left]
        seg_descs.append((seg, reads, outs))
        produced_before |= wset

    # stop_gradient vars (trace-time static set): replicate
    # append_backward's pruning — a marked var must not pass gradient to
    # ANY consumer, so the barrier applies right as the op binds it
    _sg_names = set()
    for _, op in fwd_ops:
        for n in op.output_arg_names():
            if n == EMPTY_VAR_NAME:
                continue
            vd = block.find_var_recursive(n)
            if vd is not None and vd.stop_gradient and not vd.is_parameter:
                _sg_names.add(n)

    def _sg_op_outputs(op, env):
        for n in op.output_arg_names():
            if (n in _sg_names and hasattr(env.get(n), "dtype")
                    and jnp.issubdtype(env[n].dtype, jnp.floating)):
                env[n] = jax.lax.stop_gradient(env[n])

    def fn(feed_values, state_values, rng_key):
        base = {}
        for name, val in zip(feed_names, feed_values):
            base[name] = val
        for name, val in zip(state_in_names, state_values):
            base[name] = val
        diff_set = set(diff_names)
        others = {k: v for k, v in base.items() if k not in diff_set}

        def seg_callable(seg, in_names, out_names):
            def run_seg(key, *in_vals):
                env = dict(others)
                env.update(zip(in_names, in_vals))
                with amp_scope(amp):
                    for j, op in seg:
                        run_op(op, block, env, key, j, is_test, executor,
                               prov=prov)
                        _sg_op_outputs(op, env)
                return tuple(env[n] for n in out_names)
            return run_seg

        def loss_fn(diff_vals):
            env = dict(others)
            env.update(zip(diff_names, diff_vals))
            for seg, in_names, out_names in seg_descs:
                seg_f = jax.checkpoint(
                    seg_callable(seg, in_names, out_names))
                outs = seg_f(rng_key, *[env[n] for n in in_names])
                env.update(zip(out_names, outs))
            total = jnp.float32(0.0)
            for lname, seed in losses:
                total = total + jnp.sum(
                    env[lname].astype(jnp.float32)) * seed
            return total, tuple(env[n] for n in aux_names)

        diff_vals = tuple(base[p] for p in diff_names)
        (_, aux), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(diff_vals)

        env = dict(base)
        env.update(zip(aux_names, aux))
        for p, g in zip(diff_names, grads):
            env[p + "@GRAD"] = g.astype(base[p].dtype)
        # the seed vars the fill ops would have produced (a fetch of
        # loss@GRAD must serve the same constant the explicit chain binds)
        for lname, seed_val in losses:
            env[lname + "@GRAD"] = jnp.full_like(env[lname], seed_val)

        with amp_scope(amp):
            for j, op in tail_ops:
                run_op(op, block, env, rng_key, j, is_test, executor,
                       prov=prov)

        fetches = [densify(env[n]) for n in block_program.fetch_names]
        state_out = [densify(env[n])
                     for n in block_program.state_out_names]
        return fetches, state_out

    return fn


def np_value_for_var(var_desc, value):
    """Coerce a host value to the var's declared dtype/shape."""
    dtype = convert_dtype_to_np(var_desc.dtype)
    arr = np.asarray(value, dtype=dtype)
    return arr


def lower_block_accumulated(block_program, k, is_test=False, executor=None,
                            amp=False, prov=None):
    """Gradient-accumulation lowering: the forward/backward segment runs as
    a ``lax.scan`` over ``k`` micro-batches (feeds reshaped [k, B/k, ...]),
    gradients crossing into the optimizer segment are averaged, and the
    optimizer/LR ops run ONCE on the averaged gradients — the compiled-scan
    form of the reference's batch-merge capability (reference:
    paddle/fluid/framework/ir/multi_batch_merge_pass.cc, which repeats the
    fwd/bwd subgraph k times and sums grads before the update).

    Numerics: mean-reduced losses make k-step accumulation EXACTLY equal to
    one k*B batch (mean of micro-batch grads == big-batch grad), including
    global-norm clipping, which sees the averaged grads. Persistable state
    written inside the scan (BN running stats) updates sequentially per
    micro-batch, like k real steps would.
    """
    import jax

    from paddle_tpu.core.registry import amp_scope
    from paddle_tpu.core.selected_rows import SelectedRows, densify

    block = block_program.block
    feed_names = block_program.feed_names
    state_in_names = block_program.state_in_names

    from paddle_tpu.framework import OpRole

    ONCE_ROLES = OpRole.Optimize | OpRole.RPC | OpRole.LRSched
    scan_ops, once_ops = [], []
    for op in block_program.ops:
        role = int(op.attrs.get("op_role", 0))
        (once_ops if role & ONCE_ROLES else scan_ops).append(op)

    def _is_persistable(name):
        vd = block.find_var_recursive(name)
        return vd is not None and vd.persistable

    written_scan = []
    for op in scan_ops:
        for n in op.output_arg_names():
            if n != EMPTY_VAR_NAME and n not in written_scan:
                written_scan.append(n)
    written_scan_set = set(written_scan)
    read_once = set()
    for op in once_ops:
        read_once.update(
            n for n in op.input_arg_names() if n != EMPTY_VAR_NAME)

    state_in_set = set(state_in_names)
    # loop-carried: persistable vars the scan both reads and writes
    # (BN running stats)
    carry_names = [n for n in written_scan
                   if _is_persistable(n) and n in state_in_set]
    # last-value: persistable writes never read (rare) — final micro wins
    last_names = [n for n in written_scan
                  if _is_persistable(n) and n not in state_in_set]
    # averaged: everything the once-segment consumes from the scan (grads)
    cross_names = sorted(
        (read_once & written_scan_set) - set(carry_names) - set(last_names))
    fetch_scan = [n for n in block_program.fetch_names
                  if n in written_scan_set]

    def _mean_stacked(s):
        if isinstance(s, SelectedRows):
            # stacked sparse grads: rows [k, N], values [k, N, ...] —
            # concat micro contributions, scale 1/k
            rows = s.rows.reshape(-1)
            vals = (s.values / k).reshape((-1,) + s.values.shape[2:])
            return SelectedRows(rows, vals, s.height)
        return jnp.mean(s, axis=0)

    def fn(feed_values, state_values, rng_key):
        base = dict(zip(state_in_names, state_values))
        micro_feeds = []
        for name, v in zip(feed_names, feed_values):
            if v.shape[0] % k != 0:
                raise ValueError(
                    "accumulate_steps=%d does not divide feed %r batch "
                    "dim %d" % (k, name, v.shape[0]))
            micro_feeds.append(
                v.reshape((k, v.shape[0] // k) + tuple(v.shape[1:])))

        def micro(carry, inp):
            feeds_t, t = inp
            env = dict(base)
            env.update(zip(carry_names, carry))
            env.update(zip(feed_names, feeds_t))
            key = jax.random.fold_in(rng_key, t)
            with amp_scope(amp):
                for i, op in enumerate(scan_ops):
                    run_op(op, block, env, key, i, is_test, executor,
                           prov=prov)
            new_carry = tuple(env[n] for n in carry_names)
            outs = (tuple(env[n] for n in cross_names),
                    tuple(env[n] for n in last_names),
                    tuple(env[n] for n in fetch_scan))
            return new_carry, outs

        init_carry = tuple(base[n] for n in carry_names)
        carry_final, (cross_st, last_st, fetch_st) = jax.lax.scan(
            micro, init_carry, (tuple(micro_feeds), jnp.arange(k)))

        env = dict(base)
        env.update(zip(carry_names, carry_final))
        for n, s in zip(cross_names, cross_st):
            env[n] = _mean_stacked(s)
        for n, s in zip(last_names, last_st):
            env[n] = jax.tree_util.tree_map(lambda a: a[-1], s)
        with amp_scope(amp):
            for i, op in enumerate(once_ops):
                run_op(op, block, env, rng_key, 100_000 + i, is_test,
                       executor, prov=prov)

        micro_b = micro_feeds[0].shape[1] if micro_feeds else None
        fetch_map = dict(zip(fetch_scan, fetch_st))
        fetches = []
        for n in block_program.fetch_names:
            if n in fetch_map:
                s = fetch_map[n]
                # per-example fetches (leading dim == the micro-batch
                # size) concat back to [k*b, ...]; everything else (loss,
                # metrics, debug tensors) averages — the k*B equivalents
                if (micro_b is not None and s.ndim >= 2
                        and s.shape[1] == micro_b):
                    fetches.append(s.reshape((-1,) + tuple(s.shape[2:])))
                else:
                    fetches.append(jnp.mean(s, axis=0))
            else:
                fetches.append(densify(env[n]))
        state_out = [densify(env[n])
                     for n in block_program.state_out_names]
        return fetches, state_out

    return fn
