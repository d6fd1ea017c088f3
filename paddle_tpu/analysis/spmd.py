"""Static SPMD sharding propagation & communication-cost analysis.

The ahead-of-compile mirror of what XLA's GSPMD partitioner will do to a
program under a device mesh: given a ProgramDesc, a mesh (a jax Mesh, a
``{name: size}`` dict, or a ``parallel.mesh.mesh_signature`` tuple — no
devices needed) and a ``ShardingRules`` table, ``analyze_spmd``

1. **propagates per-var shardings** through every op via per-op-type
   propagation rules over the def-use graph (graph.py), recording
   conflicts (two writers/operands demand different axes on one dim),
   silent full replication of large tensors, and sharding lost at
   barrier ops (op types with no propagation rule — the analyzer cannot
   see through them, and neither can a reader of the program);
2. **derives the collective schedule** the partitioner must insert. The
   emission law (validated instruction-by-instruction against compiled
   HLO for the bert and resnet book models under dp and dp×tp meshes):
   a psum materializes exactly where a live op contracts or reduces
   over a dim carrying mesh axes —

   * every trainable-param gradient (matmul/conv dW contract the
     batch-sharded dim; bias/LN/BN scale grads reduce over it; embedding
     grads scatter-add over it): one psum, payload = the grad shard
     (full param bytes when the param is replicated);
   * every live forward reduction over a sharded dim (loss means/sums):
     one psum, payload = the reduction output;
   * batch_norm in training mode is sync-BN by construction: two
     forward psums ([C] mean + [C] var) per op;
   * a fetched var still sharded at the fetch boundary: one all-gather
     (fetches are replicated by the engine's out_shardings).

   Per-collective payload bytes are the logical tensor bytes with every
   sharded dim divided by its axis-product — the same per-device
   quantity HLO instruction shapes carry — plus a per-step ICI total
   and a ring-traffic estimate (2(n-1)/n per psum hop);
3. **computes per-device peak memory** by re-running the liveness sweep
   (analysis/memory.py) with sharded (divided) shapes, and quantifies
   the **replicated optimizer state** a ZeRO-1-style weight-update
   sharding would reclaim (optimizer slots = persistable non-parameter
   vars read only by Optimize-role ops);
4. registers the ``spmd-unsharded-param`` / ``spmd-replication-blowup``
   / ``spmd-collective-report`` checkers in the pass registry, so
   ``verify=True`` and ``tools/lint_program.py`` get them for free.

The engine validates the schedule at its mesh cache-miss seam: on the
first run of a mesh-compiled executable it parses the jitted HLO
(``hlo_collectives``) and emits ``spmd.prediction_delta`` telemetry.

Known model limits (reported, not silently wrong): the shard_map-wrapped
flash-attention dispatch (kernels/flash_attention.py) spans the mesh's
``tp`` axis whenever tp divides the head count, and XLA then inserts
discretionary resharding around the region; programs containing
``fused_attention`` under a multi-axis mesh are flagged via
``report.shard_map_ops`` instead of predicted exactly.
"""

import re

import numpy as np

from paddle_tpu.analysis.graph import SKIP_OPS, build_graph
from paddle_tpu.analysis.memory import (
    LiveInterval,
    LivenessReport,
    _fmt_bytes,
    _var_nbytes,
    analyze_liveness,
)

__all__ = [
    "Collective", "SpmdReport", "analyze_spmd", "hlo_collectives",
    "measured_collectives", "op_flops_bytes",
]

# Optimize-role bit (mirrors framework.OpRole, reference:
# op_proto_maker.h, without the import cycle).
_ROLE_OPTIMIZE = 0x0002

# Replicated tensors at or above this size, produced from sharded inputs,
# are a "replication blowup": the partitioner materializes the full value
# on every device (spmd-replication-blowup checker threshold).
REPLICATION_BLOWUP_BYTES = 1 << 20

_UNARY_OPS = frozenset({
    "relu", "gelu", "tanh", "sigmoid", "softmax", "scale", "dropout",
    "cast", "clip", "sqrt", "square", "exp", "log", "abs", "pow",
    "rsqrt", "floor", "ceil", "erf", "assign", "increment", "sign",
    "logical_not", "equal", "not_equal", "less_than", "greater_than",
    "one_hot", "top_k", "arg_max", "arg_min", "sequence_mask",
    "fused_elementwise_activation",
})

_ELEMENTWISE_BINARY_OPS = frozenset({
    "elementwise_add", "elementwise_sub", "elementwise_mul",
    "elementwise_div", "elementwise_pow", "elementwise_max",
    "elementwise_min", "elementwise_mod",
})

_REPLICATED_SOURCE_OPS = frozenset({
    "fill_constant", "gaussian_random", "uniform_random", "shape",
    "range", "assign_value",
})

_OPTIMIZER_OPS = frozenset({
    "sgd", "momentum", "adam", "adamw", "lars_momentum", "rmsprop",
    "adagrad", "lamb",
})


def _mesh_axes(mesh):
    """Normalize the mesh argument into an ordered {axis: size} dict.
    Accepts a jax Mesh, a {name: size} dict, a mesh_signature tuple
    (((name, size), ...), device_ids), or None."""
    if mesh is None:
        return {}
    shape = getattr(mesh, "shape", None)
    if shape is not None:  # jax Mesh (shape is an OrderedDict)
        return {str(k): int(v) for k, v in shape.items()}
    if isinstance(mesh, dict):
        return {str(k): int(v) for k, v in mesh.items()}
    if isinstance(mesh, (tuple, list)):  # mesh_signature
        axes = mesh[0] if (len(mesh) == 2
                           and not isinstance(mesh[0], str)) else mesh
        return {str(n): int(s) for n, s in axes}
    raise TypeError("cannot interpret mesh %r" % (mesh,))


def _spec_dims(spec, ndim):
    """PartitionSpec -> per-dim tuple of axis tuples, padded to ndim.
    ``P('dp', None)`` over rank 3 -> ``(('dp',), (), ())``."""
    dims = []
    for entry in tuple(spec):
        if entry is None:
            dims.append(())
        elif isinstance(entry, (tuple, list)):
            dims.append(tuple(str(a) for a in entry))
        else:
            dims.append((str(entry),))
    while len(dims) < ndim:
        dims.append(())
    return tuple(dims[:ndim])


def _axes_of(dims):
    axes = []
    for entry in dims or ():
        axes.extend(entry)
    return tuple(axes)


def _dims_to_pspec(dims):
    """Per-dim axis tuples back into a PartitionSpec (the inverse of
    ``_spec_dims``) — the form ``parallel.sharding.zero1_extend_spec``
    takes, so the analyzer runs the engine's placement rule verbatim."""
    from jax.sharding import PartitionSpec

    return PartitionSpec(*[
        ((tuple(e) if len(e) > 1 else e[0]) if e else None)
        for e in (dims or ())])


def _dims_str(dims):
    if not dims or not any(dims):
        return "replicated"
    return "[%s]" % ", ".join(
        ("x".join(e) if e else "-") for e in dims)


class Collective:
    """One predicted collective: ``kind`` ('psum' | 'all_gather'),
    the mesh ``axes`` it spans, the payload var and its per-device
    ``nbytes``, and where in the program it materializes."""

    __slots__ = ("kind", "axes", "var", "nbytes", "op_type", "op_idx",
                 "order", "phase", "reason")

    def __init__(self, kind, axes, var, nbytes, op_type, op_idx, order,
                 phase, reason):
        self.kind = kind
        self.axes = tuple(axes)
        self.var = var
        self.nbytes = int(nbytes)
        self.op_type = op_type
        self.op_idx = op_idx
        self.order = order
        self.phase = phase
        self.reason = reason

    def __repr__(self):
        return "Collective(%s over %s: %s %s @%s)" % (
            self.kind, "x".join(self.axes) or "?", self.var,
            _fmt_bytes(self.nbytes), self.op_type)


class OptStateReport:
    """Replicated-optimizer-state accounting: the ZeRO-1 ledger."""

    def __init__(self, entries, data_shards):
        # entries: [(name, full_nbytes, per_device_nbytes)]
        self.entries = entries
        self.data_shards = max(int(data_shards), 1)

    @property
    def per_device_bytes(self):
        return sum(e[2] for e in self.entries)

    @property
    def replicated_bytes(self):
        """Optimizer-state bytes currently held identically on every
        device (slots whose per-device copy is the full tensor)."""
        return sum(e[2] for e in self.entries if e[1] == e[2])

    @property
    def zero1_savings_bytes(self):
        """Per-device bytes a ZeRO-1 weight-update sharding over the
        data axes would reclaim from the replicated slots."""
        if self.data_shards <= 1:
            return 0
        return int(self.replicated_bytes
                   * (self.data_shards - 1) // self.data_shards)

    def render(self):
        lines = ["optimizer state: %s per device across %d slot vars; "
                 "%s replicated -> ZeRO-1 over %d data shards would "
                 "save %s/device"
                 % (_fmt_bytes(self.per_device_bytes), len(self.entries),
                    _fmt_bytes(self.replicated_bytes), self.data_shards,
                    _fmt_bytes(self.zero1_savings_bytes))]
        for name, full, per_dev in sorted(
                self.entries, key=lambda e: (-e[2], e[0]))[:10]:
            lines.append("  %-12s %-44s%s" % (
                _fmt_bytes(per_dev), name,
                "  (replicated)" if full == per_dev else ""))
        return "\n".join(lines)


class SpmdReport:
    """Everything ``analyze_spmd`` derives; ``empty`` when no mesh."""

    def __init__(self, mesh_axes, data_axes=()):
        self.mesh_axes = dict(mesh_axes)       # {axis: size}
        self.data_axes = tuple(data_axes)
        self.shardings = {}                    # var -> dims tuple
        self.collectives = []                  # [Collective]
        self.conflicts = []      # [(var, dim, axes_a, axes_b, op_type)]
        self.barriers = []       # [(op_type, op_idx, [vars sharding lost])]
        self.replication = []    # [(var, nbytes, producer_op_type)]
        self.shard_map_ops = []  # [(op_type, op_idx)] — wrapped dispatches
        self.per_device_peak_bytes = 0
        self.replicated_peak_bytes = 0
        self.opt_state = OptStateReport([], 1)
        self.suppressed_dead = 0  # collectives not emitted: op was dead
        self.zero1 = False       # analyzed under the sharded update?

    @property
    def empty(self):
        return not self.mesh_axes

    @property
    def n_devices(self):
        n = 1
        for s in self.mesh_axes.values():
            n *= s
        return n

    @property
    def psum_count(self):
        return sum(1 for c in self.collectives if c.kind == "psum")

    @property
    def all_gather_count(self):
        return sum(1 for c in self.collectives if c.kind == "all_gather")

    @property
    def total_bytes(self):
        """Per-step ICI payload bytes: the sum of per-device collective
        payloads — the quantity HLO instruction shapes carry."""
        return sum(c.nbytes for c in self.collectives)

    def ring_traffic_bytes(self):
        """Ring-algorithm wire-byte estimate: each psum moves
        2(n-1)/n x payload per device, an all-gather (n-1)/n."""
        total = 0.0
        for c in self.collectives:
            n = 1
            for a in c.axes:
                n *= self.mesh_axes.get(a, 1)
            if n <= 1:
                continue
            factor = (2.0 if c.kind == "psum" else 1.0) * (n - 1) / n
            total += factor * c.nbytes
        return int(total)

    def sharding_table(self, only_sharded=False):
        rows = []
        for name in sorted(self.shardings):
            dims = self.shardings[name]
            if only_sharded and not any(dims):
                continue
            rows.append((name, _dims_str(dims)))
        return rows

    def render(self, top=12):
        if self.empty:
            return "spmd: no mesh — nothing to analyze"
        mesh = ",".join("%s=%d" % kv for kv in self.mesh_axes.items())
        lines = ["spmd over mesh {%s} (%d devices)"
                 % (mesh, self.n_devices)]
        sharded = self.sharding_table(only_sharded=True)
        lines.append("sharded vars: %d of %d tracked"
                     % (len(sharded), len(self.shardings)))
        for name, d in sharded[:top]:
            lines.append("  %-44s %s" % (name, d))
        if len(sharded) > top:
            lines.append("  ... %d more" % (len(sharded) - top))
        lines.append(
            "collective schedule: %d psums + %d all-gathers, %s "
            "payload/step (~%s ring traffic)"
            % (self.psum_count, self.all_gather_count,
               _fmt_bytes(self.total_bytes),
               _fmt_bytes(self.ring_traffic_bytes())))
        by_size = sorted(self.collectives,
                         key=lambda c: (-c.nbytes, c.order))
        for c in by_size[:top]:
            lines.append("  %-10s %-8s over %-8s %-40s (%s, %s)" % (
                _fmt_bytes(c.nbytes), c.kind, "x".join(c.axes) or "-",
                c.var, c.op_type, c.phase))
        if len(self.collectives) > top:
            lines.append("  ... %d more" % (len(self.collectives) - top))
        lines.append(
            "per-device peak: %s (vs %s replicated — %.2fx)"
            % (_fmt_bytes(self.per_device_peak_bytes),
               _fmt_bytes(self.replicated_peak_bytes),
               (self.replicated_peak_bytes
                / max(self.per_device_peak_bytes, 1))))
        if self.zero1:
            lines.append(
                "ZeRO-1 sharded update: ON — slots partitioned over "
                "the data axes; the ledger below is post-sharding")
        lines.append(self.opt_state.render())
        for var, dim, a, b, op_type in self.conflicts[:top]:
            lines.append("conflict: %s dim %d wants %s vs %s (at %s)"
                         % (var, dim, "x".join(a) or "-",
                            "x".join(b) or "-", op_type))
        for op_type, op_idx, lost in self.barriers[:top]:
            lines.append("barrier: op %d (%s) has no propagation rule; "
                         "sharding lost for %s"
                         % (op_idx, op_type, ", ".join(lost)))
        for var, nb, prod in self.replication[:top]:
            lines.append("replication blowup: %s (%s) is fully "
                         "replicated downstream of sharded inputs "
                         "(produced by %s)" % (var, _fmt_bytes(nb), prod))
        return "\n".join(lines)


class _Propagator:
    """One whole-program propagation walk; the per-op-type rules live in
    the ``_op_*`` methods, dispatched by name."""

    def __init__(self, graph, mesh_axes, shard_rules, data_axes,
                 feed_names, feed_shapes, fetch_names, block_idx=0,
                 zero1=False):
        self.graph = graph
        self.mesh_axes = mesh_axes
        self.rules = shard_rules
        self.data_axes = tuple(a for a in data_axes if a in mesh_axes)
        self.feed_names = set(feed_names or ())
        self.feed_shapes = dict(feed_shapes or {})
        self.fetch_names = (None if fetch_names is None
                            else list(fetch_names))
        self.block_idx = block_idx
        self.zero1 = bool(zero1)
        self.zero_params = {}  # param -> extended dims (update shard)
        self.zero_grads = {}   # grad var -> dims (constraint point)
        self.zero_slots = {}   # slot var -> dims (partitioned state)
        self.default_dim = max(
            (int(s[0]) for s in self.feed_shapes.values()
             if len(s) and int(s[0]) > 0), default=1)
        self.report = SpmdReport(mesh_axes, self.data_axes)
        self.specs = self.report.shardings
        self._live = None

    # -- shared helpers ----------------------------------------------------
    def axes_size(self, axes):
        n = 1
        for a in axes:
            n *= self.mesh_axes.get(a, 1)
        return n

    def shape_of(self, v):
        """Var's static shape with dynamic -1 dims resolved from the
        feed shapes (or the batch-sized default), like memory.py."""
        vd = v.desc
        if vd is None or vd.shape is None:
            return None
        hint = self.feed_shapes.get(v.name)
        shape = []
        for i, d in enumerate(vd.shape):
            d = int(d) if d is not None else -1
            if d < 0:
                d = (int(hint[i]) if hint is not None and i < len(hint)
                     else self.default_dim)
            shape.append(d)
        return tuple(shape)

    def nbytes_of(self, v, dims=None):
        """Per-device bytes of ``v`` under ``dims`` (its own spec when
        None): full bytes with every sharded dim divided."""
        full = _var_nbytes(v, self.feed_shapes, self.default_dim)
        dims = self.specs.get(v.name) if dims is None else dims
        return full // max(self.axes_size(_axes_of(dims)), 1)

    def spec(self, v):
        return self.specs.get(v.name, ())

    def set_spec(self, v, dims):
        ndim = (len(v.desc.shape) if v.desc is not None
                and v.desc.shape is not None else len(dims or ()))
        dims = tuple(dims or ())[:ndim] if ndim else tuple(dims or ())
        while len(dims) < ndim:
            dims = dims + ((),)
        self.specs[v.name] = dims

    def merge(self, a, b, op=None, var=None):
        """Per-dim union of two specs; a genuine disagreement (both
        sides name different axes for one dim) is recorded as a
        conflict and resolved in favor of ``a``."""
        if not a:
            return b
        if not b:
            return a
        out = []
        for i in range(max(len(a), len(b))):
            ea = a[i] if i < len(a) else ()
            eb = b[i] if i < len(b) else ()
            if ea and eb and set(ea) != set(eb):
                self.report.conflicts.append(
                    (var or "?", i, ea, eb,
                     op.type if op is not None else "?"))
                out.append(ea)
            else:
                out.append(ea or eb)
        return tuple(out)

    def emit(self, op, kind, axes, payload_var, nbytes, phase, reason):
        axes = tuple(a for a in axes if self.mesh_axes.get(a, 1) > 1)
        if not axes or nbytes <= 0:
            return
        if self._live is not None and not self._live.get(op.order, True):
            self.report.suppressed_dead += 1
            return
        self.report.collectives.append(Collective(
            kind, axes, payload_var, nbytes, op.type, op.op_idx,
            op.order, phase, reason))

    # -- liveness (mirror of the engine's DCE / passes.DeadOpPass) --------
    def _compute_live(self):
        if self.fetch_names is None:
            self._live = None  # unknown fetches: treat every op as live
            return
        ops = [op for op in self.graph.block_ops(self.block_idx)
               if op.type not in SKIP_OPS]
        live_vars = set(self.fetch_names)
        live = {}
        for op in reversed(ops):
            out_names = [v.name for _, v in op.out_edges]
            is_live = (not out_names
                       or any(n in live_vars for n in out_names)
                       or any(v.persistable for _, v in op.out_edges))
            live[op.order] = is_live
            if is_live:
                live_vars.update(v.name for _, v in op.in_edges)
        self._live = live

    # -- seeding -----------------------------------------------------------
    def _seed(self):
        """Initial specs: feeds batch-sharded over the data axes when
        the (resolved) leading dim divides (parallel/sharding.py
        batch_sharding), persistable state per the rule table (the
        engine's state_sharding, including its rank-mismatch fallback to
        replicated)."""
        n_data = self.axes_size(self.data_axes)
        for v in self.graph.all_vars():
            if not v.declared:
                continue
            if v.name in self.feed_names:
                shape = self.shape_of(v)
                if (self.data_axes and shape and len(shape) >= 1
                        and n_data > 1 and shape[0] % n_data == 0):
                    self.set_spec(v, (tuple(self.data_axes),))
                else:
                    self.set_spec(v, ())
            elif v.persistable and self.rules is not None:
                ndim = (len(v.desc.shape)
                        if v.desc.shape is not None else None)
                try:
                    spec = self.rules.spec_for(v.name)
                except ValueError:
                    spec = ()
                dims = _spec_dims(spec, ndim or len(tuple(spec)))
                if ndim is not None and len(tuple(spec)) > ndim:
                    dims = ()  # engine replicates on rank mismatch
                self.set_spec(v, dims)
            elif v.persistable:
                self.set_spec(v, ())

    # -- ZeRO-1 seeding ----------------------------------------------------
    def _seed_zero1(self):
        """Mirror of ``parallel.sharding.zero1_plan`` over the def-use
        graph — the SAME placement rule (``zero1_extend_spec``) the
        engine compiles with, so the predicted schedule is the compiled
        one: slot vars (moments, velocity) are re-seeded with the data
        axes extended onto the first divisible dim (the opt-state
        ledger then reads ~zero), each param grad is marked for the
        reduce-scatter constraint, and the param itself keeps its base
        layout — the replicated ParamOut is what the update all-gathers
        back into (emitted in ``_optimizer_op``)."""
        from paddle_tpu.core.types import VarType
        from paddle_tpu.parallel.sharding import (
            ZERO1_EXCLUDED_GRAD_OPS,
            ZERO1_REPLICATED_GRAD_OPS,
            zero1_extend_spec,
        )

        for op in self.graph.block_ops(self.block_idx):
            if op.type in SKIP_OPS or not (op.role() & _ROLE_OPTIMIZE):
                continue
            param, grad = self._in(op, "Param"), self._in(op, "Grad")
            if (param is None or grad is None or param.desc is None
                    or param.desc.shape is None):
                continue
            gt = getattr(grad.desc, "type", None) \
                if grad.desc is not None else None
            if gt is not None and int(gt) == int(VarType.SELECTED_ROWS):
                continue  # sparse grads keep the replicated path
            gw = set(w.type for w in grad.writers)
            if gw & ZERO1_EXCLUDED_GRAD_OPS:
                continue  # batch-norm updates stay replicated
            shape = tuple(param.desc.shape)
            zspec = zero1_extend_spec(
                _dims_to_pspec(self.specs.get(param.name, ())), shape,
                self.data_axes, self.mesh_axes)
            if zspec is None:
                continue
            zdims = _spec_dims(zspec, len(shape))
            self.zero_params[param.name] = zdims
            # scatter-add grads are pinned replicated (see
            # ZERO1_REPLICATED_GRAD_OPS); only the slots + update shard
            self.zero_grads[grad.name] = (
                () if gw & ZERO1_REPLICATED_GRAD_OPS else zdims)
            for slot, v in op.in_edges:
                if slot in ("Param", "Grad") or v.name in self.zero_slots:
                    continue
                if (v.desc is None or not v.persistable
                        or getattr(v.desc, "is_parameter", False)
                        or v.desc.shape is None):
                    continue
                sspec = zero1_extend_spec(
                    _dims_to_pspec(self.specs.get(v.name, ())),
                    tuple(v.desc.shape), self.data_axes, self.mesh_axes)
                if sspec is not None:
                    sdims = _spec_dims(sspec, len(v.desc.shape))
                    self.zero_slots[v.name] = sdims
                    self.set_spec(v, sdims)

    # -- walk --------------------------------------------------------------
    def run(self):
        self._compute_live()
        self._seed()
        if self.zero1:
            self._seed_zero1()
        for op in self.graph.block_ops(self.block_idx):
            if op.type in SKIP_OPS:
                continue
            self._apply(op)
        self._fetch_gathers()
        return self.report

    def _apply(self, op):
        self._dispatch(op)
        if self.zero_grads:
            # ZeRO-1 constraint points: the engine pins every planned
            # grad to its extended spec wherever an op (re)binds that
            # name, so any op writing it leaves it reduce-scattered
            for _, v in op.out_edges:
                zd = self.zero_grads.get(v.name)
                if zd is not None:
                    self.set_spec(v, zd)

    def _dispatch(self, op):
        t = op.type
        if t.endswith("_grad"):
            self._grad_op(op)
            return
        if t in _OPTIMIZER_OPS or (op.role() & _ROLE_OPTIMIZE
                                   and t not in _ELEMENTWISE_BINARY_OPS):
            self._optimizer_op(op)
            return
        handler = getattr(self, "_op_" + t, None)
        if handler is not None:
            handler(op)
            return
        if t in _UNARY_OPS:
            self._op_unary(op)
            return
        if t in _ELEMENTWISE_BINARY_OPS:
            self._op_elementwise_binary(op)
            return
        if t in _REPLICATED_SOURCE_OPS:
            for _, v in op.out_edges:
                self.set_spec(v, ())
            return
        self._barrier(op)

    def _barrier(self, op):
        lost = [v.name for _, v in op.in_edges if any(self.spec(v))]
        for _, v in op.out_edges:
            self.set_spec(v, ())
            nb = self.nbytes_of(v, dims=())
            if lost and nb >= REPLICATION_BLOWUP_BYTES:
                self.report.replication.append((v.name, nb, op.type))
        if lost:
            self.report.barriers.append((op.type, op.op_idx, lost))

    # -- generic families --------------------------------------------------
    def _in(self, op, slot):
        for s, v in op.in_edges:
            if s == slot:
                return v
        return None

    def _ins(self, op, slot):
        return [v for s, v in op.in_edges if s == slot]

    def _out(self, op, slot):
        for s, v in op.out_edges:
            if s == slot:
                return v
        return None

    def _op_unary(self, op):
        x = self._in(op, "X") or (op.in_edges[0][1] if op.in_edges
                                  else None)
        dims = self.spec(x) if x is not None else ()
        for _, v in op.out_edges:
            self.set_spec(v, dims)

    def _op_elementwise_binary(self, op):
        x, y = self._in(op, "X"), self._in(op, "Y")
        xs = self.spec(x) if x is not None else ()
        ys = self.spec(y) if y is not None else ()
        xr = len(self.shape_of(x) or xs) if x is not None else len(xs)
        yr = len(self.shape_of(y) or ys) if y is not None else len(ys)
        if yr < xr:  # broadcast Y: align its dims to X's trailing dims
            axis = int(op.desc.attrs.get("axis", -1))
            off = xr - yr if axis in (-1, None) else axis
            ys = ((),) * max(off, 0) + tuple(ys)
        out = self.merge(tuple(xs), tuple(ys), op=op,
                         var=(op.out_edges[0][1].name if op.out_edges
                              else None))
        for _, v in op.out_edges:
            self.set_spec(v, out)

    def _op_sum(self, op):
        dims = ()
        for _, v in op.in_edges:
            dims = self.merge(dims, self.spec(v), op=op,
                              var=(op.out_edges[0][1].name
                                   if op.out_edges else None))
        for _, v in op.out_edges:
            self.set_spec(v, dims)

    def _optimizer_op(self, op):
        """ParamOut/MomentOut etc. keep their paired input's sharding
        (the update is elementwise on each shard). Under the ZeRO-1
        sharded update the param's grad and slots arrive dp-sharded
        while ParamOut stays replicated (the engine's out_shardings) —
        the partitioner closes that gap with ONE all-gather per updated
        param, operand = the updated shard (validated against compiled
        HLO; combined gathers keep the count via n_operands)."""
        in_by_slot = dict((s, v) for s, v in op.in_edges)
        for slot, v in op.out_edges:
            src = None
            if slot.endswith("Out"):
                src = in_by_slot.get(slot[:-3])
            if src is None:
                src = in_by_slot.get("Param")
            self.set_spec(v, self.spec(src) if src is not None else ())
        param = in_by_slot.get("Param")
        zdims = (self.zero_params.get(param.name)
                 if param is not None else None)
        if zdims is not None:
            axes = tuple(sorted(set(_axes_of(zdims))
                                - set(_axes_of(self.spec(param)))))
            self.emit(op, "all_gather", axes, param.name,
                      self.nbytes_of(param, dims=zdims), "optimize",
                      "ZeRO-1 update all-gathers the param shard")

    def _grad_op(self, op):
        """Gradients are isomorphic to their forward vars: spec(X@GRAD)
        = spec(X). The collective law: a persistable (trainable) var's
        gradient contracts every sharded dim its forward op consumed, so
        axes carried by the grad op's INPUTS but absent from the param's
        own layout are psummed — one collective, payload = the grad
        shard."""
        in_axes = set()
        for _, v in op.in_edges:
            in_axes.update(_axes_of(self.spec(v)))
        for _, v in op.out_edges:
            if v.is_grad and v.forward_var is not None \
                    and v.forward_var.declared:
                fwd = v.forward_var
                dims = self.spec(fwd)
                self.set_spec(v, dims)
                if fwd.persistable:
                    contract = tuple(sorted(
                        in_axes - set(_axes_of(dims))))
                    self.emit(op, "psum", contract, v.name,
                              self.nbytes_of(v, dims=dims), "backward",
                              "param grad contracts sharded dim")
            else:
                # non-grad auxiliary outputs (e.g. XShape) or grads of
                # undeclared names: propagate the first input's spec
                self.set_spec(v, ())
        # batch_norm_grad additionally reduces nothing extra: its
        # dScale/dBias are covered by the persistable rule above.

    # -- specific forward ops ----------------------------------------------
    def _op_mul(self, op):
        x, y = self._in(op, "X"), self._in(op, "Y")
        out = self._out(op, "Out")
        xnum = int(op.desc.attrs.get("x_num_col_dims", 1))
        ynum = int(op.desc.attrs.get("y_num_col_dims", 1))
        xs, ys = tuple(self.spec(x)), tuple(self.spec(y))
        xr = len(self.shape_of(x) or xs)
        yr = len(self.shape_of(y) or ys)
        lead = tuple(xs[i] if i < len(xs) else () for i in range(xnum))
        tail = tuple(ys[i] if i < len(ys) else ()
                     for i in range(ynum, yr))
        if out is not None:
            self.set_spec(out, lead + tail)
        contract = set()
        for i in range(xnum, xr):
            contract.update(xs[i] if i < len(xs) else ())
        for i in range(0, ynum):
            contract.update(ys[i] if i < len(ys) else ())
        if contract and out is not None:
            self.emit(op, "psum", tuple(sorted(contract)), out.name,
                      self.nbytes_of(out), "forward",
                      "matmul contracts a sharded dim (row-parallel)")

    def _op_matmul(self, op):
        x, y = self._in(op, "X"), self._in(op, "Y")
        out = self._out(op, "Out")
        tx = bool(op.desc.attrs.get("transpose_X",
                                    op.desc.attrs.get("trans_x", False)))
        ty = bool(op.desc.attrs.get("transpose_Y",
                                    op.desc.attrs.get("trans_y", False)))
        xs, ys = tuple(self.spec(x)), tuple(self.spec(y))
        xr = len(self.shape_of(x) or xs)
        yr = len(self.shape_of(y) or ys)
        if xr < 2 or yr < 2:
            self._op_unary(op)
            return
        lead = tuple(self.merge(
            (xs[i] if i < len(xs) else (),),
            (ys[i] if i < len(ys) else (),),
            op=op, var=out.name if out is not None else None)[0]
            for i in range(max(xr, yr) - 2))
        row = xs[xr - 1 if tx else xr - 2] if xs else ()
        col = ys[yr - 2 if ty else yr - 1] if ys else ()
        kx = xs[xr - 2 if tx else xr - 1] if xs else ()
        ky = ys[yr - 1 if ty else yr - 2] if ys else ()
        if out is not None:
            self.set_spec(out, lead + (row, col))
            contract = set(kx) | set(ky)
            if contract:
                self.emit(op, "psum", tuple(sorted(contract)), out.name,
                          self.nbytes_of(out), "forward",
                          "matmul contracts a sharded dim")

    def _op_conv2d(self, op):
        x, w = self._in(op, "Input"), self._in(op, "Filter")
        out = self._out(op, "Output")
        xs, ws = tuple(self.spec(x)), tuple(self.spec(w))
        n = xs[0] if xs else ()
        o = ws[0] if ws else ()
        if out is not None:
            self.set_spec(out, (n, o, (), ()))
            contract = set(xs[1] if len(xs) > 1 else ())
            contract |= set(ws[1] if len(ws) > 1 else ())
            if contract:
                self.emit(op, "psum", tuple(sorted(contract)), out.name,
                          self.nbytes_of(out), "forward",
                          "conv contracts a sharded channel dim")

    def _op_batch_norm(self, op):
        x = self._in(op, "X")
        xs = tuple(self.spec(x))
        y = self._out(op, "Y")
        if y is not None:
            self.set_spec(y, xs)
        chan = xs[1] if len(xs) > 1 else ()
        for slot in ("MeanOut", "VarianceOut", "SavedMean",
                     "SavedVariance"):
            v = self._out(op, slot)
            if v is not None:
                self.set_spec(v, (chan,))
        is_test = bool(op.desc.attrs.get("is_test", False))
        stat_axes = set(_axes_of(xs)) - set(chan)
        if not is_test and stat_axes:
            # sync-BN by construction: the partitioner computes global
            # batch statistics with one psum each for mean and var
            for which, slot in (("mean", "SavedMean"),
                                ("var", "SavedVariance")):
                v = self._out(op, slot) or self._out(op, "MeanOut")
                if v is not None:
                    self.emit(op, "psum", tuple(sorted(stat_axes)),
                              v.name, self.nbytes_of(v, dims=(chan,)),
                              "forward", "sync batch_norm %s" % which)

    # sync_batch_norm is batch_norm with the cross-replica statistics
    # made explicit in the op type; under GSPMD both lower identically,
    # so they share the prediction rule.
    _op_sync_batch_norm = _op_batch_norm

    def _op_layer_norm(self, op):
        x = self._in(op, "X")
        xs = tuple(self.spec(x))
        begin = int(op.desc.attrs.get("begin_norm_axis", 1))
        y = self._out(op, "Y")
        if y is not None:
            self.set_spec(y, xs)
        lead = tuple(xs[:begin])
        for slot in ("Mean", "Variance"):
            v = self._out(op, slot)
            if v is not None:
                self.set_spec(v, lead)

    def _op_lookup_table(self, op):
        ids, w = self._in(op, "Ids"), self._in(op, "W")
        out = self._out(op, "Out")
        ids_s = tuple(self.spec(ids))
        ws = tuple(self.spec(w))
        if out is not None:
            osh = self.shape_of(out) or ()
            dims = list(ids_s[:max(len(osh) - 1, 0)])
            while len(dims) < max(len(osh) - 1, 0):
                dims.append(())
            dims.append(ws[1] if len(ws) > 1 else ())
            self.set_spec(out, tuple(dims))
            vocab = set(ws[0] if ws else ())
            if vocab:
                self.emit(op, "psum", tuple(sorted(vocab)), out.name,
                          self.nbytes_of(out), "forward",
                          "vocab-sharded embedding lookup")

    def _op_reduce_sum(self, op):
        self._reduce(op)

    def _op_reduce_mean(self, op):
        self._reduce(op)

    def _op_reduce_max(self, op):
        self._reduce(op, psum=False)

    def _reduce(self, op, psum=True):
        x = self._in(op, "X")
        out = self._out(op, "Out")
        xs = tuple(self.spec(x))
        xr = len(self.shape_of(x) or xs)
        dims_attr = op.desc.attrs.get("dim", None)
        reduce_all = bool(op.desc.attrs.get("reduce_all", False))
        keep = bool(op.desc.attrs.get("keep_dim", False))
        if reduce_all or not dims_attr:
            reduced = set(range(xr))
        else:
            reduced = set(int(d) % xr for d in dims_attr)
        out_dims, lost = [], set()
        for i in range(xr):
            e = xs[i] if i < len(xs) else ()
            if i in reduced:
                lost.update(e)
                if keep:
                    out_dims.append(())
            else:
                out_dims.append(e)
        if out is not None:
            self.set_spec(out, tuple(out_dims))
            if lost and psum:
                self.emit(op, "psum", tuple(sorted(lost)), out.name,
                          self.nbytes_of(out), "forward",
                          "reduction over a sharded dim")

    def _op_mean(self, op):
        x = self._in(op, "X")
        out = self._out(op, "Out")
        lost = set(_axes_of(self.spec(x)))
        if out is not None:
            self.set_spec(out, ())
            if lost:
                self.emit(op, "psum", tuple(sorted(lost)), out.name,
                          self.nbytes_of(out), "forward",
                          "mean over a sharded dim")

    def _op_softmax_with_cross_entropy(self, op):
        logits = self._in(op, "Logits")
        ls = tuple(self.spec(logits))
        for slot in ("Softmax", "Loss"):
            v = self._out(op, slot)
            if v is not None:
                vr = len(self.shape_of(v) or ls)
                self.set_spec(v, ls[:vr])
        last = set(ls[-1]) if ls else set()
        loss = self._out(op, "Loss")
        if last and loss is not None:
            self.emit(op, "psum", tuple(sorted(last)), loss.name,
                      self.nbytes_of(loss), "forward",
                      "cross-entropy over a class-sharded dim")

    def _op_accuracy(self, op):
        x = self._in(op, "Out") or self._in(op, "X")
        lost = set(_axes_of(self.spec(x))) if x is not None else set()
        for _, v in op.out_edges:
            self.set_spec(v, ())
            if lost:
                self.emit(op, "psum", tuple(sorted(lost)), v.name,
                          self.nbytes_of(v, dims=()), "forward",
                          "accuracy reduces the sharded batch")

    def _op_reshape2(self, op):
        x = self._in(op, "X")
        out = self._out(op, "Out")
        xshape = self._out(op, "XShape")
        if xshape is not None:
            self.set_spec(xshape, ())
        if x is None or out is None:
            return
        in_shape, out_shape = self.shape_of(x), self.shape_of(out)
        xs = tuple(self.spec(x))
        if in_shape is None or out_shape is None:
            self.set_spec(out, ())
            return
        self.set_spec(out, self._reshape_dims(
            in_shape, out_shape, xs, op))

    def _reshape_dims(self, in_shape, out_shape, xs, op):
        """Map sharded dims through a reshape by prefix-product
        alignment: a sharded in-dim lands on the out-dim that starts at
        the same linear offset and still divides; anything else drops
        its sharding (recorded as a barrier — the partitioner reshards
        there)."""
        out_dims = [() for _ in out_shape]
        lost = []
        for i, e in enumerate(xs):
            if not e:
                continue
            pre = int(np.prod(in_shape[:i], dtype=np.int64)) \
                if i else 1
            placed = False
            acc = 1
            for j, od in enumerate(out_shape):
                if acc == pre and od % max(self.axes_size(e), 1) == 0:
                    out_dims[j] = tuple(set(out_dims[j]) | set(e)) \
                        if out_dims[j] else e
                    placed = True
                    break
                acc *= od
            if not placed:
                lost.append(e)
        if lost:
            self.report.barriers.append(
                (op.type, op.op_idx,
                 [v.name for _, v in op.in_edges][:1]))
        return tuple(out_dims)

    def _op_transpose2(self, op):
        x = self._in(op, "X")
        out = self._out(op, "Out")
        xshape = self._out(op, "XShape")
        if xshape is not None:
            self.set_spec(xshape, ())
        perm = [int(a) for a in op.desc.attrs.get("axis", ())]
        xs = tuple(self.spec(x)) if x is not None else ()
        if out is not None and perm:
            self.set_spec(out, tuple(
                xs[p] if p < len(xs) else () for p in perm))
        elif out is not None:
            self.set_spec(out, ())

    def _op_slice(self, op):
        x = self._in(op, "Input") or self._in(op, "X")
        out = self._out(op, "Out")
        xs = tuple(self.spec(x)) if x is not None else ()
        axes = set(int(a) for a in op.desc.attrs.get("axes", ()))
        decrease = sorted(int(a)
                          for a in op.desc.attrs.get("decrease_axis", ()))
        dims = []
        for i, e in enumerate(xs):
            if i in axes:
                e = ()  # slicing a sharded dim reshards it
            dims.append(e)
        for d in reversed(decrease):
            if d < len(dims):
                dims.pop(d)
        if out is not None:
            self.set_spec(out, tuple(dims))

    def _op_pool2d(self, op):
        x = self._in(op, "X")
        out = self._out(op, "Out")
        xs = tuple(self.spec(x)) if x is not None else ()
        if out is not None:
            self.set_spec(out, tuple(
                (xs[i] if i < len(xs) else ()) if i < 2 else ()
                for i in range(len(self.shape_of(out) or (0, 0, 0, 0)))))

    def _op_concat(self, op):
        axis = int(op.desc.attrs.get("axis", 0))
        dims = ()
        for _, v in op.in_edges:
            dims = self.merge(dims, self.spec(v), op=op)
        dims = tuple(() if i == axis else e for i, e in enumerate(dims))
        for _, v in op.out_edges:
            self.set_spec(v, dims)

    def _op_split(self, op):
        axis = int(op.desc.attrs.get("axis", 0))
        x = self._in(op, "X")
        xs = tuple(self.spec(x)) if x is not None else ()
        dims = tuple(() if i == axis else e for i, e in enumerate(xs))
        for _, v in op.out_edges:
            self.set_spec(v, dims)

    def _op_fill_constant_batch_size_like(self, op):
        src = op.in_edges[0][1] if op.in_edges else None
        ss = tuple(self.spec(src)) if src is not None else ()
        for _, v in op.out_edges:
            self.set_spec(v, (ss[0] if ss else (),))

    def _op_fused_attention(self, op):
        """The shard_map-wrapped dispatch: batch stays data-sharded; the
        wrap additionally spans 'tp' over heads when tp divides the head
        count, and XLA inserts discretionary resharding around that
        region — flagged, not predicted (see module docstring)."""
        q = self._in(op, "Q") or (op.in_edges[0][1] if op.in_edges
                                  else None)
        qs = tuple(self.spec(q)) if q is not None else ()
        for _, v in op.out_edges:
            vr = len(self.shape_of(v) or qs)
            self.set_spec(v, qs[:1] + ((),) * max(vr - 1, 0))
        if self.mesh_axes.get("tp", 1) > 1:
            self.report.shard_map_ops.append((op.type, op.op_idx))

    # -- fetch boundary ----------------------------------------------------
    def _fetch_gathers(self):
        """Fetches are replicated by the engine's out_shardings: a var
        still sharded at the boundary costs one all-gather (payload =
        the full gathered value)."""
        for name in (self.fetch_names or ()):
            dims = self.specs.get(name)
            if not dims or not any(dims):
                continue
            v = self.graph.var(self.block_idx, name)
            if v is None:
                continue
            axes = tuple(sorted(set(_axes_of(dims))))
            full = _var_nbytes(v, self.feed_shapes, self.default_dim)
            fetch_op = v.readers[-1] if v.readers else (
                v.writers[-1] if v.writers else None)
            if fetch_op is None:
                continue
            self.emit(fetch_op, "all_gather", axes, name, full,
                      "forward", "fetched var is sharded; fetches "
                      "replicate")


def _sharded_liveness(graph, specs, mesh_axes, feed_shapes, default_dim):
    """The PR 7 liveness sweep re-run with sharded (divided) shapes:
    every interval's bytes shrink by its var's axis-product."""
    base = analyze_liveness(graph, feed_shapes=feed_shapes,
                            default_dim=default_dim)
    intervals = {}
    for name, iv in base.intervals.items():
        div = 1
        for a in _axes_of(specs.get(name, ())):
            div *= mesh_axes.get(a, 1)
        intervals[name] = LiveInterval(
            name, iv.start, iv.end, iv.nbytes // max(div, 1),
            iv.persistable)
    births, deaths = {}, {}
    for iv in intervals.values():
        if iv.nbytes <= 0:
            continue
        births[iv.start] = births.get(iv.start, 0) + iv.nbytes
        deaths[iv.end + 1] = deaths.get(iv.end + 1, 0) + iv.nbytes
    peak, peak_order, running = 0, 0, 0
    for order in range(0, base.n_orders + 1):
        running += births.get(order, 0) - deaths.get(order, 0)
        if running > peak:
            peak, peak_order = running, order
    return base, LivenessReport(intervals, peak, peak_order,
                                base.n_orders)


def _opt_state_report(graph, specs, mesh_axes, data_axes, feed_shapes,
                      default_dim):
    """Optimizer slots = persistable non-parameter vars every reader of
    which is an Optimize-role op (moments, beta-pow accumulators, the
    LR): exactly the state ZeRO-1 shards over the data axes."""
    n_data = 1
    for a in data_axes:
        n_data *= mesh_axes.get(a, 1)
    entries = []
    for v in graph.all_vars():
        if not v.persistable or v.desc is None:
            continue
        if getattr(v.desc, "is_parameter", False):
            continue
        if not v.readers or not all(r.role() & _ROLE_OPTIMIZE
                                    for r in v.readers):
            continue
        full = _var_nbytes(v, feed_shapes, default_dim)
        if full <= 0:
            continue
        div = 1
        for a in _axes_of(specs.get(v.name, ())):
            div *= mesh_axes.get(a, 1)
        entries.append((v.name, full, full // max(div, 1)))
    return OptStateReport(entries, n_data)


def analyze_spmd(program_or_desc, mesh=None, shard_rules=None,
                 data_axes=("dp",), feed_names=None, feed_shapes=None,
                 fetch_names=None, block_idx=0, zero1=False):
    """Whole-program SPMD analysis -> SpmdReport (see module docstring).
    ``mesh`` may be a jax Mesh, a {axis: size} dict, or a
    mesh_signature tuple; None (or an all-1 mesh) returns an empty
    report. ``zero1=True`` analyzes the program under the engine's
    ZeRO-1 weight-update sharding (PADDLE_TPU_ZERO): optimizer slots
    partitioned over the data axes, one all-gather per sharded param
    update, and the opt-state ledger post-sharding. Purely static: no
    devices, no tracing, no XLA."""
    mesh_axes = _mesh_axes(mesh)
    if not mesh_axes or all(s <= 1 for s in mesh_axes.values()):
        return SpmdReport({})
    graph = (program_or_desc
             if hasattr(program_or_desc, "op_nodes")
             else build_graph(program_or_desc))
    if feed_names is None and feed_shapes:
        feed_names = list(feed_shapes)
    prop = _Propagator(graph, mesh_axes, shard_rules, data_axes,
                       feed_names, feed_shapes, fetch_names,
                       block_idx=block_idx, zero1=zero1)
    report = prop.run()
    report.zero1 = prop.zero1 and bool(prop.zero_params)
    base, sharded = _sharded_liveness(
        graph, report.shardings, mesh_axes, prop.feed_shapes,
        prop.default_dim)
    report.replicated_peak_bytes = base.peak_bytes
    report.per_device_peak_bytes = sharded.peak_bytes
    report.opt_state = _opt_state_report(
        graph, report.shardings, mesh_axes, report.data_axes,
        prop.feed_shapes, prop.default_dim)
    return report


# -- measured side: HLO collective extraction -------------------------------

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8, "s32": 4,
    "s16": 2, "s8": 1, "u64": 8, "u32": 4, "u16": 2, "u8": 1, "pred": 1,
    "c64": 8, "c128": 16,
}

_COLLECTIVE_RE = re.compile(
    r"%(?P<name>(?:all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute)(?:-start)?[.\w]*) = "
    r"(?P<sig>.*?)(?P<kind>all-reduce|all-gather|reduce-scatter|"
    r"all-to-all|collective-permute)(?:-start)?\("
    r"(?P<operands>[^)]*)\)")

_SHAPE_RE = re.compile(r"(?P<dt>[a-z]+\d*|pred)\[(?P<dims>[0-9,]*)\]")

_COMMENT_RE = re.compile(r"/\*.*?\*/")

# "%name = <result shape(s)> opcode(": the definition an untyped operand
# reference resolves to (layout suffixes such as {1,0:T(8,128)} never
# start a lowercase "word(" after a space, so the first such match is
# the opcode)
_DEF_RE = re.compile(
    r"^\s*(?:ROOT )?%(?P<name>[^\s=]+) = (?P<sig>.*?) [a-z][\w-]*\(",
    re.M)


def _shape_bytes(dt, dims):
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dt, 4)


def _sig_bytes(sig):
    return sum(_shape_bytes(m.group("dt"), m.group("dims"))
               for m in _SHAPE_RE.finditer(sig))


def hlo_collectives(text):
    """Parse compiled HLO text into the collective ledger:
    ``[{kind, name, nbytes, n_operands}]`` where ``nbytes`` sums the
    per-device operand payload shapes (HLO shapes ARE shard shapes).
    A combined all-reduce over k tensors counts k logical psums —
    ``n_operands`` carries that multiplicity. ``-done`` halves of async
    pairs are skipped (the ``-start`` carries the payload).

    XLA prints operands as bare ``%name`` references unless asked for
    operand shapes, so an operand's payload is the result shape of the
    instruction that defines it; an operand printed with its shape
    inline uses that."""
    defs = None
    out = []
    for m in _COLLECTIVE_RE.finditer(text):
        name = m.group("name")
        if "-done" in name:
            continue
        # long operand lists carry /*index=5*/ position comments
        operands = [o for o in _COMMENT_RE.sub(
            "", m.group("operands")).split(", ") if o]
        nbytes = 0
        for o in operands:
            if "[" not in o:
                if defs is None:
                    defs = {d.group("name"): d.group("sig")
                            for d in _DEF_RE.finditer(text)}
                o = defs.get(o.strip().lstrip("%"), "")
            nbytes += _sig_bytes(o)
        out.append({
            "kind": m.group("kind"),
            "name": name,
            "nbytes": nbytes,
            "n_operands": max(len(operands), 1),
        })
    return out


def measured_collectives(text):
    """Aggregate ``hlo_collectives`` into the quantities the prediction
    seam compares: {psum_count, all_gather_count, total_bytes,
    by_kind}."""
    colls = hlo_collectives(text)
    by_kind = {}
    for c in colls:
        row = by_kind.setdefault(c["kind"], {"count": 0, "bytes": 0})
        row["count"] += c["n_operands"]
        row["bytes"] += c["nbytes"]
    psums = by_kind.get("all-reduce", {}).get("count", 0) \
        + by_kind.get("reduce-scatter", {}).get("count", 0)
    return {
        "psum_count": psums,
        "all_gather_count": by_kind.get("all-gather",
                                        {}).get("count", 0),
        "total_bytes": sum(r["bytes"] for r in by_kind.values()),
        "by_kind": by_kind,
    }


def _op_var_shape(block, name, feed_shapes, default_dim):
    """Concrete shape of ``name`` from its VarDesc with -1 dims resolved
    from the feed hints (or ``default_dim``), or None when undeclared /
    shapeless."""
    if block is None or not name:
        return None
    vd = block.find_var_recursive(name)
    if vd is None or getattr(vd, "shape", None) is None:
        return None
    hint = (feed_shapes or {}).get(name)
    shape = []
    for i, d in enumerate(vd.shape):
        d = int(d) if d is not None else -1
        if d < 0:
            d = (int(hint[i]) if hint is not None and i < len(hint)
                 else default_dim)
        shape.append(max(d, 0))
    return shape


def _prod(shape):
    n = 1
    for d in shape:
        n *= d
    return n


def op_flops_bytes(op, block, feed_shapes=None, default_dim=None):
    """Static per-op cost estimate ``(flops, bytes)`` for the op-level
    roofline (observability/opprof.py) — the per-op analog of the
    aggregate ``cost_analysis()`` MFU feed. Bytes are the op's tensor
    traffic (every declared input + output var, from the same VarDesc
    walk the liveness planner uses); FLOPs follow per-family rules:
    matmul/conv count multiply-accumulates (x2), normalizations and
    softmax count a small per-element constant, everything else one
    flop per output element. ``*_grad`` ops cost ~2x their forward
    (recompute + two matmul-shaped products is the dominant pattern).
    Estimates, not measurements — good to the factor the roofline
    verdict needs, not cycle-exact."""
    import types as _types

    feed_shapes = dict(feed_shapes or {})
    if default_dim is None:
        default_dim = max(
            [int(s[0]) for s in feed_shapes.values() if len(s)] or [1])

    is_grad = op.type.endswith("_grad")
    base = op.type[:-len("_grad")] if is_grad else op.type

    def shape_of(name):
        return _op_var_shape(block, name, feed_shapes, default_dim)

    def first_in(slot):
        names = op.input(slot) if hasattr(op, "input") \
            else op.inputs.get(slot, [])
        return names[0] if names else None

    nbytes = 0
    for name in list(op.input_arg_names()) + list(op.output_arg_names()):
        if not name or name.startswith("@"):
            continue
        vd = block.find_var_recursive(name) if block is not None else None
        if vd is None:
            continue
        nbytes += _var_nbytes(
            _types.SimpleNamespace(name=name, desc=vd),
            feed_shapes, default_dim=default_dim)

    out_elems = 0
    for name in op.output_arg_names():
        s = shape_of(name)
        if s:
            out_elems = max(out_elems, _prod(s))

    flops = out_elems  # default: one flop per output element
    if base in ("mul", "matmul", "matmul_v2"):
        x = shape_of(first_in("X"))
        k = x[-1] if x else 1
        flops = 2 * out_elems * max(k, 1)
    elif base in ("conv2d", "depthwise_conv2d", "conv2d_transpose"):
        f = shape_of(first_in("Filter"))
        per_out = _prod(f[1:]) if f and len(f) > 1 else 1
        flops = 2 * out_elems * max(per_out, 1)
    elif base == "fused_attention":
        q = shape_of(first_in("Q")) or shape_of(first_in("X"))
        seq = q[-2] if q and len(q) >= 2 else 1
        flops = 4 * (_prod(q) if q else out_elems) * max(seq, 1)
    elif base in ("softmax", "softmax_with_cross_entropy", "layer_norm",
                  "batch_norm", "sync_batch_norm",
                  "fused_elemwise_activation"):
        flops = 8 * out_elems
    if is_grad:
        flops *= 2
    return int(flops), int(nbytes)


# -- registry checkers ------------------------------------------------------

from paddle_tpu.analysis.diagnostics import Severity
from paddle_tpu.analysis.passes import Pass, register_pass


def _ctx_report(graph, ctx):
    """One propagation per verify run, shared by the three checkers via
    a cache stashed on the context object."""
    cached = getattr(ctx, "_spmd_report", None)
    if cached is not None:
        return cached
    report = analyze_spmd(
        graph, mesh=ctx.mesh, shard_rules=ctx.shard_rules,
        data_axes=ctx.data_axes,
        feed_names=(list(ctx.feed_names) if ctx.feed_names else None),
        fetch_names=(list(ctx.fetch_names)
                     if ctx.fetch_names is not None else None),
        zero1=getattr(ctx, "zero1", False))
    ctx._spmd_report = report
    return report


@register_pass("spmd-unsharded-param")
class UnshardedParamPass(Pass):
    """The static promotion of the runtime ``sharding.unmatched_param``
    warning (parallel/sharding.py): under a mesh with a NON-EMPTY rule
    table, a trainable parameter no rule matches silently replicates on
    every device — declared layout intent is being violated, so this is
    an ERROR and fails lint before any device is touched. (An empty
    table means "replicate everything" on purpose and stays quiet.)
    Shares ``ShardingRules.coverage`` with the engine's runtime path."""

    def check(self, graph, ctx):
        if ctx.mesh is None or ctx.shard_rules is None \
                or not ctx.shard_rules.rules():
            return []
        cov = ctx.shard_rules.coverage(graph.program_desc)
        findings = []
        for name in cov.unmatched:
            findings.append(self.finding(
                Severity.ERROR,
                "trainable param %r matches no sharding rule and will "
                "be fully replicated on every device" % name,
                var_names=[name],
                hint="add a rule for it (or an explicit catch-all "
                     "'.*' -> replicated rule to declare the intent)"))
        return findings


@register_pass("spmd-replication-blowup")
class ReplicationBlowupPass(Pass):
    """WARNING for large tensors the propagation proves fully
    replicated downstream of sharded inputs — each one costs every
    device the full buffer plus the resharding that un-sharded it."""

    def check(self, graph, ctx):
        if ctx.mesh is None:
            return []
        report = _ctx_report(graph, ctx)
        findings = []
        for var, nbytes, producer in report.replication:
            findings.append(self.finding(
                Severity.WARNING,
                "%r (%s) is fully replicated on all %d devices "
                "downstream of sharded inputs (produced by %s)"
                % (var, _fmt_bytes(nbytes), report.n_devices, producer),
                var_names=[var],
                hint="add a propagation rule / sharding rule for it, or "
                     "accept the %s-per-device cost"
                % _fmt_bytes(nbytes)))
        for op_type, op_idx, lost in report.barriers:
            findings.append(self.finding(
                Severity.INFO,
                "op %d (%s) has no sharding propagation rule; inputs "
                "%s lose their sharding there"
                % (op_idx, op_type, ", ".join(lost)),
                var_names=list(lost)))
        return findings


@register_pass("spmd-collective-report")
class CollectiveReportPass(Pass):
    """INFO-only summary: the predicted collective schedule, per-device
    peak vs replicated peak, and the replicated-optimizer-state ledger
    — next to the correctness findings in every --verify/lint run."""

    def check(self, graph, ctx):
        if ctx.mesh is None:
            return []
        report = _ctx_report(graph, ctx)
        if report.empty:
            return []
        findings = [self.finding(
            Severity.INFO,
            "predicted collective schedule: %d psums + %d all-gathers, "
            "%s payload/step (~%s ring traffic)"
            % (report.psum_count, report.all_gather_count,
               _fmt_bytes(report.total_bytes),
               _fmt_bytes(report.ring_traffic_bytes())),
            hint="tools/lint_program.py --spmd prints the full report")]
        findings.append(self.finding(
            Severity.INFO,
            "per-device peak %s vs %s replicated; optimizer state %s "
            "replicated (ZeRO-1 over %d shards would save %s/device)"
            % (_fmt_bytes(report.per_device_peak_bytes),
               _fmt_bytes(report.replicated_peak_bytes),
               _fmt_bytes(report.opt_state.replicated_bytes),
               report.opt_state.data_shards,
               _fmt_bytes(report.opt_state.zero1_savings_bytes))))
        for var, dim, a, b, op_type in report.conflicts:
            findings.append(self.finding(
                Severity.WARNING,
                "sharding conflict on %r dim %d: %s vs %s (at %s)"
                % (var, dim, "x".join(a) or "-", "x".join(b) or "-",
                   op_type),
                var_names=[var],
                hint="two rules/propagations disagree; the partitioner "
                     "will insert a reshard here"))
        for op_type, op_idx in report.shard_map_ops:
            findings.append(self.finding(
                Severity.INFO,
                "op %d (%s) lowers through a shard_map wrap spanning "
                "the tp axis; XLA inserts discretionary resharding "
                "around it that this schedule does not predict"
                % (op_idx, op_type)))
        return findings
