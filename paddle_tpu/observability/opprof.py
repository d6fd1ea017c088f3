"""Op-level device profiling: lowering provenance -> xplane attribution
-> roofline classification.

Fluid's op-by-op executor timed every ``OpDesc`` for free
(reference: paddle/fluid/platform/profiler); the whole-graph jit path
traded that away — the xplane device traces name raw HLO fusions
nobody can map back to a framework op. This module restores the op
granularity in three stages:

1. **Provenance** (written by engine/lowering.py): every op's lowering
   runs inside ``jax.named_scope(provenance_tag(...))`` so the XLA
   ``op_name`` metadata carries ``pt.<op_type>.<block>_<idx>`` through
   fusion. Transform passes stamp ``__src_ops__`` on ops they fuse or
   rewrite so the tag can be expanded back to its source op list.
2. **Attribution** (:func:`attribute`): the compiled HLO text is parsed
   into an instruction -> tag map (:func:`hlo_op_map`; a fusion carries
   its root's tag — the *dominant* policy, recorded in the output). The
   engine only leaves a note of an executable on its first observed
   call (:func:`make_note`, :func:`keep_note`); the note is resolved — lowered and
   compiled again, from JAX's caches, for its HLO text — when the map
   is asked for (:func:`instruction_phases`, :func:`registry_snapshot`),
   after the profiled window and never on a step. The
   xplane device planes are aggregated per tag, and per-op FLOPs/bytes
   estimates (``analysis.spmd.op_flops_bytes``) join in to yield a
   roofline verdict per op: compute-bound / memory-bound / comm-bound
   (collectives get their own lane) under ``PADDLE_TPU_PEAK_FLOPS`` and
   ``PADDLE_TPU_PEAK_MEMBW_BYTES``.
3. **Surfacing**: ``profiler.stop_profiler`` writes the attribution
   table into the run summary and a ``opprof_provenance.json`` sidecar
   next to the trace so offline tools (``tools/perf_report.py
   --roofline``, ``tools/tpu_top.py``) attribute without the live
   process.

Plane parsing (:func:`iter_planes`, :func:`top_ops`) lives HERE — the
package must never import from ``tools/``. Traces are read through
``jax.profiler.ProfileData``, with nothing but JAX.

CPU-probe caveat: CPU xplane planes attribute coarsely (thread lines
interleave HLO thunks with runtime events, durations include dispatch
overhead) — the ``source`` field of the attribution table says
``"cpu-coarse"`` so consumers know the verdicts are only
hardware-trustworthy when it says ``"tpu"``.
"""

import glob
import json
import os
import re
import threading
from collections import defaultdict

SIDECAR_NAME = "opprof_provenance.json"

# pt.<op_type>.<block>_<idx> — op types are \w+ (incl. _grad suffixes)
_TAG_RE = re.compile(r"pt\.(\w+)\.(\d+)_(\d+)")

# one HLO instruction line: "  %name = f32[...] opcode(...), ..." — the
# result type may be a (possibly nested) tuple with /*index=N*/ comments,
# e.g. "%while = (s32[], f32[64,10]{1,0}) while((...) %tuple.4), ..."
_INSTR_RE = re.compile(
    r"^\s+(?:ROOT\s+)?%?(?P<name>[\w.\-]+)\s*=\s*"
    r"(?:\((?:[^()]|\([^()]*\))*\)|\S+)\s+"
    r"(?P<opcode>[\w\-]+)\(")
# a computation header: "%region_0.12 (args) -> ty {" / "ENTRY %main ("
_COMP_RE = re.compile(r"^(?:ENTRY\s+)?%?(?P<name>[\w.\-]+)\s+\(")
_OPNAME_RE = re.compile(r'op_name="([^"]*)"')
# called-computation refs on an instruction line
_CALLS_RE = re.compile(
    r"(?:calls|to_apply|condition|body|select|scatter)="
    r"\{?%?([\w.\-]+)")

_COLLECTIVE_OPCODES = frozenset({
    "all-reduce", "all-gather", "reduce-scatter", "collective-permute",
    "all-to-all", "collective-broadcast", "all-reduce-start",
    "all-gather-start", "collective-permute-start",
})

# event names on CPU thread lines that are runtime machinery, never HLO
_NON_HLO_EVENT_RE = re.compile(
    r"Thunk|Listener|Execute|Dispatch|Callback|BufferAlloc|Stream",
    re.I)


def provenance_tag(op_type, block_idx, op_idx):
    """The named-scope tag the lowering wraps op ``op_idx`` of block
    ``block_idx`` in: ``pt.<op_type>.<block>_<idx>``."""
    return "pt.%s.%d_%d" % (op_type, int(block_idx), int(op_idx))


def parse_tag(op_name):
    """Extract the canonical provenance tag from an XLA ``op_name``
    metadata path (``jit(fn)/.../pt.mul.0_3/dot_general``). Returns the
    ``pt.<type>.<b>_<i>`` string, or None when the path carries no
    provenance (e.g. jit-internal ops)."""
    if not op_name:
        return None
    m = _TAG_RE.search(op_name)
    if m is None:
        return None
    return "pt.%s.%s_%s" % (m.group(1), m.group(2), m.group(3))


def tag_op_type(tag):
    """The framework op type a tag encodes, or None."""
    m = _TAG_RE.search(tag or "")
    return m.group(1) if m else None


def _parse_hlo(hlo_text):
    """-> (instr_tags, instr_kinds, instr_calls, comp_of): each
    instruction's own provenance tag (None without ``op_name`` metadata)
    and opcode, the computations it calls, and the computation it lives
    in."""
    instr_tags = {}
    instr_kinds = {}
    instr_calls = {}
    comp_of = {}  # instr -> computation it lives in
    current = None
    for line in hlo_text.splitlines():
        m = _INSTR_RE.match(line)
        if m is not None:
            name = m.group("name")
            instr_kinds[name] = m.group("opcode")
            om = _OPNAME_RE.search(line)
            instr_tags[name] = parse_tag(om.group(1)) if om else None
            calls = _CALLS_RE.findall(line)
            if calls:
                instr_calls[name] = calls
            if current is not None:
                comp_of[name] = current
            continue
        if line and not line[0].isspace():
            cm = _COMP_RE.match(line)
            if cm is not None and "{" in line:
                current = cm.group("name")
    return instr_tags, instr_kinds, instr_calls, comp_of


def hlo_op_map(hlo_text):
    """Parse compiled HLO text into ``(instr_tags, instr_kinds)``:
    ``{instruction name: provenance tag or None}`` and
    ``{instruction name: opcode}``.

    A fusion instruction carries the ``op_name`` XLA gives it (its
    root's, or its matmul's or convolution's where it holds one) — the
    dominant policy. Instructions with no metadata of their own (e.g.
    ``reduce-window``) inherit the dominant tag of any computation they
    call (``to_apply=%region...``), and in the other direction a tagged
    caller charges its called computations' untagged member
    instructions (a scatter-expanded ``while`` loop's add/copy/
    dynamic-update-slice plumbing executes as per-iteration thunks on
    CPU — that time belongs to the op that owns the loop). The fixpoint
    iterates so nested regions (fusion inside a while body) resolve."""
    return _inherit_tags(*_parse_hlo(hlo_text))


def _inherit_tags(own_tags, instr_kinds, instr_calls, comp_of):
    instr_tags = dict(own_tags)

    def _dominant(comp):
        votes = defaultdict(int)
        for i, c in comp_of.items():
            if c == comp and instr_tags.get(i):
                votes[instr_tags[i]] += 1
        if not votes:
            return None
        return max(votes.items(), key=lambda kv: kv[1])[0]

    for _ in range(4):  # fusion -> region -> instrs, nested one deeper
        changed = False
        dom_cache = {}
        for name, tag in list(instr_tags.items()):
            if tag is not None:
                continue
            for comp in instr_calls.get(name, ()):
                if comp not in dom_cache:
                    dom_cache[comp] = _dominant(comp)
                if dom_cache[comp]:
                    instr_tags[name] = dom_cache[comp]
                    changed = True
                    break
        # downward: a tagged caller charges its called computations'
        # untagged members. Nothing calls ENTRY, so top-level
        # instructions never inherit this way and the honest
        # unattributed bucket is preserved.
        comp_tag = {}
        for name, tag in instr_tags.items():
            if tag is None:
                continue
            for comp in instr_calls.get(name, ()):
                comp_tag.setdefault(comp, tag)
        for i, c in comp_of.items():
            if instr_tags.get(i) is None and comp_tag.get(c):
                instr_tags[i] = comp_tag[c]
                changed = True
        if not changed:
            break
    return instr_tags, instr_kinds


def mixed_phase_instructions(own_tags, instr_calls, comp_of, tag_phase):
    """``{instruction: "backward+optimizer"}`` for the instructions
    (fusions, as a rule) whose called computations hold members of more
    than one phase: a fusion is booked whole to the op XLA names it
    after, so this is the time the phase split books to a neighbour —
    XLA on the TPU fuses a weight gradient's matmul with the optimizer's
    update of that weight. ``own_tags``, ``instr_calls``, ``comp_of``:
    ``_parse_hlo``'s; ``tag_phase``: tag -> phase."""
    members = defaultdict(set)  # computation -> phases of its members
    for instr, comp in comp_of.items():
        phase = tag_phase.get(own_tags.get(instr))
        if phase:
            members[comp].add(phase)
    out = {}
    for name, comps in instr_calls.items():
        phases = set().union(*(members[c] for c in comps))
        if len(phases) > 1:
            out[name] = "+".join(sorted(phases))
    return out


# -- process-level provenance registry --------------------------------------
# Accumulates across every executable registered since the last reset —
# a profiled run typically compiles startup + train-step blocks and all
# of them contribute instructions to the same trace.
_LOCK = threading.Lock()
_REGISTRY = {
    "policy": "dominant",
    "instr_tags": {},   # instr name -> tag or None
    "instr_kinds": {},  # instr name -> opcode
    "costs": {},        # tag -> {op_type, op_role, flops, bytes, src_ops}
    "mixed_phase": {},  # instr -> "backward+optimizer": members span phases
    "collectives": {"hlo_psums": 0, "hlo_bytes": 0, "instances": 0},
}
# Executables the engine has seen run while spans were live or the flag
# was up, not yet resolved: [``make_note``'s tuples].
_NOTES = []
PHASES = ("forward", "backward", "optimizer")


def reset():
    with _LOCK:
        del _NOTES[:]
        _REGISTRY["instr_tags"] = {}
        _REGISTRY["instr_kinds"] = {}
        _REGISTRY["costs"] = {}
        _REGISTRY["mixed_phase"] = {}
        _REGISTRY["collectives"] = {
            "hlo_psums": 0, "hlo_bytes": 0, "instances": 0}


def registry_snapshot():
    """The registry, with every pending note resolved first."""
    resolve_notes()
    with _LOCK:
        return {
            "policy": _REGISTRY["policy"],
            "instr_tags": dict(_REGISTRY["instr_tags"]),
            "instr_kinds": dict(_REGISTRY["instr_kinds"]),
            "costs": {t: dict(c) for t, c in _REGISTRY["costs"].items()},
            "mixed_phase": dict(_REGISTRY["mixed_phase"]),
            "collectives": dict(_REGISTRY["collectives"]),
        }


def role_phase(op_role):
    """``forward``, ``backward`` or ``optimizer`` (Optimize and LRSched)
    from an op's ``op_role`` bits (framework.OpRole); None without one."""
    if op_role is None:
        return None
    from paddle_tpu.framework import OpRole

    role = int(op_role)
    if role & OpRole.Backward:
        return "backward"
    if role & (OpRole.Optimize | OpRole.LRSched):
        return "optimizer"
    return "forward"


def make_note(jitted, args, prov, block=None, feed_names=()):
    """What the device join needs of one executable, made on its first
    call (the cache-miss seam: a shape for each argument, taken before
    the call donates them). Nothing is lowered, compiled or parsed here.
    ``prov`` is the lowering's ``{tag: OpDesc}``, filled by the first
    call's trace. The engine keeps the note on the executable and hands
    it to ``keep_note`` when a step runs while spans are live."""
    import jax

    def shape_of(v):
        # A sharding is kept only where it spans devices: a shape with a
        # one-device sharding lowers to another module text than the call
        # itself made (``sdy.sharding`` on every argument), which JAX's
        # caches would not know.
        sharding = getattr(v, "sharding", None)
        if sharding is not None and len(sharding.device_set) < 2:
            sharding = None
        if not hasattr(v, "dtype"):
            import numpy as np

            v = np.asarray(v)
        return jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=sharding)

    avals = jax.tree_util.tree_map(shape_of, args)
    feed_shapes = {n: tuple(a.shape) for n, a in zip(feed_names, avals[0])}
    return (jitted, avals, prov, block, feed_shapes)


def keep_note(note):
    """Remember an executable for the device join: O(1), on a step.
    ``resolve_notes`` lowers, compiles and parses it when the map is
    asked for, after the window."""
    with _LOCK:
        _NOTES.append(note)


def resolve_notes():
    """Resolve every pending note into the registry: lower and compile
    the noted function again for its shapes (JAX's tracing cache and the
    persistent compilation cache serve it where they can), parse the HLO
    text, register. -> (executables resolved, seconds it took)."""
    import time

    with _LOCK:
        notes = list(_NOTES)
        del _NOTES[:]
    t0, done = time.perf_counter(), 0
    for jitted, avals, prov, block, feed_shapes in notes:
        try:
            hlo = jitted.lower(*avals).compile().as_text()
            register_executable(hlo, prov, block=block,
                                feed_shapes=feed_shapes)
            done += 1
        except Exception as e:  # noqa: BLE001 - the table goes on without it
            import warnings

            from paddle_tpu import observability as obs

            obs.inc("opprof.register_crashes")
            warnings.warn("opprof: an executable's note could not be "
                          "resolved, its instructions stay unattributed: "
                          "%r" % (e,), RuntimeWarning)
    return done, time.perf_counter() - t0


def instruction_phases():
    """``{HLO instruction name: (tag, op type, phase)}`` over every
    executable noted so far (pending notes are resolved first). ``phase``
    is ``forward``, ``backward`` or ``optimizer`` by the ``op_role`` of
    the Fluid op whose lowering made the instruction (a fusion: its
    root's); all three are None where an instruction carries no tag.
    An instruction name is the text between ``%`` and `` = `` of an
    ``XLA Ops`` event's name."""
    snap = registry_snapshot()
    costs = snap["costs"]
    out = {}
    for instr, tag in snap["instr_tags"].items():
        if tag is None:
            out[instr] = (None, None, None)
            continue
        row = costs.get(tag, {})
        out[instr] = (tag, row.get("op_type") or tag_op_type(tag),
                      role_phase(row.get("op_role", 0)))
    return out


def register_executable(hlo_text, prov, block=None, feed_shapes=None):
    """Record one compiled executable's provenance: parse its HLO into
    the instruction->tag map and compute static FLOPs/bytes for every
    op the lowering tagged (``prov``: tag -> OpDesc, collected at trace
    time so tags match exactly what was emitted — including the
    accumulated lowering's once-op index offset)."""
    from paddle_tpu.analysis import spmd

    own_tags, instr_kinds, instr_calls, comp_of = _parse_hlo(hlo_text)
    instr_tags, _ = _inherit_tags(own_tags, instr_kinds, instr_calls,
                                  comp_of)
    try:
        measured = spmd.measured_collectives(hlo_text)
    except Exception:
        measured = {"psum_count": 0, "total_bytes": 0}
    costs = {}
    for tag, op in (prov or {}).items():
        try:
            flops, nbytes = spmd.op_flops_bytes(
                op, block, feed_shapes=feed_shapes)
        except Exception:
            flops, nbytes = 0, 0
        src = op.attrs.get("__src_ops__")
        costs[tag] = {
            "op_type": op.type,
            "op_role": int(op.attrs.get("op_role", 0) or 0),
            "flops": int(flops),
            "bytes": int(nbytes),
            "src_ops": list(src) if src else [op.type],
        }
    mixed = mixed_phase_instructions(
        own_tags, instr_calls, comp_of,
        {t: role_phase(c["op_role"]) for t, c in costs.items()})
    with _LOCK:
        _REGISTRY["instr_tags"].update(instr_tags)
        _REGISTRY["instr_kinds"].update(instr_kinds)
        _REGISTRY["costs"].update(costs)
        _REGISTRY["mixed_phase"].update(mixed)
        _REGISTRY["collectives"]["hlo_psums"] += int(
            measured.get("psum_count", 0))
        _REGISTRY["collectives"]["hlo_bytes"] += int(
            measured.get("total_bytes", 0))
        _REGISTRY["collectives"]["instances"] += sum(
            1 for k in instr_kinds.values()
            if k in _COLLECTIVE_OPCODES and not k.endswith("-start"))
    return len(costs)


def save_sidecar(trace_dir):
    """Write the registry snapshot next to the xplane dumps so offline
    tools (perf_report --roofline) can attribute without the process.
    Returns the sidecar path, or None when there is nothing to save."""
    snap = registry_snapshot()
    if not snap["instr_tags"] and not snap["costs"]:
        return None
    path = os.path.join(trace_dir, SIDECAR_NAME)
    try:
        os.makedirs(trace_dir, exist_ok=True)
        with open(path, "w") as f:
            json.dump(snap, f)
    except OSError:
        return None
    return path


def load_sidecar(trace_dir):
    path = os.path.join(trace_dir, SIDECAR_NAME)
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


# -- xplane parsing ---------------------------------------------------------
def iter_planes(trace_dir):
    """Yield every non-empty DISTINCT plane (``jax.profiler.ProfileData``
    planes: ``.name``, ``.lines`` of ``.events`` with ``.name``,
    ``.start_ns``, ``.duration_ns``, ``.stats``) from the .xplane.pb
    files under ``trace_dir`` (shared by ``top_ops`` and
    observability/tracing.py). Planes alike to the
    last event are skipped — some sessions embed the same device plane
    in more than one dump file, which would double every aggregate —
    while genuine multi-host planes (same name, different
    events/timestamps) all pass through."""
    import hashlib

    from jax.profiler import ProfileData

    files = sorted(glob.glob("%s/**/*.xplane.pb" % trace_dir,
                             recursive=True))
    if not files:
        raise FileNotFoundError("no xplane.pb under %s" % trace_dir)
    seen = set()
    for f in files:
        for plane in ProfileData.from_file(f).planes:
            digest = hashlib.sha256(plane.name.encode())
            events = 0
            for line in plane.lines:
                digest.update(line.name.encode())
                for e in line.events:
                    events += 1
                    digest.update(("%s|%r|%r" % (
                        e.name, e.start_ns, e.duration_ns)).encode())
            if not events or digest.digest() in seen:
                continue
            seen.add(digest.digest())
            yield plane


def instruction_name(event_name):
    """The HLO instruction an ``XLA Ops`` event ran: the trace names the
    event by the instruction's whole text (``%fusion.12 = f32[...]
    fusion(...``) or by its name alone."""
    return event_name.split(" = ", 1)[0].split(" ", 1)[0].lstrip("%")


def top_ops(trace_dir, top_n=25, group="op"):
    """Aggregate device-time by raw HLO op name from the trace's device
    planes (the pre-provenance view; ``group='kind'`` collapses to the
    opcode-ish prefix)."""
    per = defaultdict(float)
    total = 0.0
    for plane in iter_planes(trace_dir):
        if "/device:" in plane.name:
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for e in line.events:
                    name = e.name
                    if group == "kind":
                        name = re.split(r"[.\d]", name, 1)[0]
                    per[name] += e.duration_ns / 1e6
                    total += e.duration_ns / 1e6
    rows = sorted(per.items(), key=lambda kv: -kv[1])[:top_n]
    return rows, total


def device_op_events(trace_dir, known=None):
    """Collect per-HLO-instruction device events from the trace:
    ``([(instr_name, duration_ms)], source)`` where ``source`` is
    ``"tpu"`` when real ``/device:`` planes with ``XLA Ops`` lines were
    found, else ``"cpu-coarse"`` (CPU-client thread lines — durations
    include host dispatch, attribution is approximate).

    On CPU lines only events recognizable as HLO work enter the list:
    the name is in ``known`` (the registered instruction set), carries
    an ``hlo_op`` stat, or at least does not look like runtime
    machinery — so thunk/dispatch noise never pollutes the
    attributed-fraction denominator."""
    known = known or ()
    device_events, cpu_events = [], []
    for plane in iter_planes(trace_dir):
        if "/device:" in plane.name:
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for e in line.events:
                    device_events.append(
                        (instruction_name(e.name), e.duration_ns / 1e6))
        elif "/host:CPU" in plane.name:
            for line in plane.lines:
                if not line.name.startswith("tf_XLA"):
                    continue
                for e in line.events:
                    # "end: <instr>": the close of an instruction's
                    # parallel task on an Eigen thread
                    name = instruction_name(e.name[5:] if e.name.startswith(
                        "end: ") else e.name)
                    if (name not in known
                            and _NON_HLO_EVENT_RE.search(e.name)
                            and not any(k == "hlo_op"
                                        for k, _ in e.stats)):
                        continue
                    cpu_events.append((name, e.duration_ns / 1e6))
    if device_events:
        return device_events, "tpu"
    return cpu_events, "cpu-coarse"


# -- roofline ---------------------------------------------------------------
def classify(flops, nbytes, peak_flops=None, peak_membw=None):
    """Roofline verdict for one op from its static FLOPs/bytes:
    ``compute-bound`` when the arithmetic intensity (FLOPs/byte) sits at
    or above the machine ridge point ``peak_flops / peak_membw``,
    ``memory-bound`` below it, ``unknown`` when either peak is unset
    (``PADDLE_TPU_PEAK_FLOPS`` / ``PADDLE_TPU_PEAK_MEMBW_BYTES``) or
    the op moved no bytes. Collectives never reach here — they get the
    ``comm-bound`` lane in :func:`attribute`."""
    from paddle_tpu import flags

    if peak_flops is None:
        peak_flops = float(flags.get_flag("peak_flops") or 0)
    if peak_membw is None:
        peak_membw = float(flags.get_flag("peak_membw_bytes") or 0)
    if not nbytes or peak_flops <= 0 or peak_membw <= 0:
        return "unknown"
    ridge = peak_flops / peak_membw
    return ("compute-bound" if (float(flops) / float(nbytes)) >= ridge
            else "memory-bound")


def attribute(trace_dir, sidecar=None, peak_flops=None, peak_membw=None):
    """Join the trace's device events against the provenance sidecar
    (or, absent one, the live registry) into the per-op table::

        {"ops": {tag: {ms, events, op_type, src_ops, flops, bytes,
                       intensity, verdict, frac}},
         "total_ms", "attributed_ms", "unattributed_ms",
         "attributed_frac", "comm_ms", "collective_instances",
         "expected_collective_instances", "fusion_policy", "source",
         "by_type": {op type: ms}, "by_phase": {phase: ms},
         "mixed_phase_ms": {"backward+optimizer": ms, ...}}

    ``by_phase`` splits the attributed time into ``forward``,
    ``backward`` and ``optimizer`` by the op's ``op_role``;
    ``mixed_phase_ms`` is the part of it spent in fusions whose members
    come from more than one phase (each booked whole to one op), by the
    phases they hold.

    Every tag the registry knows appears in ``ops`` even at 0 ms (XLA
    may constant-fold an op away entirely; "every ProgramDesc op in the
    table" still holds). Time on instructions with no tag lands in the
    explicit ``unattributed_ms`` bucket. Collective instructions form
    their own comm lane: their time is attributed (counted in
    ``attributed_frac``) but the verdict is ``comm-bound`` regardless
    of intensity."""
    sc = sidecar or load_sidecar(trace_dir) or registry_snapshot()
    instr_tags = sc.get("instr_tags", {})
    instr_kinds = sc.get("instr_kinds", {})
    costs = sc.get("costs", {})
    mixed = sc.get("mixed_phase", {})
    tag_phase = {t: role_phase(c.get("op_role", 0))
                 for t, c in costs.items()}
    events, source = device_op_events(trace_dir, known=instr_tags)

    ops = {}
    for tag, c in costs.items():
        ops[tag] = {
            "ms": 0.0, "events": 0,
            "op_type": c.get("op_type") or tag_op_type(tag),
            "src_ops": c.get("src_ops", []),
            "flops": c.get("flops", 0), "bytes": c.get("bytes", 0),
        }
    total = attributed = comm_ms = unattributed = 0.0
    by_type, by_phase = defaultdict(float), defaultdict(float)
    mixed_ms = defaultdict(float)
    comm_tags = set()
    seen_collectives = set()
    for name, ms in events:
        total += ms
        tag = instr_tags.get(name)
        if tag is None and "." in name:
            tag = instr_tags.get(name.rsplit(".", 1)[0])
        kind = instr_kinds.get(name, "")
        is_coll = (kind in _COLLECTIVE_OPCODES
                   or any(name.startswith(p) for p in (
                       "all-reduce", "all-gather", "reduce-scatter",
                       "collective-permute", "all-to-all")))
        if is_coll:
            comm_ms += ms
            seen_collectives.add(name.replace("-start", "")
                                 .replace("-done", ""))
        if tag is None:
            if is_coll:
                attributed += ms  # comm lane is its own attribution
            else:
                unattributed += ms
            continue
        attributed += ms
        row = ops.setdefault(tag, {
            "ms": 0.0, "events": 0, "op_type": tag_op_type(tag),
            "src_ops": [tag_op_type(tag)], "flops": 0, "bytes": 0,
        })
        row["ms"] += ms
        row["events"] += 1
        by_type[row["op_type"]] += ms
        by_phase[tag_phase.get(tag, "forward")] += ms
        if name in mixed:
            mixed_ms[mixed[name]] += ms
        if is_coll:
            comm_tags.add(tag)

    for tag, row in ops.items():
        nb = row["bytes"]
        row["intensity"] = (float(row["flops"]) / nb) if nb else 0.0
        if tag in comm_tags:
            row["verdict"] = "comm-bound"
        else:
            row["verdict"] = classify(
                row["flops"], nb, peak_flops, peak_membw)
        row["frac"] = (row["ms"] / total) if total else 0.0

    return {
        "ops": ops,
        "total_ms": total,
        "attributed_ms": attributed,
        "unattributed_ms": unattributed,
        "attributed_frac": (attributed / total) if total else 0.0,
        "comm_ms": comm_ms,
        "collective_instances": len(seen_collectives),
        "expected_collective_instances": int(
            sc.get("collectives", {}).get("instances", 0)),
        "fusion_policy": sc.get("policy", "dominant"),
        "source": source,
        "by_type": dict(by_type),
        "by_phase": dict(by_phase),
        "mixed_phase_ms": dict(mixed_ms),
    }


def gate_issues(table):
    """The ``perf_report --roofline --gate`` predicate: issue strings
    when the table is unusable (empty) or the comm lane disagrees with
    the registered HLO collective schedule (the PR 16
    ``spmd.prediction_delta`` cross-check at op granularity). Empty
    list = gate passes."""
    issues = []
    hot = [t for t, r in table.get("ops", {}).items() if r["ms"] > 0]
    if not hot:
        issues.append("roofline table is empty: no device time "
                      "attributed to any provenance tag")
    expected = table.get("expected_collective_instances", 0)
    seen = table.get("collective_instances", 0)
    if seen and expected and seen != expected:
        issues.append(
            "collective lane disagrees with the registered HLO "
            "schedule: trace saw %d distinct collective instruction(s), "
            "registration recorded %d" % (seen, expected))
    return issues


def top_rows(table, top_k=15):
    """The table's hot rows, worst-first: ``[(tag, row)]`` sorted by
    device ms descending, zero-ms rows last (alphabetical)."""
    items = list(table.get("ops", {}).items())
    items.sort(key=lambda kv: (-kv[1]["ms"], kv[0]))
    return items[:top_k]
