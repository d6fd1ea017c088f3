"""Op-level device profiling (observability/opprof.py): lowering
provenance scope tags, HLO op_metadata parsing with the dominant-fusion
policy, xplane -> framework-op attribution on a real profiled MLP run,
roofline classification, fused-op source lists at opt 2, the gate
predicate, and the bit-exactness guarantee — named_scope is metadata-only, so the
instrumented lowering emits the same computation as the plain one.
"""

import os

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu import flags, observability as obs
from paddle_tpu.core.registry import OpRegistry
from paddle_tpu.framework import Program, program_guard
from paddle_tpu.observability import opprof


def _build_mlp():
    img = fluid.layers.data(name="img", shape=[784], dtype="float32")
    label = fluid.layers.data(name="label", shape=[1], dtype="int64")
    h = fluid.layers.fc(input=img, size=64, act="relu")
    pred = fluid.layers.fc(input=h, size=10, act="softmax")
    loss = fluid.layers.mean(
        fluid.layers.cross_entropy(input=pred, label=label))
    return loss


def _mlp_feed(batch=64, seed=0):
    rng = np.random.RandomState(seed)
    return {"img": rng.randn(batch, 784).astype(np.float32),
            "label": rng.randint(0, 10, size=(batch, 1)).astype(np.int64)}


# -- scope tags ----------------------------------------------------------

def test_every_registered_op_tag_round_trips():
    """The tier-1 provenance lint: every registered op lowering's scope
    tag survives the full jit path join (tools/lint_program.py
    --provenance runs the same check plus a live compile)."""
    types = OpRegistry.all_types()
    assert len(types) > 200
    for t in types:
        tag = opprof.provenance_tag(t, 0, 7)
        path = "jit(run)/transpose(jvp(run))/%s/dot_general" % tag
        assert opprof.parse_tag(path) == tag, t
        assert opprof.tag_op_type(tag) == t


def test_parse_tag_misses_return_none():
    assert opprof.parse_tag("jit(run)/dot_general") is None
    assert opprof.parse_tag("") is None
    # malformed block/op indices never match
    assert opprof.parse_tag("jit(f)/pt.mul.x_y/dot") is None


def test_hlo_op_map_dominant_fusion_policy():
    """A fusion instruction is charged to its ROOT's op_name tag; a
    metadata-less instruction inherits the dominant tag of the
    computation it calls."""
    hlo = """\
HloModule jit_run

%fused_add (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8] parameter(0)
  ROOT %add.1 = f32[8] add(%param_0, %param_0), metadata={op_name="jit(run)/pt.elementwise_add.0_1/add"}
}

%region_max (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %max.9 = f32[] maximum(%a, %b), metadata={op_name="jit(run)/pt.pool2d.0_2/reduce_window_max"}
}

ENTRY %main (p0: f32[8]) -> f32[8] {
  %p0 = f32[8] parameter(0)
  %multiply.2 = f32[8] multiply(%p0, %p0), metadata={op_name="jit(run)/pt.mul.0_0/mul"}
  %rw.3 = f32[8] reduce-window(%multiply.2, %p0), to_apply=%region_max
  ROOT %fusion = f32[8] fusion(%rw.3), kind=kLoop, calls=%fused_add
}
"""
    tags, kinds = opprof.hlo_op_map(hlo)
    assert tags["multiply.2"] == "pt.mul.0_0"
    assert kinds["multiply.2"] == "multiply"
    # fusion with no own metadata inherits its called computation's
    # dominant tag (the ROOT add carries it)
    assert tags["fusion"] == "pt.elementwise_add.0_1"
    # reduce-window has no metadata; its to_apply region resolves it
    assert tags["rw.3"] == "pt.pool2d.0_2"


# -- roofline classifier -------------------------------------------------

def test_classify_roofline_verdicts():
    # ridge = 100 GFLOP/s over 10 GB/s = 10 FLOP/byte
    peak_flops, peak_membw = 100e9, 10e9
    assert opprof.classify(1000, 10, peak_flops, peak_membw) \
        == "compute-bound"
    assert opprof.classify(10, 1000, peak_flops, peak_membw) \
        == "memory-bound"
    # exactly at the ridge counts as compute-bound
    assert opprof.classify(100, 10, peak_flops, peak_membw) \
        == "compute-bound"
    # no bytes moved, or peaks unset -> unknown
    assert opprof.classify(1000, 0, peak_flops, peak_membw) == "unknown"
    assert opprof.classify(1000, 10, 0, peak_membw) == "unknown"
    assert opprof.classify(1000, 10, peak_flops, 0) == "unknown"


def test_classify_reads_peak_flags():
    flags.set_flags({"peak_flops": 100e9, "peak_membw_bytes": 10e9})
    try:
        assert opprof.classify(1000, 10) == "compute-bound"
        assert opprof.classify(10, 1000) == "memory-bound"
    finally:
        flags.reset_flag("peak_flops")
        flags.reset_flag("peak_membw_bytes")
    # defaults (both 0) -> unknown
    assert opprof.classify(1000, 10) == "unknown"


def test_gate_issues():
    empty = {"ops": {}, "collective_instances": 0,
             "expected_collective_instances": 0}
    issues = opprof.gate_issues(empty)
    assert issues and "empty" in issues[0]
    good = {"ops": {"pt.mul.0_0": {"ms": 1.0}},
            "collective_instances": 2,
            "expected_collective_instances": 2}
    assert opprof.gate_issues(good) == []
    bad_comm = {"ops": {"pt.mul.0_0": {"ms": 1.0}},
                "collective_instances": 3,
                "expected_collective_instances": 2}
    issues = opprof.gate_issues(bad_comm)
    assert issues and "collective" in issues[0]


# -- fused-op source lists ----------------------------------------------

def test_fused_op_source_list_at_opt2():
    """The opt-2 transform pipeline stamps ``__src_ops__`` on ops it
    fuses/rewrites, so attribution can say what a fused op stands for.
    Forward-only program: the add+act fusion self-blocks on training
    graphs (the act grad reads the intermediate sum)."""
    from paddle_tpu.analysis.transforms import optimize_program

    main, startup = Program(), Program()
    with program_guard(main, startup):
        loss = _build_mlp()
    desc, _report = optimize_program(
        main, level=2, feed_names=["img", "label"],
        fetch_names=[loss.name])
    srcs = [op.attrs.get("__src_ops__")
            for op in desc.block(0).ops if "__src_ops__" in op.attrs]
    assert srcs, "opt-2 pipeline fused nothing on the MLP"
    # the fc(act=relu) add+relu pair fuses with its sources recorded
    assert ["elementwise_add", "relu"] in [list(s) for s in srcs]
    # __src_ops__ is bookkeeping only: clean_attrs hides it from
    # lowerings, so no lowering ever sees the dunder attr
    from paddle_tpu.engine.lowering import clean_attrs

    for op in desc.block(0).ops:
        assert "__src_ops__" not in clean_attrs(op.attrs)


# -- bit-exactness -------------------------------------------------------

@pytest.mark.parametrize("opt_level", [0, 2])
def test_instrumentation_is_bit_exact(opt_level):
    """named_scope only decorates op_metadata: the instrumented lowering
    (opprof on) fetches bit-identical losses to the plain one."""
    main, startup = Program(), Program()
    with program_guard(main, startup):
        loss = _build_mlp()
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    flags.set_flags({"opt_level": opt_level})
    try:
        runs = []
        for opprof_on in (False, True):
            flags.set_flags({"opprof": opprof_on})
            exe = fluid.Executor()
            scope = fluid.Scope()
            with fluid.scope_guard(scope):
                exe.run(startup)
                losses = [
                    exe.run(main, feed=_mlp_feed(seed=step),
                            fetch_list=[loss.name])[0]
                    for step in range(3)]
            runs.append(np.asarray(losses))
        assert np.array_equal(runs[0], runs[1]), \
            "opprof instrumentation changed the computed losses"
    finally:
        flags.reset_flag("opt_level")
        flags.reset_flag("opprof")


def test_live_spans_leave_the_lowered_step_as_it_is():
    """The ``op:<type>`` spans round the lowerings (live on a first call,
    under the flag or in a profiler session) are host-side only: the step
    lowers to the same module, locations and all, with them and without."""
    import jax

    main, startup = Program(), Program()
    with program_guard(main, startup):
        loss = _build_mlp()
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    exe, scope = fluid.Executor(), fluid.Scope()
    feed = _mlp_feed()
    with fluid.scope_guard(scope):
        exe.run(startup)
        exe.run(main, feed=feed, fetch_list=[loss.name])
    compiled = list(exe.engine._cache.values())[-1]
    args = ([feed[n] for n in compiled.block_program.feed_names],
            [scope.get(n) for n in compiled.mutated_names],
            [scope.get(n) for n in compiled.readonly_names],
            (np.uint32(0), np.uint32(1)))

    def lowered():  # a function of its own each time: JAX traces it anew
        step = compiled.jitted.__wrapped__
        return jax.jit(lambda *a: step(*a)).lower(*args).as_text(
            debug_info=True)

    obs.reset()
    texts, op_spans = [], []
    for seam in (obs.NULL_BLOCK, obs.seam_span("compile")):
        with seam:  # one call site: the locations hold the caller's line
            texts.append(lowered())
        op_spans.append(
            sum(s.name.startswith("op:") for s in obs.spans()))
    assert op_spans == [0, len(compiled.block_program.ops)]
    assert "pt.mul." in texts[0] and texts[0] == texts[1]


# -- end-to-end attribution on a real profiled run ----------------------

def test_profiled_mlp_attribution(tmp_path):
    """The acceptance path: train the MLP under jax.profiler with
    opprof on, then attribute the xplane device time back to provenance
    tags — >= 95% of device time attributed, every live ProgramDesc op
    in the table, and stop_profiler's opprof.* gauges populated."""
    from paddle_tpu import profiler

    trace_dir = str(tmp_path / "trace")
    flags.set_flags({"opprof": True, "trace_dir": trace_dir})
    opprof.reset()
    try:
        main, startup = Program(), Program()
        with program_guard(main, startup):
            loss = _build_mlp()
            fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
        exe = fluid.Executor()
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe.run(startup)
            # warmup compile outside the trace window
            exe.run(main, feed=_mlp_feed(), fetch_list=[loss.name])
            profiler.start_profiler()
            for step in range(3):
                exe.run(main, feed=_mlp_feed(seed=step),
                        fetch_list=[loss.name])
            profiler.stop_profiler(
                profile_path=str(tmp_path / "profile"))

        snap = opprof.registry_snapshot()
        assert snap["instr_tags"], "no pt.* tag reached the HLO metadata"
        assert snap["costs"], "no cost rows registered"
        # the sidecar landed next to the xplane dumps for offline tools
        assert opprof.load_sidecar(trace_dir) is not None

        try:
            table = opprof.attribute(trace_dir)
        except FileNotFoundError:
            pytest.skip("profiler wrote no xplane dump on this backend")
        if table["total_ms"] <= 0:
            pytest.skip("xplane dump carried no device/XLA events")

        # >= 95% of device time attributed to provenance tags
        assert table["attributed_frac"] >= 0.95, table["attributed_frac"]
        # every registered cost tag (== every live ProgramDesc op of
        # every compiled executable) appears, 0-ms rows included
        for tag in snap["costs"]:
            assert tag in table["ops"], tag
        # the hot rows are real framework ops with parseable tags
        hot = [t for t, r in opprof.top_rows(table, 5) if r["ms"] > 0]
        assert hot
        known_types = set(OpRegistry.all_types())
        for tag in hot:
            t = opprof.tag_op_type(tag)
            # *_grad ops lower through the generic vjp path and are not
            # separately registered — their forward type must be
            base = t[:-len("_grad")] if t.endswith("_grad") else t
            assert base in known_types, tag
        # no mesh, no collectives: the comm lane stays empty and the
        # gate passes
        assert table["comm_ms"] == 0.0
        assert opprof.gate_issues(table) == []

        # stop_profiler surfaced the table as opprof.* gauges
        gauges = obs.snapshot()["gauges"]
        assert gauges.get("opprof.attributed_frac") == pytest.approx(
            table["attributed_frac"], abs=0.05)
        assert any(k.startswith("opprof.pt.") and k.endswith("_ms")
                   for k in gauges)
        # ... and appended the op table to the written profile summary
        text = (tmp_path / "profile").read_text()
        assert "Device time by framework op" in text
    finally:
        flags.reset_flag("opprof")
        flags.reset_flag("trace_dir")
        opprof.reset()


def _write_synthetic_xplane(path, events):
    """A device plane with an ``XLA Ops`` line of ``events`` ((name,
    milliseconds), back to back), written as an ``.xplane.pb`` with
    nothing but JAX: ``ProfileData`` turns the text form of the proto
    into its bytes."""
    from jax.profiler import ProfileData

    meta, rows, at = [], [], 0
    for i, (name, ms) in enumerate(events, start=1):
        ps = int(ms * 1e9)
        meta.append('event_metadata { key: %d value { id: %d name: "%s" } }'
                    % (i, i, name))
        rows.append("events { metadata_id: %d offset_ps: %d "
                    "duration_ps: %d }" % (i, at, ps))
        at += ps
    text = ('planes { name: "/device:TPU:0 (synthetic)" '
            'lines { name: "XLA Ops" timestamp_ns: 1000 %s } %s }'
            % (" ".join(rows), " ".join(meta)))
    with open(path, "wb") as f:
        f.write(ProfileData.text_proto_to_serialized_xspace(text))


# the trace names an event by its instruction's whole text on the chip,
# and by the instruction's name alone elsewhere: both must join
@pytest.mark.parametrize("multiply_event", [
    "%multiply.1",
    "%multiply.1 = f32[8]{0} multiply(f32[8]{0} %p0, f32[8]{0} %p0)"])
def test_attribute_joins_synthetic_xplane_against_sidecar(tmp_path,
                                                          multiply_event):
    """Offline attribution: a hand-built device plane + sidecar joins
    deterministically (perf_report --roofline runs out-of-process, no
    live registry) — tagged time lands on its op, untagged time in the
    explicit unattributed bucket, and fused-away ops seed 0-ms rows.
    The plane is read through ``jax.profiler.ProfileData``."""
    _write_synthetic_xplane(str(tmp_path / "host.xplane.pb"),
                            [(multiply_event, 3.0), ("%copy.7", 1.0)])

    sidecar = {
        "policy": "dominant",
        "instr_tags": {"multiply.1": "pt.mul.0_0"},
        "instr_kinds": {"multiply.1": "multiply"},
        "costs": {"pt.mul.0_0": {"op_type": "mul", "flops": 100,
                                 "bytes": 10, "src_ops": None},
                  "pt.relu.0_1": {"op_type": "relu", "flops": 1,
                                  "bytes": 1, "src_ops": None}},
        "collectives": {"hlo_psums": 0, "hlo_bytes": 0, "instances": 0},
    }
    table = opprof.attribute(str(tmp_path), sidecar=sidecar,
                             peak_flops=100e9, peak_membw=10e9)
    assert table["source"] == "tpu"
    # every known cost tag appears, the never-executed one at 0 ms
    assert set(table["ops"]) == {"pt.mul.0_0", "pt.relu.0_1"}
    assert table["ops"]["pt.mul.0_0"]["ms"] == pytest.approx(3.0)
    assert table["ops"]["pt.mul.0_0"]["verdict"] == "compute-bound"
    assert table["ops"]["pt.relu.0_1"]["ms"] == 0.0
    # the untagged copy lands in the unattributed bucket, not on an op
    assert table["total_ms"] == pytest.approx(4.0)
    assert table["unattributed_ms"] == pytest.approx(1.0)
    assert table["attributed_frac"] == pytest.approx(0.75)
    assert table["by_type"] == {"mul": pytest.approx(3.0)}
    assert table["by_phase"] == {"forward": pytest.approx(3.0)}


def test_synthetic_xplane_splits_device_time_by_phase(tmp_path):
    """Device time by op type and by phase from the op_role the registry
    keeps for each tag; a fusion whose members come from two phases is
    counted under ``mixed_phase_ms``; twin planes are read once."""
    from paddle_tpu.framework import OpRole

    events = [("%dot.1", 4.0), ("%dot.2", 6.0), ("%fusion.3", 2.0),
              ("%copy.7", 1.0)]
    _write_synthetic_xplane(str(tmp_path / "a.xplane.pb"), events)
    _write_synthetic_xplane(str(tmp_path / "b.xplane.pb"), events)
    rows, total = opprof.top_ops(str(tmp_path))
    assert total == pytest.approx(13.0)  # not 26: the twin is skipped
    assert rows[0] == ("%dot.2", pytest.approx(6.0))

    def cost(op_type, role):
        return {"op_type": op_type, "op_role": int(role), "flops": 0,
                "bytes": 0, "src_ops": [op_type]}

    sidecar = {
        "instr_tags": {"dot.1": "pt.mul.0_0", "dot.2": "pt.mul_grad.0_5",
                       "fusion.3": "pt.adam.0_9", "copy.7": None},
        "instr_kinds": {},
        "costs": {"pt.mul.0_0": cost("mul", OpRole.Forward),
                  "pt.mul_grad.0_5": cost("mul_grad", OpRole.Backward),
                  "pt.adam.0_9": cost("adam", OpRole.Optimize)},
        "mixed_phase": {"fusion.3": "backward+optimizer"},
    }
    table = opprof.attribute(str(tmp_path), sidecar=sidecar)
    assert table["by_type"] == {"mul": pytest.approx(4.0),
                                "mul_grad": pytest.approx(6.0),
                                "adam": pytest.approx(2.0)}
    assert table["by_phase"] == {"forward": pytest.approx(4.0),
                                 "backward": pytest.approx(6.0),
                                 "optimizer": pytest.approx(2.0)}
    assert table["mixed_phase_ms"] == {
        "backward+optimizer": pytest.approx(2.0)}
    assert table["unattributed_ms"] == pytest.approx(1.0)
    from paddle_tpu import profiler

    text = profiler.op_summary_text(table)
    assert "Device time by op type" in text and "mul_grad" in text
    assert "Device time by phase" in text and "optimizer" in text
