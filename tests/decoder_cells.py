"""What the decoder configurations' tests share: a cell's configuration at
its rehearsal sizes, its program driven through its first three steps by
the benchmark's own ``train.Trainer`` (the counters and gauges it set on
the way), and the float32 program's comparison with its reference.
Not a test module: the configurations' test files import it."""

import json
import os

import jax

import paddle_tpu.fluid as fluid

from benchmarks import compare
from benchmarks.drivers import train

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def cell(name, amp="bf16"):
    """-> (workload, configuration, configuration at the rehearsal sizes,
    rows) of the benchmark's cell ``name``; ``amp`` None: float32."""
    with open(os.path.join(ROOT, "benchmarks", "workloads",
                           name + ".json")) as f:
        workload = json.load(f)
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           workload["config"] + ".json")) as f:
        config = json.load(f)
    config = dict(config, program=dict(config["program"], amp=amp))
    cfg, rows = train.sized(config, workload, rehearse=True)
    return workload, config, cfg, rows


def drive(name, seed, counters=(), gauges=(), amp="bf16"):
    """The cell's program at the rehearsal size, built and driven through
    its first three steps from ``seed`` once, with the metrics flag up ->
    dict of the cell's parts, ``trainer``, its ``program`` readings, what
    each of ``counters`` ``counted`` meanwhile and the ``gauges`` as they
    stand after the steps. The caller closes ``trainer.exe``."""
    from paddle_tpu import observability as obs

    workload, config, cfg, rows = cell(name, amp)
    obs.set_enabled(True)
    before = {n: obs.counter_value(n) for n in counters}
    with fluid.unique_name.guard():
        trainer = train.Trainer(cfg, rows, workload, rehearse=True)
    trainer.start(seed)
    program, _ = trainer.warm_up()
    jax.effects_barrier()
    snapshot = obs.snapshot()["gauges"]
    return dict(workload=workload, config=config, cfg=cfg, rows=rows,
                trainer=trainer, program=program,
                counted={n: obs.counter_value(n) - was
                         for n, was in before.items()},
                gauges={n: snapshot.get(n) for n in gauges})


def float32_compared(name, reference, seed):
    """The cell's program without ``enable_bf16`` against ``reference``,
    what ``common.follow`` gave for the float32 reference from ``seed`` at
    the rehearsal size, through three steps -> every number of the
    comparison (no limits)."""
    driven = drive(name, seed, amp=None)
    driven["trainer"].exe.close()
    return compare.judge(driven["program"], reference, {})[1]
