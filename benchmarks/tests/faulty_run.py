#!/usr/bin/env python3
"""A run of the harness with the timed path broken underneath, for
``test_faults.py``: everything but the look for a chip is driven as in a
real run (``--rehearse`` sizes on the CPU), and ``correct`` has to come
out false.

    python3 benchmarks/tests/faulty_run.py <fault> --workload <cell> --seed 5 --seconds 1 --trace 0

``unchanged``  every step returns its state unchanged (the loss is still
               computed): the scope gets its old values back
``half``       half of the batch is left out, the mean taken over the rest
``none``       no fault: the run has to come out correct
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
os.environ["JAX_PLATFORMS"] = "cpu"

from benchmarks import run  # noqa: E402
from benchmarks.drivers import train  # noqa: E402


def plant(fault):
    if fault == "unchanged":
        real = train.Trainer.step

        def step(self, i):
            import jax.numpy as jnp

            held = {n: jnp.copy(v) for n, v in self.scope._vars.items()
                    if v is not None}
            loss = real(self, i)
            for n, v in held.items():
                self.scope.set(n, v)
            return loss

        train.Trainer.step = step
    elif fault == "half":
        real = train.Trainer.start

        def start(self, seed):
            real(self, seed)
            self.pool = [{k: v[:self.rows // 2] for k, v in batch.items()}
                         for batch in self.pool]

        train.Trainer.start = start
    elif fault != "none":
        raise SystemExit("unknown fault %r" % fault)


if __name__ == "__main__":
    plant(sys.argv[1])
    sys.exit(run.main(sys.argv[2:] + ["--rehearse"]))
