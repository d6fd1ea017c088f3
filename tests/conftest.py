"""Test harness configuration: force the JAX CPU backend with 8 virtual
devices so multi-chip SPMD paths are exercised without TPU hardware — the
equivalent of the reference's multi-process-on-localhost cluster simulation
(reference: tests/unittests/test_dist_base.py), per SURVEY.md §4."""

import os

# Tests always run on the 8-virtual-device CPU mesh, whatever the
# environment says: the chip is reached only through the chip tool
# (chip_smoke.py), one process at a time, and a test worker must never
# take it. tests/test_tpu_compile.py compiles FOR a described TPU from
# this same CPU process; nothing in the suite runs on one.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# jax may already be imported by a pytest plugin, in which case it read
# JAX_PLATFORMS before the line above; set the config directly too.
import jax

jax.config.update("jax_platforms", "cpu")


import pytest


@pytest.fixture(autouse=True)
def _reset_observability():
    """Global telemetry state (metrics registry + span tracer) never
    leaks across tests: reset before AND after every test, and restore
    the flag-derived gate in case a test forced it."""
    from paddle_tpu import observability

    observability.reset()
    observability.set_enabled(None)
    yield
    observability.reset()
    observability.set_enabled(None)


def pytest_addoption(parser):
    parser.addoption(
        "--verify-programs", action="store_true", default=False,
        help="run the static program verifier (paddle_tpu.analysis) on "
             "every program the suite compiles (sets PADDLE_TPU_VERIFY=1 "
             "and, unless PADDLE_TPU_OPT_LEVEL is already set, opt level 2 "
             "so the verifier sees the post-transform descs; "
             "ERROR-severity findings fail the test)")


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running variant excluded from the tier-1 run "
        "(-m 'not slow')")
    config.addinivalue_line(
        "markers",
        "multichip: needs the 8-virtual-device CPU mesh (the conftest "
        "provisions it; auto-skips where it could not)")
    if config.getoption("--verify-programs"):
        os.environ["PADDLE_TPU_VERIFY"] = "1"
        # The engine verifies the desc it actually compiles — the
        # post-transform clone — so running the suite at level 2
        # re-verifies every transformed program suite-wide.
        os.environ.setdefault("PADDLE_TPU_OPT_LEVEL", "2")
