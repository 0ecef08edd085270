"""Test harness config: force an 8-device virtual CPU mesh.

Multi-chip sharding is tested on virtual CPU devices (SURVEY §4: the
reference emulates multi-node as multi-process localhost; our analogue is a
host-platform device mesh).  Must run before the first jax backend
initialization: jax.config.update('jax_platforms') holds whatever the
environment says, so tests never take the chip.
"""
import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
).strip()
# CI is strict: a dryrun leg failure fails the test run (the driver gate
# stays non-strict so extra legs can't redden a green primary leg)
os.environ.setdefault("PTN_DRYRUN_STRICT", "1")

import jax

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


def pytest_configure(config):
    # compile-rail tests run by default (they ARE the CPU perf gate) but
    # are deselectable for quick local iteration: -m "not perf"
    config.addinivalue_line(
        "markers", "perf: perf-rail measurement (deselect with -m 'not perf')")
    # multi-process soak tests (subprocess fleets under chaos/SIGKILL)
    # cost tens of seconds each on one core; tier-1 runs -m 'not slow'
    # and keeps the cheap inproc siblings of every one of them
    config.addinivalue_line(
        "markers", "slow: heavyweight soak (deselected by tier-1)")


@pytest.fixture(autouse=True)
def _reset_framework_state():
    yield
    # isolate static-graph default programs between tests
    from paddle_tpu.static import program as prog_mod

    prog_mod._main_program = prog_mod.Program()
    prog_mod._startup_program = prog_mod.Program()
    from paddle_tpu.static.executor import _global_scope

    _global_scope._vars.clear()
