"""Test configuration: run JAX on a virtual 8-device CPU mesh so sharding
paths are exercised without TPU hardware, and so no test ever holds a
chip (one process per chip).

The mechanics live in reporter_tpu.utils.runtime.force_virtual_cpu — the
same helper every CLI front door uses under REPORTER_TPU_PLATFORM=cpu —
so pytest and the shell harnesses share one copy of the logic.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from reporter_tpu.utils.runtime import force_virtual_cpu  # noqa: E402

force_virtual_cpu(8)
# child processes spawned by tests (pipeline stages, multihost workers)
# inherit the decision. Unconditional: a pre-set =tpu in the operator's
# shell must not leak into test children
os.environ["REPORTER_TPU_PLATFORM"] = "cpu"
os.environ["REPORTER_TPU_VIRTUAL_DEVICES"] = "8"

import jax  # noqa: E402
import pytest  # noqa: E402

# fail loudly if the force-to-CPU mechanism ever stops working; tests must
# never contend for a chip
assert jax.default_backend() == "cpu", (
    "tests must run on the CPU backend, got " + jax.default_backend())


def pytest_configure(config):
    """Build the native library once, before any test process loads it.
    Test processes that each find it missing or stale run ``make`` at
    once; one that loads the library while another relinks it reads a
    partial file ("file too short"), its own ``make`` then sees that
    file as new and builds nothing, and the process keeps the numpy
    fallback for every later test: native tests skip, and those that
    need native prep fail. An xdist worker has ``workerinput``; the
    controller configures before it starts any worker."""
    if not hasattr(config, "workerinput"):
        from reporter_tpu import native
        native.available()


@pytest.fixture(autouse=True)
def _racecheck_gate():
    """The witness-armed CI leg (REPORTER_TPU_LOCKCHECK=1): any RC
    finding the runtime lock witness / guarded-state audit records
    fails the test that surfaced it — zero findings is the contract,
    same as the static suite's empty baseline. Disarmed runs pay one
    flag check per test. Findings are reset after reporting so one
    race does not cascade into every later test."""
    yield
    from reporter_tpu.utils import locks
    if not locks.armed():
        return
    from reporter_tpu.analysis import racecheck
    lines = racecheck.render()
    if lines:
        racecheck.reset()
        pytest.fail("runtime concurrency findings:\n"
                    + "\n".join(lines), pytrace=False)
