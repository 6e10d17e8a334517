"""Test bring-up: force an 8-device CPU mesh.

Must run before any JAX backend initializes: pytest always runs on the
CPU backend with 8 host devices, whatever accelerator the machine has,
so the whole distributed battery runs on one machine — the single-host
simulated-multi-rank harness the reference only has for Ascend
(``test/ascend/conftest.py:31-44`` run_dist_test).
"""

import faulthandler
import os

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402
from jax.sharding import Mesh  # noqa: E402

from triton_dist_tpu.parallel.mesh import MeshContext  # noqa: E402


NUM_DEVICES = 8
_STDERR_FD = 2


# Seconds one test may take, fixtures and teardown included: about three
# times the slowest test of the suite on the slower of the two machines
# that run it (docs/testing.md).
TEST_LIMIT_S = 180.0


@pytest.hookimpl(wrapper=True)
def pytest_runtest_protocol(item):
    """Every test has a limit of its own.

    The Pallas interpreter's deadlocks (docs/testing.md) block the main
    thread in native code, where no signal handler runs. ``faulthandler``
    watches from a thread of its own: at the limit it writes every
    thread's stack to stderr and exits the process. Under xdist that takes
    down the one worker, which is reported as the failure of the test it
    was running (``worker 'gwN' crashed while running '<node id>'``), and
    a new worker takes the rest. Without workers the run ends there.
    Tests marked ``slow`` are long by design and run unwatched."""
    if item.get_closest_marker("slow"):
        return (yield)
    faulthandler.dump_traceback_later(TEST_LIMIT_S, exit=True,
                                      file=_STDERR_FD)
    try:
        return (yield)
    finally:
        faulthandler.cancel_dump_traceback_later()


def pytest_configure(config):
    # Capturing is suspended here, so this is the terminal's stderr and
    # not the file a test's output is captured into.
    global _STDERR_FD
    _STDERR_FD = os.dup(2)
    config.addinivalue_line(
        "markers",
        "slow: long-running fault plans (subprocess deadlock harness); "
        "deselected from the tier-1 battery via -m 'not slow'")


@pytest.fixture(scope="session")
def tp8_mesh():
    """1D mesh: all 8 devices on the ``tp`` axis."""
    devices = jax.devices()
    assert len(devices) >= NUM_DEVICES, (
        f"need {NUM_DEVICES} devices, got {len(devices)} — conftest env "
        "setup ran too late?")
    return Mesh(np.array(devices[:NUM_DEVICES]), ("tp",))


@pytest.fixture(scope="session")
def tp8_ctx(tp8_mesh):
    return MeshContext.from_mesh(tp8_mesh)


@pytest.fixture(scope="session")
def tp4_mesh():
    """1D mesh of four devices: the model-level parity tests. What they
    prove is how the layers compose; the 8-rank ring of each fused op is
    held by the op tests on ``tp8_mesh``."""
    return Mesh(np.array(jax.devices()[:4]), ("tp",))


@pytest.fixture(scope="session")
def tp4_ctx(tp4_mesh):
    return MeshContext.from_mesh(tp4_mesh)


@pytest.fixture(scope="session")
def dp2tp4_mesh():
    """2D mesh: 2 × 4 (dp × tp) — exercises logical-id linearization."""
    devices = jax.devices()[:NUM_DEVICES]
    return Mesh(np.array(devices).reshape(2, 4), ("dp", "tp"))


@pytest.fixture(scope="session")
def dp2tp4_ctx(dp2tp4_mesh):
    return MeshContext.from_mesh(dp2tp4_mesh)
