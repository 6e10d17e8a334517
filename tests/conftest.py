"""Test bring-up: force an 8-device CPU mesh.

Must run before any JAX backend initializes: pytest always runs on the
CPU backend with 8 host devices, whatever accelerator the machine has,
so the whole distributed battery runs on one machine — the single-host
simulated-multi-rank harness the reference only has for Ascend
(``test/ascend/conftest.py:31-44`` run_dist_test).
"""

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


def pytest_configure(config):
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
def dp2tp4_mesh():
    """2D mesh: 2 × 4 (dp × tp) — exercises logical-id linearization."""
    devices = jax.devices()[:NUM_DEVICES]
    return Mesh(np.array(devices).reshape(2, 4), ("dp", "tp"))


@pytest.fixture(scope="session")
def dp2tp4_ctx(dp2tp4_mesh):
    return MeshContext.from_mesh(dp2tp4_mesh)
