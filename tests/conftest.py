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


# The tier-1 command runs six xdist workers under a time limit, and its
# wall time was set by which worker drew a minutes-long test last: each of
# these holds one core for 90 to 300 s while the other workers idle, and
# the run ended within a minute of its limit (PR 26 read them off
# ``--durations`` under six workers). Longest first.
_LONG_TESTS = (
    "test_qwen3_next_hf.py::test_hybrid_checkpoint_engine_serve",
    "test_qwen_next.py::test_moe_ffn_forward_fused_matches_xla",
    "test_qwen_next.py::test_decode_fused_matches_xla",
    "test_resilience.py::test_signal_faults_ag_gemm_terminate[dropped_signal]",
    "test_chaos.py::test_soak_megakernel_with_restore",
    "test_qwen_moe.py::test_moe_model_fused_vs_xla",
    "test_megakernel.py::test_megakernel_dynamic_token_exact_all_families",
    "test_chaos.py::test_soak_megakernel_quantized",
    "test_qwen_next.py::test_forward_fused_matches_xla",
    "test_mk_chunked_prefill.py::"
    "test_mk_chunked_token_exact_bucket_edges_vs_lane_and_layer",
    "test_e2e_dense.py::test_decode_fused_matches_xla",
    "test_kv_quant.py::test_megakernel_quant_decode_token_agreement[fp8-0.5]",
    "test_paged_qblock.py::test_no_recompile_gates_with_flash",
    "test_spec_decode.py::test_megakernel_spec_token_exact_vs_nonspec",
)


def pytest_collection_modifyitems(config, items):
    """Under xdist, start the minutes-long tests first, one to a worker.

    ``--dist load`` hands every worker a contiguous chunk of the collection
    first (a quarter of the tests over the workers) and deals the rest out
    as workers come free, so a long test late in the alphabet starts late
    and the run waits for it alone. The long tests move to the front, a
    chunk apart. Every worker computes the same order; a run without
    workers keeps the collection's own."""
    workers = getattr(config, "workerinput", {}).get("workercount")
    if not workers:
        return
    rank = {}
    for it in items:
        for k, name in enumerate(_LONG_TESTS):
            if it.nodeid.endswith(name):
                rank[it] = k
    long = sorted(rank, key=rank.get)
    rest = [it for it in items if it not in rank]
    gap = max(len(items) // (4 * workers), 2) - 1
    order = []
    for k, it in enumerate(long):
        order += [it] + rest[k * gap:(k + 1) * gap]
    items[:] = order + rest[len(long) * gap:]


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
