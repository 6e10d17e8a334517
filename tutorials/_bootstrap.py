"""Shared tutorial bring-up. Tutorials run on whatever backend JAX
initialises; ``JAX_PLATFORMS=cpu`` selects the 8-virtual-device CPU mesh
(the device-count flag below only affects the CPU; see docs/testing.md)."""

import os
import sys


def bootstrap(num_devices: int = 8):
    # Repo root on sys.path so tutorials run from anywhere.
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    if os.environ.get("NUM_PROCESSES"):
        # Launched by scripts/launch.py: the launcher already fixed the
        # per-process device count and backend — appending another
        # device-count flag here would double the local device pool.
        import jax
        return jax
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + f" --xla_force_host_platform_device_count={num_devices}")
    import jax
    return jax
