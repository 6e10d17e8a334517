"""Host-side distributed runtime bring-up.

TPU-native analogue of the reference's host runtime
(``python/triton_dist/utils.py:341`` ``initialize_distributed`` /
``:229`` ``init_nvshmem_by_torch_process_group``): instead of a torchrun
process group + NVSHMEM symmetric heap, a JAX program is a single SPMD
computation over a :class:`jax.sharding.Mesh`; multi-host bring-up is
``jax.distributed.initialize`` and the "symmetric heap" is simply sharded
device arrays addressed by remote DMA (see ``triton_dist_tpu.shmem``).
"""

from __future__ import annotations

import contextlib
import functools
import os
import time
import warnings
from typing import Optional

import jax


# ---------------------------------------------------------------------------
# Platform predicates (reference: utils.py:51-112 is_cuda()/is_rocshmem()/...)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def platform() -> str:
    """The platform of the backend JAX initialised ("tpu", "cpu", ...)."""
    return jax.devices()[0].platform


def on_tpu() -> bool:
    return platform() == "tpu"


# ---------------------------------------------------------------------------
# Persistent compile cache. Entry points (chip_smoke.py, bench.py,
# examples/, benchmark/, tools/tune_cli.py) call this before their first
# compile; the library itself never does (importing it sets no config).
# ---------------------------------------------------------------------------

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Keep compiled programs across processes; returns the directory.

    ``JAX_COMPILATION_CACHE_DIR`` wins when set: JAX reads it itself and
    no other directory is set in code. Otherwise the cache lives at the
    fixed ``<checkout>/.jax_cache`` (git-ignored) — never a temporary
    name, so a second process in the same checkout hits what the first
    one compiled. The minimum compile time drops to zero either way:
    the per-layer Mosaic kernels each compile in well under JAX's
    default one-second floor and there are hundreds of them per model.
    """
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(_REPO_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


# ---------------------------------------------------------------------------
# Interpret-mode plumbing.
#
# The reference has no fake/mock comm backend (SURVEY.md §4); we make one
# first-class: every pallas_call in this package routes its ``interpret``
# argument through use_interpret(), so the full kernel battery runs on a
# CPU mesh (XLA_FLAGS=--xla_force_host_platform_device_count=N).
# ---------------------------------------------------------------------------

_INTERPRET_OVERRIDE: Optional[bool] = None


def set_interpret(value: Optional[bool]) -> None:
    """Force interpret mode on/off globally (None = auto: on unless on TPU)."""
    global _INTERPRET_OVERRIDE
    _INTERPRET_OVERRIDE = value


def use_interpret() -> bool:
    if _INTERPRET_OVERRIDE is not None:
        return _INTERPRET_OVERRIDE
    return not on_tpu()


def interpret_arg():
    """Value to pass as ``pl.pallas_call(interpret=...)``.

    Set ``TRITON_DIST_TPU_DETECT_RACES=1`` to run the whole battery
    under the vector-clock race detector — the deliberate signal-
    protocol checker SURVEY.md §5 calls for (the reference only has a
    compute-sanitizer hook).

    Fault-injection hook: an active ``resilience.faults`` plan may
    override the DMA execution mode (``dma_on_wait`` = every transfer
    completes as late as its wait allows — the maximally-adversarial
    arrival schedule the signal protocols must tolerate).
    """
    if use_interpret():
        from jax.experimental.pallas import tpu as pltpu

        from triton_dist_tpu.resilience import faults

        kwargs = {
            "dma_execution_mode": "eager",
            "detect_races": os.environ.get(
                "TRITON_DIST_TPU_DETECT_RACES") == "1",
        }
        kwargs.update(faults.interpret_overrides())
        return pltpu.InterpretParams(**kwargs)
    return False


@contextlib.contextmanager
def interpret_mode(value: bool = True):
    global _INTERPRET_OVERRIDE
    prev = _INTERPRET_OVERRIDE
    _INTERPRET_OVERRIDE = value
    try:
        yield
    finally:
        _INTERPRET_OVERRIDE = prev


# ---------------------------------------------------------------------------
# Bring-up / teardown
# ---------------------------------------------------------------------------

def initialize_distributed(coordinator_address: Optional[str] = None,
                           num_processes: Optional[int] = None,
                           process_id: Optional[int] = None, *,
                           max_attempts: Optional[int] = None,
                           backoff_s: float = 0.5) -> None:
    """Initialize multi-host JAX if the standard env vars are present.

    Single-host (including the CPU-mesh test configuration) needs no
    initialization; multi-host pods read ``COORDINATOR_ADDRESS`` /
    ``NUM_PROCESSES`` / ``PROCESS_ID`` (or the arguments), mirroring the
    torchrun env-var contract in the reference (``utils.py:342-347``).

    Coordinator connect is retried with exponential backoff
    (``max_attempts`` tries, first sleep ``backoff_s`` doubling each
    round; default 3, or ``TRITON_DIST_TPU_INIT_RETRIES``): on a pod,
    workers race the coordinator's bind, and one refused connection
    must not kill a whole slice's bring-up. The last failure is
    re-raised with the attempt count.
    """
    addr = coordinator_address or os.environ.get("COORDINATOR_ADDRESS")
    nproc = num_processes or _int_env("NUM_PROCESSES")
    pid = process_id if process_id is not None else _int_env("PROCESS_ID")
    if not (addr and nproc and nproc > 1):
        return
    if max_attempts is None:
        max_attempts = _int_env("TRITON_DIST_TPU_INIT_RETRIES") or 3
    delay = backoff_s
    for attempt in range(1, max_attempts + 1):
        try:
            jax.distributed.initialize(coordinator_address=addr,
                                       num_processes=nproc,
                                       process_id=pid or 0)
            return
        except Exception as e:  # noqa: BLE001 — filtered below
            # Only transient bring-up races are worth retrying: a
            # ValueError/TypeError (malformed address/config) or a
            # re-init of a live runtime ("already initialized") cannot
            # be fixed by waiting — fail loudly and immediately instead
            # of burying the cause under backoff warnings. Keep the
            # match tight: "address already in use" (coordinator port
            # in TIME_WAIT after a restart) IS the retryable race.
            msg = str(e).lower()
            if (isinstance(e, (ValueError, TypeError))
                    or ("already" in msg and "in use" not in msg)):
                raise
            if attempt == max_attempts:
                raise RuntimeError(
                    f"jax.distributed.initialize failed after "
                    f"{max_attempts} attempts (coordinator {addr}, "
                    f"process {pid or 0}/{nproc})") from e
            warnings.warn(
                f"initialize_distributed attempt {attempt}/"
                f"{max_attempts} failed ({e!r}); retrying in "
                f"{delay:.1f}s", RuntimeWarning, stacklevel=2)
            time.sleep(delay)
            delay *= 2


def finalize_distributed() -> None:
    """Reference: utils.py:302 finalize_distributed.

    Teardown failures are non-fatal but must stay diagnosable: a
    swallowed shutdown error on one host of a pod looks identical to a
    clean exit until the next job inherits a half-dead coordinator.
    """
    try:
        jax.distributed.shutdown()
    except (RuntimeError, ValueError) as e:
        warnings.warn(
            f"jax.distributed.shutdown failed during teardown: {e!r}",
            RuntimeWarning, stacklevel=2)


def _int_env(name: str) -> Optional[int]:
    v = os.environ.get(name)
    return int(v) if v else None


# ---------------------------------------------------------------------------
# Rank-aware printing (reference: utils.py:445 dist_print)
# ---------------------------------------------------------------------------

def dist_print(*args, allowed_ranks=(0,), prefix: bool = True, **kwargs):
    """Print only on the allowed process indices (host-level ranks)."""
    rank = jax.process_index()
    if allowed_ranks == "all" or rank in tuple(allowed_ranks):
        if prefix:
            print(f"[rank {rank}]", *args, **kwargs)
        else:
            print(*args, **kwargs)
