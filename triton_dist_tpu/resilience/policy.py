"""Graceful degradation: per-op fallback onto the plain-XLA path.

Every fused op in this package has a semantically-equivalent XLA
collective form (the ``mode="xla"`` oracles). This module decides —
per op, automatically, logged once — when to take it:

- a fused dispatch raised at runtime (recorded via
  :func:`note_failure`; subsequent calls re-route);
- the operator forced it (``TRITON_DIST_TPU_FORCE_XLA="ag_gemm,p2p"``
  or ``"*"``);
- a startup :func:`health_probe` failed.

The fused ops consult :func:`should_fallback` at dispatch — ``ag_gemm``,
``gemm_rs``, ``all_to_all``, ``p2p``, ``broadcast``, ``ulysses_fused``,
and ``sp_ag_attention`` each route to their XLA oracle when it answers
True. ``ep_dispatch``/``ep_combine`` inherit the policy through the
``all_to_all`` transport they ride on (their drop-free mode is already
pure ``lax.ragged_all_to_all``), and ``flash_decode`` is pure XLA to
begin with, so neither consults the policy under its own name. The
model :class:`~triton_dist_tpu.models.engine.Engine` additionally wraps
whole prefill/decode dispatches (``fallback="xla"``) so a mid-flight
kernel failure degrades the serving path instead of killing it.
"""

from __future__ import annotations

import dataclasses
import logging
import os
import random
import threading
import time
from typing import Callable, Dict, Optional, Tuple

logger = logging.getLogger("triton_dist_tpu.resilience")

__all__ = ["FallbackPolicy", "RetryPolicy", "should_fallback",
           "note_failure", "health_probe", "reset"]


@dataclasses.dataclass(frozen=True)
class RetryPolicy:
    """Deterministic retry-with-exponential-backoff for transient
    comm/op failures — the layer BETWEEN the watchdog (which detects a
    wedge) and the fail-one-request containment (which gives up).

    A retried op must be IDEMPOTENT at the caller: the serving paths
    that consume this (page migration, chunked prefill, the bench
    backend probe) all are — staging pages, two-phase prefix
    publication, and position-keyed append accounting make a replay
    write the same bytes to the same places.

    ``max_attempts`` counts total tries (1 = no retry). Delay before
    retry ``i`` (1-based) is ``base_delay_s * multiplier**(i-1)``,
    capped at ``max_delay_s``, plus a seeded jitter fraction in
    ``[0, jitter]`` — jitter is drawn from ``random.Random(seed)`` per
    :meth:`call`, so two runs with one seed sleep identically (the
    chaos harness and the tests replay schedules bit-for-bit).
    """

    max_attempts: int = 3
    base_delay_s: float = 0.0
    multiplier: float = 2.0
    max_delay_s: float = 30.0
    jitter: float = 0.0
    seed: int = 0

    def __post_init__(self):
        if self.max_attempts < 1:
            raise ValueError(
                f"max_attempts must be >= 1, got {self.max_attempts}")
        if self.base_delay_s < 0 or self.max_delay_s < 0:
            raise ValueError("delays must be >= 0")
        if not 0.0 <= self.jitter <= 1.0:
            raise ValueError(f"jitter is a fraction in [0, 1], got "
                             f"{self.jitter}")

    def delay_s(self, attempt: int, rng: Optional[random.Random] = None
                ) -> float:
        """Backoff before retry ``attempt`` (1-based: the sleep after
        the ``attempt``-th failure)."""
        if attempt < 1:
            raise ValueError(f"attempt is 1-based, got {attempt}")
        d = min(self.base_delay_s * self.multiplier ** (attempt - 1),
                self.max_delay_s)
        if self.jitter and rng is not None:
            d *= 1.0 + self.jitter * rng.random()
        return d

    def delays(self) -> Tuple[float, ...]:
        """The full deterministic backoff schedule (one fresh seeded
        rng — what :meth:`call` will actually sleep)."""
        rng = random.Random(self.seed)
        return tuple(self.delay_s(i, rng)
                     for i in range(1, self.max_attempts))

    def call(self, fn: Callable, *, op: str = "",
             retry_on: Tuple = (Exception,),
             deadline_s: Optional[float] = None,
             on_retry: Optional[Callable] = None,
             event_cb: Optional[Callable] = None,
             sleep: Callable[[float], None] = time.sleep):
        """Run ``fn()`` under the policy; returns ``(result, attempts)``.

        Only exceptions matching ``retry_on`` are retried; anything
        else propagates immediately (a logic bug is not a transient).
        ``deadline_s`` bounds the TOTAL wall clock (monotonic): when the
        next backoff would land past it, the last error re-raises even
        with attempts left — the bench probe's budget semantics.
        ``on_retry(attempt, exc)`` fires before each backoff sleep
        (telemetry: the serving counters and ``probe_attempts`` hang
        off it). ``event_cb(kind, **attrs)`` — when given — receives
        the policy's timeline events (``"retry_backoff"`` with the
        scheduled delay before each sleep, ``"retry_giveup"`` when the
        attempts or the deadline exhaust); the serving telemetry layer
        passes its span-log emitter here so backoff schedules are
        trace-inspectable (docs/observability.md). ``sleep`` is
        injectable so tests never wall-clock.
        """
        rng = random.Random(self.seed)
        t_end = (None if deadline_s is None
                 else time.monotonic() + deadline_s)
        attempt = 0

        def _emit(kind, **attrs):
            if event_cb is not None:
                event_cb(kind, op=op, **attrs)

        while True:
            attempt += 1
            try:
                return fn(), attempt
            except retry_on as e:
                if attempt >= self.max_attempts:
                    _emit("retry_giveup", attempts=attempt,
                          error=type(e).__name__)
                    raise
                d = self.delay_s(attempt, rng)
                if t_end is not None and time.monotonic() + d > t_end:
                    _emit("retry_giveup", attempts=attempt,
                          error=type(e).__name__, deadline=True)
                    raise
                if on_retry is not None:
                    on_retry(attempt, e)
                _emit("retry_backoff", attempt=attempt,
                      delay_s=round(d, 6), error=type(e).__name__)
                logger.warning(
                    "op %r attempt %d/%d failed (%r); retrying in "
                    "%.3fs", op or "<fn>", attempt, self.max_attempts,
                    e, d)
                if d > 0:
                    sleep(d)

    def run(self, fn: Callable, **kw):
        """:meth:`call` without the attempt count."""
        return self.call(fn, **kw)[0]


class FallbackPolicy:
    """Per-op fused-vs-XLA dispatch decisions with log-once semantics."""

    def __init__(self):
        self._lock = threading.Lock()
        self._failed: Dict[str, str] = {}
        self._logged: set = set()

    # -- queries ----------------------------------------------------------

    def forced_ops(self) -> frozenset:
        raw = os.environ.get("TRITON_DIST_TPU_FORCE_XLA", "")
        return frozenset(s.strip() for s in raw.split(",") if s.strip())

    def should_fallback(self, op: str) -> bool:
        forced = self.forced_ops()
        if "*" in forced or op in forced:
            self._log_once(op, "forced via TRITON_DIST_TPU_FORCE_XLA")
            return True
        with self._lock:
            if op in self._failed:
                return True
        return False

    # -- recording --------------------------------------------------------

    def note_failure(self, op: str, exc: BaseException) -> None:
        """Record a fused-path failure; later calls of ``op`` fall back."""
        with self._lock:
            first = op not in self._failed
            self._failed[op] = repr(exc)
        if first:
            logger.warning(
                "fused op %r failed (%r); falling back to the XLA "
                "collective path for subsequent calls", op, exc)

    def _log_once(self, op: str, reason: str) -> None:
        key = (op, reason)
        with self._lock:
            if key in self._logged:
                return
            self._logged.add(key)
        logger.warning("op %r dispatching via XLA fallback: %s", op, reason)

    def reset(self) -> None:
        with self._lock:
            self._failed.clear()
            self._logged.clear()


_GLOBAL = FallbackPolicy()


def should_fallback(op: str) -> bool:
    return _GLOBAL.should_fallback(op)


def note_failure(op: str, exc: BaseException) -> None:
    _GLOBAL.note_failure(op, exc)


def reset() -> None:
    """Clear recorded failures (test scaffolding)."""
    _GLOBAL.reset()


def health_probe(mesh, axis: str = "tp", *, timeout_s: float = 120.0) -> bool:
    """Startup canary: run one tiny fused ``ag_gemm`` on ``mesh`` and
    check it against the XLA oracle under a deadline.

    Returns True when the fused comm path is healthy on this platform;
    False (after logging) on mismatch, exception, or timeout — callers
    (``Engine(fallback="xla", probe=True)``) then route through XLA.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    import numpy as np

    from triton_dist_tpu.ops.ag_gemm import (
        ag_gemm, ag_gemm_ref, create_ag_gemm_context)
    from triton_dist_tpu.parallel.mesh import MeshContext
    from triton_dist_tpu.resilience.watchdog import (
        CommTimeoutError, Watchdog)

    mctx = MeshContext.from_mesh(mesh)
    n = mesh.shape[axis]
    m_loc, k, nn = 8, 128, 128
    a = jnp.arange(n * m_loc * k, dtype=jnp.float32).reshape(
        n * m_loc, k) / (m_loc * k)
    b = jnp.ones((k, nn), jnp.float32) / k
    ctx = create_ag_gemm_context(mctx, axis, block_m=m_loc, block_n=nn,
                                 block_k=k)

    def probe():
        # force_kernel=True: the canary must exercise the REAL fused
        # path — an already-active fallback (FORCE_XLA, a recorded
        # failure) would otherwise reroute it to the oracle and the
        # probe would compare XLA against XLA, vacuously healthy.
        run = jax.jit(jax.shard_map(
            lambda a_, b_: ag_gemm(a_, b_, ctx, force_kernel=True),
            mesh=mesh,
            in_specs=(P(axis, None), P(None, None)),
            out_specs=P(None, None), check_vma=False))
        ref = jax.jit(jax.shard_map(
            lambda a_, b_: ag_gemm_ref(a_, b_, axis=axis), mesh=mesh,
            in_specs=(P(axis, None), P(None, None)),
            out_specs=P(None, None), check_vma=False))
        out = jax.block_until_ready(run(a, b))
        want = jax.block_until_ready(ref(a, b))
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)
        return True

    try:
        return Watchdog(timeout_s, op="health_probe[ag_gemm]").run(probe)
    except CommTimeoutError as e:
        logger.warning("health probe timed out: %s", e)
        return False
    except Exception as e:  # noqa: BLE001 — any failure means unhealthy
        logger.warning("health probe failed: %r", e)
        return False
