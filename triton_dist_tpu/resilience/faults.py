"""Fault-injection registry and hooks for the interpret-mode comm path.

The signal/wait protocols this package is built on (ring puts certified
by DMA semaphores, scoreboard edge semaphores, entry barriers) are
exactly where a lost signal or a stalled remote DMA turns into a silent
hang or a corrupted tile. This module makes those failures *injectable*:
a :class:`FaultPlan` names a set of :class:`Fault` events, and thin
hooks in ``lang.shmem_device`` (puts / signals / barriers), the fused
ops (call counting), ``utils.distributed.interpret_arg`` (DMA-timing
overrides), and the megakernel builder (scoreboard edges) consult the
active plan at kernel-trace time.

USAGE — trace-time injection::

    from triton_dist_tpu.resilience import faults
    with faults.inject(faults.get_plan("skewed_barrier", op="ag_gemm",
                                       rank=2)):
        out = fresh_jitted_ag_gemm(a, b)   # trace INSIDE the scope

Faults are baked in when the kernel is traced, so callers must build a
fresh jitted closure inside the ``inject`` scope (the test harness
does); a function traced before the scope keeps its fault-free schedule.

Fault kinds (``Fault.kind``):

- ``"delay_dma"``  — spin ``iters`` dependent FLOP iterations on
  ``rank`` before issuing the ``k``-th remote put of ``op`` (``k=None``
  = every put). Plans may also set ``dma_on_wait=True`` to flip the
  interpreter's DMA completion to the maximally-late schedule
  (``InterpretParams(dma_execution_mode="on_wait")``).
- ``"drop_put"``   — the ``k``-th remote put of ``op`` is never issued
  on ``rank``: no data, no send/recv semaphore counts.
- ``"dup_put"``    — the ``k``-th remote put of ``op`` is issued twice
  on ``rank``: duplicated data and doubled semaphore counts.
- ``"drop_signal"``/``"dup_signal"`` — a ``dl.notify`` increment from
  ``rank`` is dropped / doubled.
- ``"skew_barrier"`` — ``rank`` spins ``iters`` iterations before its
  entry-barrier arrival.
- ``"drop_edge"``  — the megakernel scoreboard signal for edge index
  ``k`` is never raised (every rank; the merged queue is SPMD). Unlike
  the put/call kinds, ``k=None`` here selects edge 0, not "all edges"
  (the builder suppresses exactly one edge's signal per plan).
- ``"fail_call"``  — the ``k``-th host-level call of ``op`` raises
  :class:`InjectedFault` (drives the watchdog / fallback machinery).
- ``"timeout_call"`` — the ``k``-th host-level call of ``op`` raises a
  :class:`~triton_dist_tpu.resilience.watchdog.CommTimeoutError`
  directly: the deterministic stand-in for "the transfer wedged and
  the watchdog fired" (a real wedge leaks an uncancellable worker
  thread — see the watchdog caveat — so soak-style tests inject the
  *detected* outcome instead; the genuine-deadlock plans stay in the
  subprocess harness). The serving retry/backoff and containment
  paths treat it exactly like a watchdog miss.
- ``"corrupt_payload"`` — the ``k``-th host-staged payload of ``op``
  (``tier_transfer`` / ``page_migration`` / ``fleet_handoff``) gets a
  seeded bit flip applied to a COPY of its staged bytes before the
  consuming edge verifies the digest (``iters`` seeds which bit;
  ``k=None`` = every staged payload). Consulted via
  :func:`corrupt_fault` by ``resilience.integrity.maybe_corrupt`` —
  the model of silent wire/storage corruption the end-to-end payload
  digests exist to catch (docs/resilience.md, "Payload integrity").
"""

from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Dict, Optional, Tuple

__all__ = [
    "Fault", "FaultPlan", "InjectedFault", "inject", "active_plan",
    "on_op_call", "corrupt_fault", "register_plan", "get_plan",
    "battery",
]


class InjectedFault(RuntimeError):
    """Raised by a ``fail_call`` fault at the targeted op invocation."""

    def __init__(self, op: str, call_index: int):
        self.op = op
        self.call_index = call_index
        super().__init__(
            f"injected fault: call #{call_index} of op {op!r}")


@dataclasses.dataclass(frozen=True)
class Fault:
    kind: str
    op: str = "*"                 # op name, or "*" = any op
    rank: int = -1                # target rank along the op's axis
    k: Optional[int] = None      # which put / call (None = all);
                                 # drop_edge: which edge (None = 0)
    iters: int = 0               # spin length for delay/skew kinds

    def matches_op(self, op: str) -> bool:
        return self.op == "*" or self.op == op


@dataclasses.dataclass(frozen=True)
class FaultPlan:
    """A named, replayable adversarial schedule."""
    name: str
    faults: Tuple[Fault, ...] = ()
    # Newer-JAX interpreter: defer every DMA's completion to its wait
    # (the maximally-late arrival schedule).
    dma_on_wait: bool = False

    def faults_of(self, kind: str, op: str) -> Tuple[Fault, ...]:
        return tuple(f for f in self.faults
                     if f.kind == kind and f.matches_op(op))


# ---------------------------------------------------------------------------
# Active-plan state. Trace-time counters are keyed per op occurrence so
# "the k-th put of the op" is well-defined within one inject() scope.
# ---------------------------------------------------------------------------

_STATE = threading.local()


def _st():
    if not hasattr(_STATE, "plan"):
        _STATE.plan = None
        _STATE.op_stack = []
        _STATE.call_counts = {}
        _STATE.put_counts = {}
        _STATE.corrupt_counts = {}
    if not hasattr(_STATE, "corrupt_counts"):   # upgraded mid-thread
        _STATE.corrupt_counts = {}
    return _STATE


def active_plan() -> Optional[FaultPlan]:
    return _st().plan


def current_op() -> Optional[str]:
    st = _st()
    return st.op_stack[-1] if st.op_stack else None


@contextlib.contextmanager
def inject(plan: FaultPlan):
    """Activate ``plan`` for code traced inside the scope."""
    st = _st()
    prev = st.plan
    st.plan = plan
    st.call_counts = {}
    st.put_counts = {}
    st.corrupt_counts = {}
    try:
        yield plan
    finally:
        st.plan = prev


@contextlib.contextmanager
def _op_scope(op: str):
    st = _st()
    st.op_stack.append(op)
    # Save/restore so a nested same-op scope (an op composed from
    # another op) cannot clobber the outer scope's k-th-put counter.
    prev_puts = st.put_counts.get(op)
    st.put_counts[op] = 0
    try:
        yield
    finally:
        st.op_stack.pop()
        if prev_puts is None:
            st.put_counts.pop(op, None)
        else:
            st.put_counts[op] = prev_puts


def on_op_call(op: str):
    """Host/trace-time hook at a fused op's public entry.

    Counts the invocation, raises :class:`InjectedFault` when a
    ``fail_call`` fault targets it, and returns a context manager
    scoping kernel-level hooks (puts/signals/barriers) to this op::

        with faults.on_op_call("ag_gemm"):
            ... core_call(...)  # traced under the op scope

    Free when no plan is active (returns a no-op scope).
    """
    st = _st()
    plan = st.plan
    if plan is None:
        return contextlib.nullcontext()
    idx = st.call_counts.get(op, 0)
    st.call_counts[op] = idx + 1
    for f in plan.faults_of("fail_call", op):
        if f.k is None or f.k == idx:
            raise InjectedFault(op, idx)
    for f in plan.faults_of("timeout_call", op):
        if f.k is None or f.k == idx:
            from triton_dist_tpu.resilience.watchdog import (
                CommTimeoutError)

            raise CommTimeoutError(
                op=op, timeout_s=0.0, progress={"call_index": idx},
                detail="injected wedge (timeout_call fault): the "
                       "deterministic stand-in for a watchdog miss")
    return _op_scope(op)


def corrupt_fault(op: str) -> Optional[Fault]:
    """``corrupt_payload`` fault (if any) targeting the host-staged
    payload of ``op`` being serialized right now.

    Counts payload stagings per op (its OWN counter — independent of
    the call/put counters, so a retried transfer that re-stages the
    payload advances it) and returns the matching fault, whose
    ``iters`` field seeds the bit flip. Consumed by
    ``resilience.integrity.maybe_corrupt``; free when no plan is
    active.
    """
    st = _st()
    plan = st.plan
    if plan is None:
        return None
    faults = plan.faults_of("corrupt_payload", op)
    if not faults:
        return None
    idx = st.corrupt_counts.get(op, 0)
    st.corrupt_counts[op] = idx + 1
    for f in faults:
        if f.k is None or f.k == idx:
            return f
    return None


# ---------------------------------------------------------------------------
# Kernel-side (trace-time) consultation, called from lang.shmem_device
# and the megakernel builder. All return None on the fault-free path.
# ---------------------------------------------------------------------------

def put_fault() -> Optional[Fault]:
    """Fault (if any) targeting the remote put being traced right now.

    Increments the per-op put counter as a side effect — call exactly
    once per traced put (``dl.remote_put`` does).

    drop_put/dup_put need rank-divergent control flow (``pl.when(me ==
    rank)`` around the DMA), which the old generic discharge
    interpreter cannot execute (divergent sites deadlock its hidden
    collectives) — and is vacuous there anyway, since its semaphore
    waits never block. Those kinds are skipped under that backend;
    delay_dma (a uniform spin) always applies.
    """
    st = _st()
    plan, op = st.plan, current_op()
    if plan is None or op is None:
        return None
    idx = st.put_counts.get(op, 0)
    st.put_counts[op] = idx + 1
    for kind in ("drop_put", "dup_put", "delay_dma"):
        for f in plan.faults_of(kind, op):
            if f.k is None or f.k == idx:
                return f
    return None


def signal_fault() -> Optional[Fault]:
    """drop_signal/dup_signal fault scoped to the op being traced."""
    st = _st()
    plan, op = st.plan, current_op()
    if plan is None or op is None:
        return None
    for kind in ("drop_signal", "dup_signal"):
        for f in plan.faults_of(kind, op):
            return f
    return None


def barrier_fault() -> Optional[Fault]:
    """skew_barrier fault scoped to the op being traced."""
    st = _st()
    plan, op = st.plan, current_op()
    if plan is None or op is None:
        return None
    for f in plan.faults_of("skew_barrier", op):
        return f
    return None


def edge_drop(op: str) -> Optional[int]:
    """Scoreboard edge index whose completion signal must be dropped."""
    plan = _st().plan
    if plan is None:
        return None
    for f in plan.faults_of("drop_edge", op):
        return f.k if f.k is not None else 0
    return None


def interpret_overrides() -> Dict[str, object]:
    """Extra ``InterpretParams`` kwargs requested by the active plan
    (consulted by ``utils.distributed.interpret_arg``)."""
    plan = _st().plan
    if plan is not None and plan.dma_on_wait:
        return {"dma_execution_mode": "on_wait"}
    return {}


def spin(iters: int, seed):
    """Dependent-FLOP busy loop (the only skew source that exists on
    both the compiled and interpreted backends — ``pl.delay`` is a
    no-op under interpret mode). Returns a float32 scalar the caller
    must fold into an effectful op's operand (e.g. ``peer + spin*0``)
    so XLA cannot dead-code it away."""
    import jax
    import jax.numpy as jnp

    return jax.lax.fori_loop(
        0, iters, lambda _, x: x * 1.0000001 + 1e-7,
        jnp.float32(1.0) + jnp.asarray(seed, jnp.float32) * 0.0)


def rank_spin_zero(axis: str, rank: int, iters: int):
    """Traced int32 zero that costs ``iters`` spin iterations on
    ``rank`` (and nothing elsewhere). Add it to a device id or
    semaphore increment to inject skew without changing semantics."""
    import jax
    import jax.numpy as jnp

    me = jax.lax.axis_index(axis)
    s = jax.lax.cond(me == rank,
                     lambda: spin(iters, me),
                     lambda: jnp.float32(1.0))
    return (s * 0.0).astype(jnp.int32)


# ---------------------------------------------------------------------------
# Named plan registry — the standard battery.
# ---------------------------------------------------------------------------

_REGISTRY: Dict[str, object] = {}


def register_plan(name: str, factory) -> None:
    """Register a plan factory: ``factory(op=..., rank=..., k=...,
    iters=...) -> FaultPlan``."""
    _REGISTRY[name] = factory


def get_plan(name: str, **kw) -> FaultPlan:
    if name not in _REGISTRY:
        raise KeyError(
            f"unknown fault plan {name!r}; known: {sorted(_REGISTRY)}")
    return _REGISTRY[name](**kw)


def battery():
    """Names of the standard adversarial-schedule battery."""
    return sorted(_REGISTRY)


def _delayed_dma(op="*", rank=0, k=None, iters=20000):
    return FaultPlan(
        name="delayed_dma", dma_on_wait=True,
        faults=(Fault("delay_dma", op=op, rank=rank, k=k, iters=iters),))


def _dropped_signal(op="*", rank=0, k=0, **_):
    return FaultPlan(
        name="dropped_signal",
        faults=(Fault("drop_put", op=op, rank=rank, k=k),
                Fault("drop_signal", op=op, rank=rank)))


def _dup_signal(op="*", rank=0, k=0, **_):
    return FaultPlan(
        name="dup_signal",
        faults=(Fault("dup_put", op=op, rank=rank, k=k),
                Fault("dup_signal", op=op, rank=rank)))


def _skewed_barrier(op="*", rank=0, iters=20000, **_):
    return FaultPlan(
        name="skewed_barrier",
        faults=(Fault("skew_barrier", op=op, rank=rank, iters=iters),))


def _dropped_edge(op="megakernel", k=0, **_):
    return FaultPlan(
        name="dropped_edge",
        faults=(Fault("drop_edge", op=op, k=k),))


def _fail_kth_call(op="*", k=0, **_):
    return FaultPlan(
        name="fail_kth_call",
        faults=(Fault("fail_call", op=op, k=k),))


def _wedge_kth_call(op="*", k=0, **_):
    return FaultPlan(
        name="wedge_kth_call",
        faults=(Fault("timeout_call", op=op, k=k),))


def _corrupt_payload(op="tier_transfer", k=0, iters=0, **_):
    # ``iters`` seeds the flipped bit (integrity.maybe_corrupt);
    # k=None corrupts every staged payload of the op.
    return FaultPlan(
        name="corrupt_payload",
        faults=(Fault("corrupt_payload", op=op, k=k, iters=iters),))


register_plan("delayed_dma", _delayed_dma)
register_plan("dropped_signal", _dropped_signal)
register_plan("dup_signal", _dup_signal)
register_plan("skewed_barrier", _skewed_barrier)
register_plan("dropped_edge", _dropped_edge)
register_plan("fail_kth_call", _fail_kth_call)
register_plan("wedge_kth_call", _wedge_kth_call)
register_plan("corrupt_payload", _corrupt_payload)
