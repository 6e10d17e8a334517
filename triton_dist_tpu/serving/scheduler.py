"""Continuous-batching scheduler: admission, slots, deadlines.

Reference: the serving loop the source paper's inference Engine assumes
but never ships (``Engine.serve`` is a fixed-batch greedy loop); the
megakernel-decode serving analysis of arXiv 2605.00686 §serving makes
the same assumption explicit — a PERSISTENT decode batch that requests
join and leave without recompilation.

This module is engine-agnostic bookkeeping: a bounded request queue
(admission control / backpressure), a fixed set of batch slots requests
are admitted into, per-request deadlines, and slot recycling on
completion. The device work — prefill, the fixed-shape decode dispatch,
page allocation — is driven by
:class:`~triton_dist_tpu.serving.server.ServingEngine`, which consumes
this scheduler's decisions.

Policies:

- ``"continuous"`` — admit into any free slot every tick (requests of
  different ages share the decode batch; a finished slot is refilled
  next tick).
- ``"static"`` — gang admission: new requests wait until EVERY slot is
  free, then a full batch enters together (the fixed-batch baseline;
  kept as the bench/ablation reference, not for production).

Deadlines use an injectable ``clock`` (tests drive a fake one — no
wall-clock in the battery). A deadline miss fails THAT request; a hung
collective (the watchdog's :class:`CommTimeoutError`) is mapped by the
server onto :meth:`Scheduler.timeout_victims` so one wedged dispatch
fails the expired (or eldest) request instead of the whole server.
"""

from __future__ import annotations

import dataclasses
import itertools
import time
from collections import deque
from typing import Callable, Dict, List, Optional, Sequence

__all__ = ["Request", "RequestHandle", "QueueFullError", "Scheduler",
           "DEADLINE_CLASSES", "deadline_class"]

# SLO deadline classes, priority-ordered (docs/serving.md,
# "Multi-tenant SLO scheduling"). Rank 0 preempts rank 2, never the
# reverse; EDF orders WITHIN a class, the rank orders across them.
DEADLINE_CLASSES = ("interactive", "standard", "batch")
_CLASS_RANK = {c: i for i, c in enumerate(DEADLINE_CLASSES)}


def deadline_class(request: "Request") -> str:
    """Canonical deadline class of a request: an explicit
    ``slo_class`` wins; otherwise deadline-bearing requests are
    ``"interactive"`` and unbounded ones ``"batch"`` — the same split
    the fleet router's shed policy has always used, now named."""
    c = getattr(request, "slo_class", None)
    if c is not None:
        return c
    return "interactive" if request.deadline is not None else "batch"


class QueueFullError(RuntimeError):
    """Admission control rejected the request — the wait queue is at
    ``max_queue``. Back off and resubmit (backpressure, not a crash)."""


@dataclasses.dataclass
class Request:
    """One generation request.

    ``deadline`` is an ABSOLUTE time on the scheduler's clock (pass
    ``scheduler.now() + budget``); ``None`` = unbounded. ``stream_cb``
    (token_id, handle) fires for every generated token as soon as the
    host sees it. Sampling fields mirror ``Engine.serve`` (temperature
    0 = greedy); seeds fold per-request steps, so a request samples the
    same tokens whether it is served alone or in a shared batch.
    ``tenant`` is a free-form grouping tag: the telemetry layer keys
    latency histograms (TTFT / inter-token) per tenant in addition to
    the global series (docs/observability.md), and when the engine is
    built with ``slo=...`` it also selects the tenant's bounded queue /
    quota buckets. ``slo_class`` pins the deadline class explicitly
    (one of :data:`DEADLINE_CLASSES`); ``None`` derives it from the
    deadline via :func:`deadline_class`. Without an SLO layer both
    fields are telemetry-only and never affect scheduling.
    """

    prompt: Sequence[int]
    max_new_tokens: int = 32
    request_id: Optional[str] = None
    eos_id: Optional[int] = None
    deadline: Optional[float] = None
    temperature: float = 0.0
    top_k: int = 0
    seed: int = 0
    stream_cb: Optional[Callable[[int, "RequestHandle"], None]] = None
    tenant: Optional[str] = None
    slo_class: Optional[str] = None


@dataclasses.dataclass
class RequestHandle:
    """Mutable per-request state the server and callers observe.

    ``status``: queued → prefill → running → one of
    done | failed | timeout; the tiered-KV verbs add parked (KV
    offloaded, no slot, waiting for ``resume()``) and resuming (tier
    payload scattering back, activated next tick); the fleet router
    adds shed (dropped by deadline class under fleet loss — terminal,
    surfaced separately from failures). ``tokens`` grows as the
    request decodes (``stream_cb`` sees each append); ``error``
    carries the failure.
    """

    request: Request
    status: str = "queued"
    tokens: List[int] = dataclasses.field(default_factory=list)
    # KV-tier park/resume (docs/serving.md, "KV memory hierarchy"):
    # a PARKED handle owns no slot and sits in the engine's parked
    # registry with its KV offloaded to the tier store; ``resume()``
    # requeues it with ``resume_key`` set, so admission prefetches the
    # tier payload instead of re-prefilling (status passes through
    # "resuming" for the one tick the scatter overlaps decode).
    # ``resume_t0`` stamps the resume() call — the "resume" span (and
    # the session_resume_ms bench key) closes at reactivation.
    resume_key: Optional[tuple] = None
    resume_t0: Optional[float] = None
    error: Optional[BaseException] = None
    slot: Optional[int] = None
    submitted_at: float = 0.0
    started_at: Optional[float] = None
    finished_at: Optional[float] = None
    decode_steps: int = 0
    # prefill cursor + sequence. Megakernel path: the lane the prompt
    # streams through the decode batch, one token per tick. Chunked
    # layer path: the same fields at CHUNK granularity — ``prompt_pos``
    # is the absolute compute cursor, ``resident`` the prefix-shared
    # token count whose pages are already written (never re-blitted),
    # and ``chunks`` logs each dispatched (start, bucket, valid) — the
    # determinism record the preemption-resume test replays. The lane
    # is the prompt on a fresh admit, or prompt + already-generated
    # tokens when a PREEMPTED request re-enters (cache rebuilt).
    prompt_pos: int = 0
    lane: Optional[List[int]] = None
    resident: int = 0
    chunks: List = dataclasses.field(default_factory=list)
    # Telemetry edges (engine clock): ``queued_at`` is when the handle
    # LAST entered the wait queue (submission, or a preemption/stall/
    # failover requeue — each resets it, so a queue_wait span never
    # swallows time the request already spent running); the first/last
    # emission stamps are what the TTFT and inter-token-latency
    # histograms read. Host-side only — never serialized into a
    # checkpoint (a restored request records no second TTFT, and its
    # ITL restarts at its first post-restore token).
    queued_at: float = 0.0
    first_token_at: Optional[float] = None
    last_token_at: Optional[float] = None
    # Tokens a launched step program owes this request and the host has
    # not fetched yet (docs/serving.md, "The tick in two halves"): with
    # ``len(tokens)`` it says, by count, whether the request has a next
    # step to run. Host-side only, 0 at every checkpoint.
    in_flight: int = 0

    @property
    def done(self) -> bool:
        return self.status in ("done", "failed", "timeout", "shed")


class Scheduler:
    """Slot + queue bookkeeping for one serving engine (see module
    docstring). Not thread-safe: the serving loop is single-threaded
    host code, like the reference's model server."""

    def __init__(self, num_slots: int, *, max_queue: int = 64,
                 policy: str = "continuous",
                 clock: Callable[[], float] = time.monotonic):
        if policy not in ("continuous", "static"):
            raise ValueError(f"policy must be 'continuous' or 'static', "
                             f"got {policy!r}")
        if num_slots < 1:
            raise ValueError(f"num_slots must be >= 1, got {num_slots}")
        self.num_slots = num_slots
        self.max_queue = max_queue
        self.policy = policy
        self.clock = clock
        self.queue: deque[RequestHandle] = deque()
        self.slots: Dict[int, RequestHandle] = {}
        # Requests whose slot went back to admission while their last
        # token is still in flight (:meth:`release`): they keep the
        # scheduler busy until they retire.
        self.landing: List[RequestHandle] = []
        self._ids = itertools.count()
        self.counters = {
            "submitted": 0, "rejected": 0, "completed": 0, "failed": 0,
            "timed_out": 0, "queue_peak": 0,
        }

    def now(self) -> float:
        return self.clock()

    # -- admission ---------------------------------------------------

    def submit(self, request: Request) -> RequestHandle:
        """Admit into the wait queue, or raise :class:`QueueFullError`
        (backpressure) when it is at ``max_queue``."""
        if (request.slo_class is not None
                and request.slo_class not in DEADLINE_CLASSES):
            raise ValueError(
                f"slo_class must be one of {DEADLINE_CLASSES}, "
                f"got {request.slo_class!r}")
        if len(self.queue) >= self.max_queue:
            self.counters["rejected"] += 1
            raise QueueFullError(
                f"wait queue full ({self.max_queue}); retry later")
        if request.request_id is None:
            request = dataclasses.replace(
                request, request_id=f"req-{next(self._ids)}")
        h = RequestHandle(request=request, submitted_at=self.now())
        h.queued_at = h.submitted_at
        self.queue.append(h)
        self.counters["submitted"] += 1
        self.counters["queue_peak"] = max(self.counters["queue_peak"],
                                          len(self.queue))
        return h

    # -- slot assignment --------------------------------------------

    def free_slots(self) -> List[int]:
        return [s for s in range(self.num_slots) if s not in self.slots]

    def admit(self) -> List[RequestHandle]:
        """Move queued requests into free slots per the policy; returns
        the newly-placed handles (status ``"prefill"`` — the server
        runs their prefill / starts their prefill lane)."""
        free = self.free_slots()
        if self.policy == "static" and len(free) < self.num_slots:
            return []
        placed = []
        while free and self.queue:
            h = self.queue.popleft()
            h.slot = free.pop(0)
            h.status = "prefill"
            h.started_at = self.now()
            self.slots[h.slot] = h
            placed.append(h)
        return placed

    def running(self) -> List[RequestHandle]:
        """Handles currently owning a slot, slot-ordered."""
        return [self.slots[s] for s in sorted(self.slots)]

    def release(self, h: RequestHandle):
        """Hand ``h``'s slot back to admission before ``h`` retires: the
        server knows by count that the step in flight is its last. The
        handle keeps its ``slot`` and a non-terminal status, and the
        scheduler stays busy, until :meth:`retire`."""
        self.slots.pop(h.slot, None)
        self.landing.append(h)

    def retire(self, h: RequestHandle, status: str,
               error: Optional[BaseException] = None):
        """Finish a request and recycle its slot (unless
        :meth:`release` already handed it on)."""
        h.status = status
        h.error = error
        h.finished_at = self.now()
        self.landing = [x for x in self.landing if x is not h]
        if h.slot is not None and self.slots.get(h.slot) is h:
            del self.slots[h.slot]
        h.slot = None
        key = {"done": "completed", "timeout": "timed_out"}.get(
            status, "failed")
        self.counters[key] += 1

    # -- deadlines ---------------------------------------------------

    def expired(self, now: Optional[float] = None) -> List[RequestHandle]:
        """Queued or running handles whose deadline has passed (the
        caller retires them — queued ones never touch a slot)."""
        t = self.now() if now is None else now
        out = [h for h in self.queue
               if h.request.deadline is not None
               and t >= h.request.deadline]
        for h in out:
            self.queue.remove(h)
        out += [h for h in self.running()
                if h.request.deadline is not None
                and t >= h.request.deadline]
        return out

    def timeout_victims(self) -> List[RequestHandle]:
        """Who a hung collective (CommTimeoutError on the shared decode
        dispatch) should fail: every running request past its deadline,
        else ONE victim chosen class-aware — batch before standard
        before interactive (an interactive session should be the last
        thing a wedged dispatch takes down), eldest ``started_at``
        within a class, slot id as the deterministic final tiebreak.
        One victim guarantees progress; the server and the other
        requests survive."""
        victims = [h for h in self.running()
                   if h.request.deadline is not None
                   and self.now() >= h.request.deadline]
        if not victims:
            alive = self.running()
            if alive:
                victims = [min(alive, key=lambda h: (
                    -_CLASS_RANK[deadline_class(h.request)],
                    h.started_at, h.slot))]
        return victims

    @property
    def idle(self) -> bool:
        return not self.queue and not self.slots and not self.landing
