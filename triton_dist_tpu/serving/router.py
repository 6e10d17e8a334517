"""Fleet-scale serving: a front-end router over R replicated fleets.

The ROADMAP's north star is serving heavy traffic from millions of
users; one :class:`~triton_dist_tpu.serving.server.ServingEngine` is a
single failure domain with a single pool. This module composes R
INDEPENDENT serving fleets (each its own engine, page pool, and tier
store — a ``DisaggServingEngine`` counts as one fleet) behind a
:class:`FleetRouter` front end:

- **prefix-affinity routing** — a request routes to the fleet whose
  prefix cache *or tier store* holds the longest leading run of its
  prompt's chained content keys (the exact key algebra
  :meth:`~triton_dist_tpu.serving.blocks.BlockManager.alloc_prefill`
  uses), so multi-turn sessions keep hitting the fleet that already
  holds their KV; ties break by load, then fleet id — fully
  deterministic. Routing to a fleet also fires that fleet's
  router-time tier prefetch
  (:meth:`~triton_dist_tpu.serving.server.ServingEngine.tier_prefetch`)
  so the tier hop overlaps queue wait.
- **health-routed dispatch** — per-fleet
  :class:`~triton_dist_tpu.resilience.watchdog.HealthTracker`\\ s beat
  on completed serving ticks and strike on post-retry ``fleet_route``
  failures; a fleet crossing the threshold fails over automatically.
  The router→fleet link rides the ``"fleet_route"`` fault op (chaos
  can drop or wedge it) under an optional
  :class:`~triton_dist_tpu.resilience.policy.RetryPolicy`.
- **fleet failover** — a dead fleet's queued requests requeue on
  survivors token-preserving; its *running* sessions fail over
  cross-fleet: on a REACHABLE victim they park into its tier store and
  the pinned payload hops to a survivor's tier over the
  ``"fleet_handoff"`` op (resumed token-exact through the ordinary
  tier-resume path); an unreachable victim's sessions re-enter via the
  deterministic re-prefill contract — token-exact either way, by
  construction.
- **drain/restore autoscale** — :meth:`FleetRouter.scale_to` grows the
  fleet set from the factory, or drains a fleet (stop admitting, park
  or finish in-flight), snapshots it via
  :meth:`~triton_dist_tpu.serving.server.ServingEngine.checkpoint`
  (which carries the tier snapshot), and restores the parked sessions
  onto the new topology FROM THE SNAPSHOT with the live handles
  reattached.
- **graceful degradation** — when fleet loss leaves the survivors
  saturated, the router sheds load by DEADLINE CLASS (requests without
  a deadline — the batch class — first) instead of failing broadly;
  shed requests terminate with status ``"shed"`` and are surfaced in
  ``stats()["shed_requests"]``, separately from failures.

Every cross-fleet payload stays a one-sided whole-page hop through the
tier store (the Triton-distributed handoff discipline, arXiv
2504.19442), and the router's control path never blocks on a fleet's
device work — the hidden-serialization guidance of arXiv 2605.00686
for the DCN hop this models. Chaos coverage lives in
:func:`~triton_dist_tpu.resilience.chaos.run_fleet_soak`.
"""

from __future__ import annotations

import dataclasses
import itertools
import time
from collections import deque
from typing import Callable, Dict, List, Optional, Tuple

from triton_dist_tpu.serving.scheduler import (
    _CLASS_RANK, QueueFullError, Request, RequestHandle,
    deadline_class,
)

__all__ = ["FleetRouter", "ShedError"]


class ShedError(RuntimeError):
    """The router dropped this request by deadline class under fleet
    loss / saturation (graceful degradation — capacity went to the
    higher class instead of failing everyone a little). Terminal
    status ``"shed"``; counted in ``stats()["shed_requests"]``,
    never in ``failed``."""


@dataclasses.dataclass
class _Fleet:
    """One serving fleet behind the router: the engine, its health
    view, and the router-side liveness flags (``dead`` = failed over
    or drained; ``draining`` = no new admissions)."""

    id: int
    engine: object
    health: object
    dead: bool = False
    draining: bool = False


class FleetRouter:
    """Front-end router over R replicated serving fleets (see module
    docstring).

    ``factory`` builds ONE fleet per call — a layer-path
    :class:`~triton_dist_tpu.serving.server.ServingEngine` (or
    ``DisaggServingEngine``) over the same weights and pool plan; all
    fleets must be identically planned (page / p_max / max_len /
    kv_dtype are validated), or cross-fleet failover could not be
    token-exact. ``affinity=True`` (default) requires
    ``prefix_reuse`` on the fleet engines — the chained content keys
    ARE the affinity signal. ``retry`` arms the ``fleet_route`` /
    ``fleet_handoff`` ops (a :class:`~triton_dist_tpu.resilience.
    policy.RetryPolicy`, an ``{op: policy}`` dict, or None).
    ``fleet_fail_threshold`` consecutive post-retry route failures
    declare a fleet dead (never the last live one — the sole survivor
    keeps serving fail-soft). ``max_queue`` bounds the ROUTER's
    overflow queue, behind the per-fleet queues. ``clock`` is
    injectable (share it with the fleet engines in tests).
    """

    def __init__(self, factory: Callable[[], object], *,
                 fleets: int = 2, clock=time.monotonic,
                 affinity: bool = True, retry=None,
                 fleet_fail_threshold: int = 3, max_queue: int = 256,
                 telemetry: str = "counters",
                 telemetry_capacity: int = 4096):
        from triton_dist_tpu.obs import Telemetry
        from triton_dist_tpu.resilience.policy import RetryPolicy

        if fleets < 1:
            raise ValueError(f"fleets must be >= 1, got {fleets}")
        self.factory = factory
        self.clock = clock
        self.affinity = bool(affinity)
        self.fleet_fail_threshold = int(fleet_fail_threshold)
        self.max_queue = int(max_queue)
        if isinstance(telemetry, Telemetry):
            self.obs = telemetry
        else:
            self.obs = Telemetry(telemetry, clock=clock,
                                 capacity=telemetry_capacity)
        if retry is None:
            self.retry_policies = {}
        elif isinstance(retry, RetryPolicy):
            self.retry_policies = {"fleet_route": retry,
                                   "fleet_handoff": retry}
        elif isinstance(retry, dict):
            for op, pol in retry.items():
                if not isinstance(pol, RetryPolicy):
                    raise TypeError(
                        f"retry[{op!r}] must be a RetryPolicy, got "
                        f"{type(pol).__name__}")
            self.retry_policies = dict(retry)
        else:
            raise TypeError(
                "retry must be a RetryPolicy, an {op: RetryPolicy} "
                f"dict, or None — got {type(retry).__name__}")
        self.fleets: List[_Fleet] = []
        self._fleet_ids = itertools.count()
        self._ids = itertools.count()
        self._rr = itertools.count()   # affinity-off rotation cursor
        # Router-level overflow queue: requests every fleet rejected
        # (admission control), retried at each tick.
        self.queue: deque = deque()
        self.counters: Dict[str, int] = {
            "routed": 0, "affinity_hits": 0, "affinity_misses": 0,
            "spillovers": 0, "shed_requests": 0, "fleet_failovers": 0,
            "failover_resumed": 0, "failover_reprefilled": 0,
            "drain_resumed": 0, "drain_reprefilled": 0,
            "scale_ups": 0, "scale_downs": 0,
            "router_retries": 0, "comm_timeouts": 0,
            "integrity_failures": 0,
        }
        # Per-tenant shed breakdown (one tenant's flood spends its own
        # shed budget — docs/serving.md, "Multi-tenant SLO
        # scheduling"); keys appear on first shed.
        self.shed_by_tenant: Dict[str, int] = {}
        for _ in range(fleets):
            self.fleets.append(self._make_fleet(factory()))

    # -- fleet construction / topology --------------------------------

    def _make_fleet(self, engine) -> _Fleet:
        from triton_dist_tpu.resilience.watchdog import HealthTracker
        from triton_dist_tpu.serving.server import ServingEngine

        if not isinstance(engine, ServingEngine):
            raise TypeError(
                "factory must build a ServingEngine (or a "
                f"DisaggServingEngine), got {type(engine).__name__}")
        if engine.mega:
            raise ValueError(
                "the fleet router fronts the layer serving path; the "
                "megakernel engine has no checkpoint/tier plumbing "
                "for cross-fleet failover (docs/serving.md)")
        if self.affinity and (engine.manager is None
                              or not engine.manager.prefix_reuse):
            raise ValueError(
                "affinity routing reads the chained-content-key "
                "prefix cache: build the fleet engines with "
                "prefix_reuse=True (or pass affinity=False)")
        if self.fleets:
            ref = self.fleets[0].engine
            bad = {k: (getattr(engine, k), getattr(ref, k))
                   for k in ("page", "p_max", "max_len", "kv_dtype",
                             "num_slots")
                   if getattr(engine, k) != getattr(ref, k)}
            if bad:
                raise ValueError(
                    "fleets must be identically planned (cross-fleet "
                    f"failover is token-exact only then): {bad}")
        fid = next(self._fleet_ids)

        def _on_event(kind, at, cause, fid=fid):
            self.obs.event(f"fleet_{kind}", fleet=fid, cause=cause)

        health = HealthTracker(fail_threshold=self.fleet_fail_threshold,
                               clock=self.clock, on_event=_on_event)
        # ONE clock governs the whole topology: the router queue,
        # every fleet's scheduler deadlines, and every fleet's
        # telemetry stamps. Factory-built engines default to
        # time.monotonic — rebinding here (clock is a plain attribute
        # on both) makes deadline/shed decisions consistent across
        # fleets and lets tests drive the full fleet with one fake
        # clock (the PR-13 known limit: the router used to borrow
        # fleet 0's scheduler clock while other fleets kept their
        # own).
        engine.sched.clock = self.clock
        engine.obs.clock = self.clock
        return _Fleet(id=fid, engine=engine, health=health)

    def _live_fleets(self, exclude: Optional[_Fleet] = None
                     ) -> List[_Fleet]:
        return [f for f in self.fleets
                if not f.dead and f is not exclude]

    def _routable_fleets(self) -> List[_Fleet]:
        return [f for f in self._live_fleets() if not f.draining]

    @staticmethod
    def _load(f: _Fleet) -> int:
        sch = f.engine.sched
        return len(sch.queue) + len(sch.slots)

    # -- affinity ------------------------------------------------------

    def _affinity_run(self, engine, prompt) -> int:
        """Leading count of the prompt's full-page chained content
        keys resident on ``engine`` — in its HBM prefix cache or its
        tier store (either serves the bytes without recompute). The
        same key chain :meth:`BlockManager.alloc_prefill` builds, so
        a hit here IS a prefix hit there."""
        mgr = engine.manager
        if mgr is None or not mgr.prefix_reuse:
            return 0
        run = 0
        for key in mgr.iter_prefix_keys(prompt):
            if key in mgr._prefix:
                run += 1
                continue
            if engine.tiers is not None \
                    and engine._tier_resident_prefix(key):
                run += 1
                continue
            break
        return run

    def _route_order(self, prompt, tenant=None
                     ) -> Tuple[List[_Fleet], Dict[int, int]]:
        """Deterministic target order for one prompt. Affinity mode:
        longest resident prefix run first, then least loaded, then
        lowest fleet id (the spillover order when the preferred fleet
        is saturated). Affinity off: plain round-robin rotation with
        load as the tiebreak — the spread-only baseline the affinity
        ablation measures against.

        When any fleet is armed with an SLO layer the order is also
        TENANT-aware: between equal prefix runs, a fleet already
        holding the same tenant's work sorts later — one tenant's
        flood spreads across the fleet instead of piling up behind
        its own backlog. With SLO off the sort key is unchanged, so
        the pre-existing deterministic routing stays byte-identical.
        """
        cands = self._routable_fleets()
        if not self.affinity:
            if cands:
                k = next(self._rr) % len(cands)
                cands = cands[k:] + cands[:k]
            return cands, {f.id: 0 for f in cands}
        runs = {f.id: self._affinity_run(f.engine, prompt)
                for f in cands}
        if tenant is not None and any(
                getattr(f.engine, "slo", None) is not None
                for f in cands):
            tload = {f.id: self._tenant_load(f, tenant) for f in cands}
            order = sorted(cands, key=lambda f: (
                -runs[f.id], tload[f.id], self._load(f), f.id))
        else:
            order = sorted(cands, key=lambda f: (-runs[f.id],
                                                 self._load(f), f.id))
        return order, runs

    def _tenant_load(self, f: "_Fleet", tenant) -> int:
        """In-system request count for one tenant on one fleet
        (queued + running + SLO-tenant-queued)."""
        e = f.engine
        n = sum(1 for h in e.sched.queue if h.request.tenant == tenant)
        n += sum(1 for h in e.sched.running()
                 if h.request.tenant == tenant)
        if getattr(e, "slo", None) is not None:
            n += sum(1 for h in e.slo.queued_handles()
                     if h.request.tenant == tenant)
        return n

    # -- retryable router ops ------------------------------------------

    def _run_router_op(self, op: str, fn):
        """One retryable router op (``fleet_route`` /
        ``fleet_handoff``) under its configured RetryPolicy — the same
        machinery the serving engine arms for migrations and tier
        transfers (none configured = one attempt)."""
        from triton_dist_tpu.resilience import faults
        from triton_dist_tpu.resilience.watchdog import CommTimeoutError

        pol = self.retry_policies.get(op)
        if pol is None:
            return fn()

        def _note(attempt, exc):
            self.counters["router_retries"] += 1
            self.obs.event("retry", op=op, attempt=attempt,
                           error=type(exc).__name__)
            if isinstance(exc, CommTimeoutError):
                self.counters["comm_timeouts"] += 1

        from triton_dist_tpu.resilience.integrity import IntegrityError

        # IntegrityError is retryable here: a corrupted HANDOFF hop
        # re-fetches from the victim's still-authoritative tier entry
        # (a corrupted victim GET quarantines inside the store and
        # surfaces as LookupError on the retry — the re-prefill path).
        return pol.run(fn, op=f"router.{op}",
                       retry_on=(CommTimeoutError, faults.InjectedFault,
                                 IntegrityError),
                       on_retry=_note,
                       event_cb=(self.obs.event if self.obs.spans_on
                                 else None))

    # -- admission / routing -------------------------------------------

    def submit(self, request, **kw) -> RequestHandle:
        """Route one request to a fleet (a :class:`Request` or a
        prompt sequence plus Request kwargs). The handle is terminal
        ``"shed"`` when admission control dropped a batch-class
        request with everything saturated; interactive requests raise
        :class:`~triton_dist_tpu.serving.scheduler.QueueFullError`
        instead (backpressure the caller can retry)."""
        if isinstance(request, Request):
            if kw:
                raise TypeError(
                    f"keyword args {sorted(kw)} ignored when passing "
                    "a Request — set them on the Request itself")
        else:
            request = Request(prompt=list(request), **kw)
        if len(request.prompt) == 0:
            raise ValueError("empty prompt")
        if request.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        ref = self.fleets[0].engine
        total = len(request.prompt) + request.max_new_tokens
        cap = min(ref.p_max * ref.page, ref.max_len)
        if total > cap:
            raise ValueError(
                f"prompt {len(request.prompt)} + gen "
                f"{request.max_new_tokens} exceeds fleet capacity "
                f"{cap}")
        if request.request_id is None:
            # Router-assigned ids: unique ACROSS fleets (tier session
            # keys and failover bookkeeping are keyed on them).
            request = dataclasses.replace(
                request, request_id=f"req-r{next(self._ids)}")
        h = RequestHandle(request=request,
                          submitted_at=self.obs.now())
        h.queued_at = h.submitted_at
        self.counters["routed"] += 1
        self.obs.event("submit", request_id=request.request_id,
                       tenant=request.tenant,
                       prompt_tokens=len(request.prompt),
                       max_new_tokens=request.max_new_tokens)
        with self.obs.span("route", request_id=request.request_id,
                           tenant=request.tenant):
            self._route(h)
        return h

    def _send(self, f: _Fleet, h: RequestHandle, *,
              head: bool = False) -> None:
        """The router→fleet link: one queue insertion under the
        ``fleet_route`` fault op (chaos drops/wedges raise BEFORE any
        mutation, so a retried send is idempotent)."""
        from triton_dist_tpu.resilience import faults

        with faults.on_op_call("fleet_route"):
            sch = f.engine.sched
            h.slot = None
            h.status = "queued"
            h.queued_at = sch.now()
            slo = getattr(f.engine, "slo", None)
            if slo is not None and not head:
                # SLO-armed fleet: land in the TENANT queue so class
                # ordering / DRR / quotas apply to routed requests too.
                # Head insertions (failover handoffs, resumes) keep the
                # direct front-of-queue contract — they already ran.
                st = slo.registry.state(h.request.tenant, sch.now())
                slo.adopt(f.engine, h)
                st.admitted += 1
            else:
                (sch.queue.appendleft if head else sch.queue.append)(h)
            sch.counters["queue_peak"] = max(
                sch.counters["queue_peak"], len(sch.queue))

    def _route(self, h: RequestHandle, *, head: bool = False,
               degrade: bool = False, requeue_only: bool = False,
               force_queue: bool = False) -> bool:
        """Place ``h`` on the best available fleet (affinity order,
        deterministic spillover). Returns True when placed; otherwise
        the request lands in the router queue, is shed by class
        (``degrade`` — fleet-loss mode), or raises QueueFullError —
        ``requeue_only`` silently re-queues instead (the tick drain
        loop), and ``force_queue`` (the voluntary-drain path) queues
        past ``max_queue`` rather than ever shedding."""
        from triton_dist_tpu.resilience import faults
        from triton_dist_tpu.resilience.watchdog import CommTimeoutError

        order, runs = self._route_order(h.request.prompt,
                                        h.request.tenant)
        for f in order:
            sch = f.engine.sched
            if len(sch.queue) >= sch.max_queue:
                continue                      # saturated: spill over
            slo = getattr(f.engine, "slo", None)
            if slo is not None and not head:
                st = slo.registry.state(h.request.tenant, sch.now())
                if len(st.queue) >= st.spec.max_queue:
                    continue    # tenant-saturated here: spill over
            try:
                self._run_router_op(
                    "fleet_route",
                    lambda f=f: self._send(f, h, head=head))
            except (CommTimeoutError, faults.InjectedFault) as e:
                if isinstance(e, CommTimeoutError):
                    self.counters["comm_timeouts"] += 1
                self._strike(f, e)
                if f.dead:
                    # The strike crossed the death threshold and the
                    # failover ran; routing targets changed under us —
                    # recompute rather than walk a stale order.
                    return self._route(h, head=head, degrade=degrade,
                                       requeue_only=requeue_only,
                                       force_queue=force_queue)
                continue
            if f is not order[0]:
                self.counters["spillovers"] += 1
            if runs.get(f.id, 0) > 0:
                self.counters["affinity_hits"] += 1
            else:
                self.counters["affinity_misses"] += 1
            # Predictive tier prefetch fires at ROUTE time, so the
            # tier hop overlaps queue wait (admission consumes the
            # warm payload without a second transfer).
            f.engine.tier_prefetch(h.request.prompt)
            return True
        if requeue_only:
            self.queue.append(h)
            return False
        self._overflow(h, degrade=degrade, force_queue=force_queue)
        return False

    def _overflow(self, h: RequestHandle, *, degrade: bool,
                  force_queue: bool = False) -> None:
        """Every fleet rejected ``h``: hold it in the router queue, or
        shed when that is full too. The shed order is **(class, tenant
        over-quota first)**: the victim is the lowest-deadline-class
        request among the router queue PLUS the incoming one, with an
        over-fair-share tenant's requests first within a class and the
        newest arrival as the deterministic tiebreak — so one tenant's
        batch flood spends its own shed budget, and a higher-class
        arrival displaces a queued lower-class request instead of
        being dropped. When the incoming request IS the victim, the
        pre-existing class policy applies: batch sheds terminally,
        interactive/standard shed only in fleet-loss mode (``degrade``)
        and otherwise raise backpressure. ``force_queue`` — a
        voluntary drain rehoming its backlog — always queues: an
        operator's ``scale_to`` must never terminate traffic."""
        if force_queue or len(self.queue) < self.max_queue:
            h.slot = None
            h.status = "queued"
            h.queued_at = self.obs.now()
            self.queue.append(h)
            return
        counts = self._tenant_counts()
        hkey = (h.request.tenant if h.request.tenant is not None
                else "default")
        counts[hkey] = counts.get(hkey, 0) + 1   # the incoming one
        n_tenants = len(counts)
        total = sum(counts.values())

        def over_quota(x: RequestHandle) -> bool:
            if n_tenants <= 1:
                return False
            key = (x.request.tenant if x.request.tenant is not None
                   else "default")
            return counts.get(key, 0) > total / n_tenants + 1e-9

        cands = list(enumerate(self.queue)) + [(len(self.queue), h)]
        victim = max(cands, key=lambda it: (
            _CLASS_RANK[deadline_class(it[1].request)],
            over_quota(it[1]), it[0]))[1]
        if victim is not h:
            self.queue.remove(victim)
            self._shed(victim, "displaced: router and fleet queues "
                               f"saturated and a higher-class request "
                               f"({h.request.request_id}) arrived")
            h.slot = None
            h.status = "queued"
            h.queued_at = self.obs.now()
            self.queue.append(h)
            return
        cls = deadline_class(h.request)
        if cls == "batch":
            self._shed(h, "router and fleet queues saturated "
                          "(batch class)")
        elif degrade:
            self._shed(h, "fleet loss: router and fleet queues "
                          f"saturated ({cls} class)")
        else:
            raise QueueFullError(
                f"router queue full ({self.max_queue}) and every "
                "fleet saturated; retry later")

    def _tenant_counts(self) -> Dict[str, int]:
        """In-system request count per tenant (router queue + every
        live fleet's queued/running/SLO-queued) — the fair-share
        denominator the shed order reads."""
        counts: Dict[str, int] = {}

        def bump(x: RequestHandle):
            key = (x.request.tenant if x.request.tenant is not None
                   else "default")
            counts[key] = counts.get(key, 0) + 1

        for x in self.queue:
            bump(x)
        for f in self._live_fleets():
            e = f.engine
            for x in e.sched.queue:
                bump(x)
            for x in e.sched.running():
                bump(x)
            if getattr(e, "slo", None) is not None:
                for x in e.slo.queued_handles():
                    bump(x)
        return counts

    def _shed(self, h: RequestHandle, reason: str) -> None:
        h.status = "shed"
        h.error = ShedError(
            f"request {h.request.request_id} shed: {reason}")
        h.finished_at = self.obs.now()
        h.slot = None
        self.counters["shed_requests"] += 1
        key = (h.request.tenant if h.request.tenant is not None
               else "default")
        self.shed_by_tenant[key] = self.shed_by_tenant.get(key, 0) + 1
        self.obs.event(
            "shed", request_id=h.request.request_id,
            tenant=h.request.tenant,
            deadline_class=deadline_class(h.request))

    # -- health --------------------------------------------------------

    def _strike(self, f: _Fleet, exc) -> None:
        """One post-retry route failure against ``f``. Crossing the
        threshold fails the fleet over — unless it is the last live
        fleet, which keeps serving fail-soft (there is nowhere to move
        its work; the streak keeps counting)."""
        died = f.health.fail(repr(exc))
        if not died or f.dead:
            return
        if self._live_fleets(exclude=f):
            self._failover_fleet(f, f.health.cause, reachable=True)
        else:
            # Sole live fleet: revoke the verdict — a dead-everything
            # router serves nothing, a degraded single fleet still
            # serves (the next strike re-evaluates).
            f.health.dead = False
            f.health.cause = None

    def kill_fleet(self, fleet_id: int, *,
                   reachable: bool = True) -> bool:
        """Operator/chaos verb: declare fleet ``fleet_id`` dead and
        fail its work over. ``reachable=True`` models a fleet whose
        process is up but unhealthy (running sessions park into its
        tier and hop to survivors, resumed token-exact);
        ``reachable=False`` a vanished fleet (sessions re-enter via
        deterministic re-prefill — equally token-exact, slower).
        True iff a live fleet was killed."""
        f = next((x for x in self.fleets if x.id == fleet_id), None)
        if f is None:
            raise ValueError(f"no fleet with id {fleet_id}")
        if f.dead:
            return False
        if not self._live_fleets(exclude=f):
            raise ValueError("cannot kill the last live fleet")
        f.health.declare_dead("operator/chaos kill")
        self._failover_fleet(f, "operator/chaos kill",
                             reachable=reachable)
        return True

    # -- fleet failover ------------------------------------------------

    def _reset_handle(self, h: RequestHandle) -> None:
        """Token-preserving reset for the deterministic re-prefill
        contract on an adoptive fleet (generated-so-far tokens stay;
        every cursor and cache association clears)."""
        h.slot = None
        h.status = "queued"
        h.prompt_pos, h.lane, h.resident = 0, None, 0
        h.chunks = []
        h.resume_key = None
        h.resume_t0 = None
        h.in_flight = 0       # what a lost tick owed is recomputed
        h.queued_at = self.obs.now()

    def _handoff_session(self, victim: _Fleet, h: RequestHandle, *,
                         resume: bool = True) -> bool:
        """Hop one parked session's pinned tier payload from the
        victim to a survivor over the ``fleet_handoff`` op; on success
        the session resumes there through the ordinary tier-resume
        path (token-exact — bit-exact when it was never requantized).
        ``resume=False`` leaves it PARKED on the target instead — a
        caller-parked session is a deliberate suspension, so failover
        moves the payload without overriding the caller's intent (a
        later ``router.resume(h)`` finds it). False → the caller
        falls back to re-prefill."""
        from triton_dist_tpu.resilience import faults, integrity
        from triton_dist_tpu.resilience.watchdog import CommTimeoutError
        from triton_dist_tpu.serving.tiers import TierFullError

        rid = h.request.request_id
        key = ("session", rid)
        entry = victim.engine.tiers.entry(key)
        if entry is None:
            return False
        order, _ = self._route_order(h.request.prompt)
        for target in order:
            if target.engine.tiers is None:
                continue

            def _attempt(t=target, entry=entry):
                with faults.on_op_call("fleet_handoff"):
                    arrays = victim.engine.tiers.get(key)
                    if arrays is None:
                        raise LookupError(key)
                    # The cross-fleet hop is its own corruptible wire;
                    # verify against the entry's producing-edge digest
                    # BEFORE the target put (which forwards that same
                    # digest in meta — the end-to-end check, not a
                    # per-hop re-stamp). A flipped bit raises
                    # IntegrityError; the retry re-fetches from the
                    # victim's still-authoritative entry.
                    staged = integrity.maybe_corrupt(
                        arrays, "fleet_handoff")
                    integrity.verify_payload(
                        staged, entry.meta.get("digest"),
                        boundary="fleet_handoff", key=key)
                    t.engine.tiers.put(key, staged, pages=entry.pages,
                                       pinned=True,
                                       meta=dict(entry.meta))

            try:
                self._run_router_op("fleet_handoff", _attempt)
            except TierFullError:
                continue          # pinned-full target: next survivor
            except LookupError:
                return False
            except (CommTimeoutError, faults.InjectedFault,
                    integrity.IntegrityError) as e:
                if isinstance(e, CommTimeoutError):
                    self.counters["comm_timeouts"] += 1
                if isinstance(e, integrity.IntegrityError):
                    # Never hand corrupt bytes to the target fleet —
                    # count the detection and fall back to the
                    # deterministic re-prefill (token-exact).
                    self.counters["integrity_failures"] = (
                        self.counters.get("integrity_failures", 0) + 1)
                    self.obs.complete_span(
                        "integrity_check", self.obs.now(),
                        boundary="fleet_handoff", ok=False,
                        request_id=rid)
                self.obs.event("fleet_handoff_failed",
                               request_id=rid, fleet=target.id,
                               error=type(e).__name__)
                return False      # re-prefill: still token-exact
            victim.engine.tiers.pop(key, None)
            target.engine._parked[rid] = h
            if resume:
                target.engine.resume(h)
            return True
        return False

    def _failover_fleet(self, victim: _Fleet, cause,
                        reachable: bool = True) -> None:
        """Rehome a dead fleet's work on the survivors (module
        docstring: parked-tier handoff for running sessions on a
        reachable victim, deterministic re-prefill otherwise; queued
        requests move token-preserving, interactive class placed
        before batch — the shed order under saturation). Sessions the
        CALLER parked stay parked: a reachable handoff moves the
        payload and re-registers without resuming; only an
        unreachable victim (payload lost) forces them through
        re-prefill, where re-entering is the sole way to preserve the
        session at all."""
        t0 = self.obs.now()
        victim.dead = True
        self.counters["fleet_failovers"] += 1
        preparked = set(victim.engine._parked)
        # 0. A tick the victim launched and had not landed: a reachable
        # victim lands it (its requests move with every token they are
        # owed); a dead one's is lost with its device, and what it owed
        # is recomputed by the re-prefill like any other token.
        if reachable:
            victim.engine._land()
        else:
            victim.engine._flight = None
        # 1. On a reachable victim, park every running session with
        # tokens into ITS tier — the two-phase offload: a faulted park
        # leaves the session for the re-prefill path below.
        if reachable and victim.engine.tiers is not None:
            for h in list(victim.engine.sched.running()):
                if h.status == "running" and h.tokens:
                    try:
                        victim.engine.park(h)
                    except Exception:  # noqa: BLE001 — fall through
                        pass           # to deterministic re-prefill
        # 2. Collect ownership off the victim wholesale (its pools and
        # mirrors are abandoned — a real dead fleet's memory is gone).
        parked = list(victim.engine._parked.values())
        victim.engine._parked.clear()
        # (A request released by count, its last token lost in flight,
        # held a slot too.)
        inflight = [h for h in (victim.engine.sched.running()
                                + victim.engine.sched.landing)
                    if not h.done]
        victim.engine.sched.slots.clear()
        victim.engine.sched.landing = []
        victim.engine._resuming = []
        queued = [h for h in victim.engine.sched.queue if not h.done]
        victim.engine.sched.queue.clear()
        # 3. Parked sessions hop their tier payload (reachable), else
        # re-prefill.
        resumed = stayed = 0
        reprefill: List[RequestHandle] = []
        for h in parked:
            stay = h.request.request_id in preparked
            if reachable and self._handoff_session(victim, h,
                                                   resume=not stay):
                if stay:
                    stayed += 1
                else:
                    resumed += 1
            else:
                reprefill.append(h)
        reprefill.extend(inflight)
        for h in reprefill:
            self._reset_handle(h)
        # 4. Placement: in-flight work at the HEAD (it held slots),
        # then the queued backlog — interactive before batch, so any
        # shedding under saturation hits the batch class first.
        for h in reversed(reprefill):
            self._route(h, head=True, degrade=True)
        for h in sorted(queued,
                        key=lambda x: x.request.deadline is None):
            self._route(h, degrade=True)
        self.counters["failover_resumed"] += resumed
        self.counters["failover_reprefilled"] += len(reprefill)
        self.obs.complete_span(
            "fleet_failover", t0, fleet=victim.id,
            cause=str(cause)[:120], reachable=reachable,
            resumed=resumed, stayed_parked=stayed,
            reprefilled=len(reprefill), requeued=len(queued))

    # -- drain / restore autoscale -------------------------------------

    def scale_to(self, n: int, *,
                 max_drain_steps: int = 2000) -> List[dict]:
        """Autoscale to ``n`` live fleets. Growing builds fresh fleets
        from the factory; shrinking drains the highest-id live fleets
        (stop admitting → park or finish in-flight → ``checkpoint()``
        incl. the tier snapshot) and restores their parked sessions
        onto the remaining topology FROM THE SNAPSHOT, live handles
        reattached. Returns the drain snapshots (empty on scale-up) —
        the durable record a preemptible deployment would persist."""
        if n < 1:
            raise ValueError(f"scale_to needs n >= 1, got {n}")
        snaps: List[dict] = []
        live = self._live_fleets()
        if n > len(live):
            for _ in range(n - len(live)):
                with self.obs.span("restore_fleet", fresh=True):
                    self.fleets.append(self._make_fleet(self.factory()))
                self.counters["scale_ups"] += 1
        elif n < len(live):
            for victim in live[n:]:
                snaps.append(self._drain_fleet(
                    victim, max_drain_steps=max_drain_steps))
                self.counters["scale_downs"] += 1
        return snaps

    def _drain_fleet(self, victim: _Fleet, *,
                     max_drain_steps: int) -> dict:
        """Drain one fleet: no new admissions (the drain gate — the
        invariant sweep asserts its queue stays empty), queued backlog
        rehomed up front, running sessions parked (tiers) or finished
        (no tiers), then the checkpoint+tier snapshot, then the
        restore onto the survivors."""
        t0 = self.obs.now()
        victim.draining = True
        preparked = set(victim.engine._parked)
        queued = list(victim.engine.sched.queue)
        victim.engine.sched.queue.clear()
        # force_queue: a voluntary drain must never shed — saturated
        # survivors push the backlog into the router queue instead
        # (bounded by the victim's own backlog, host-side only).
        for h in sorted(queued,
                        key=lambda x: x.request.deadline is None):
            self._route(h, force_queue=True)
        for _ in range(max_drain_steps):
            if victim.engine.tiers is not None:
                for h in list(victim.engine.sched.running()):
                    if h.status == "running" and h.tokens:
                        try:
                            victim.engine.park(h)
                        except Exception:  # noqa: BLE001 — keep
                            pass           # stepping; finishes instead
            if victim.engine._drained():
                break
            victim.engine.step()
        else:
            raise RuntimeError(
                f"fleet {victim.id} did not drain within "
                f"{max_drain_steps} steps "
                f"(slots={sorted(victim.engine.sched.slots)})")
        snap = victim.engine.checkpoint()
        parked_live = dict(victim.engine._parked)
        victim.engine._parked.clear()
        victim.dead = True
        victim.draining = False
        victim.health.declare_dead("drained (scale_to)")
        self.obs.complete_span("drain", t0, fleet=victim.id,
                               parked=len(parked_live),
                               requeued=len(queued))
        with self.obs.span("restore_fleet", fleet=victim.id,
                           fresh=False):
            self._restore_parked(snap, parked_live, preparked)
        return snap

    def _restore_parked(self, snap: dict,
                        parked_live: Dict[str, RequestHandle],
                        preparked: set) -> None:
        """Reattach a drained fleet's parked sessions on the new
        topology — payloads come FROM THE SNAPSHOT (the durable
        artifact), not the defunct store, proving the checkpoint path
        carries everything a restore needs. Sessions in ``preparked``
        (caller-parked BEFORE the drain, vs parked BY the drain loop)
        land parked — the drain preserves the suspension; a later
        ``router.resume(h)`` reactivates them."""
        from triton_dist_tpu.resilience import faults
        from triton_dist_tpu.resilience.watchdog import CommTimeoutError
        from triton_dist_tpu.serving.tiers import TierFullError

        t_snap = snap.get("tiers") or {"host": [], "disk": []}
        entries = {tuple(d["key"]): d
                   for d in list(t_snap["host"]) + list(t_snap["disk"])}
        for rid, h in parked_live.items():
            d = entries.get(("session", rid))
            placed = False
            if d is not None:
                order, _ = self._route_order(h.request.prompt)
                for target in order:
                    if target.engine.tiers is None:
                        continue

                    def _attempt(t=target, d=d):
                        with faults.on_op_call("fleet_handoff"):
                            t.engine.tiers.put(
                                tuple(d["key"]), tuple(d["arrays"]),
                                pages=d["pages"], pinned=True,
                                meta=dict(d["meta"]))

                    try:
                        self._run_router_op("fleet_handoff", _attempt)
                    except TierFullError:
                        continue
                    except (CommTimeoutError, faults.InjectedFault):
                        break             # re-prefill below
                    target.engine._parked[rid] = h
                    if rid not in preparked:
                        target.engine.resume(h)
                        self.counters["drain_resumed"] += 1
                    placed = True
                    break
            if not placed:
                # Voluntary drain: re-prefill must not shed either —
                # the router queue absorbs what no survivor admits.
                self._reset_handle(h)
                self._route(h, head=True, force_queue=True)
                self.counters["drain_reprefilled"] += 1

    # -- the serving loop ----------------------------------------------

    def step(self) -> int:
        """One router tick: retry the router-queue backlog, then step
        every live fleet once (its own admission → prefill → decode
        pipeline). Beats each fleet's health on a completed tick.
        Returns total live slots decoded."""
        if self.queue:
            pending = list(self.queue)
            self.queue.clear()
            for h in pending:
                if not h.done:
                    self._route(h, requeue_only=True)
        n = 0
        for f in self._live_fleets():
            n += f.engine.step()
            f.health.beat()
        return n

    @property
    def drained(self) -> bool:
        """Nothing left anywhere (parked sessions are deliberate
        suspensions, not drain blockers — same as the engines)."""
        return (not self.queue
                and all(f.engine._drained()
                        for f in self._live_fleets()))

    def run(self, *, max_steps: int = 100000, on_tick=None) -> None:
        """Drive :meth:`step` until every queue and fleet drains."""
        for _ in range(max_steps):
            if self.drained:
                return
            self.step()
            if on_tick is not None:
                on_tick()
        raise RuntimeError(
            f"fleet serving loop did not drain in {max_steps} steps")

    def generate(self, prompts, max_new_tokens: int = 32,
                 **kw) -> List[List[int]]:
        """Batch convenience mirroring ``ServingEngine.generate``."""
        handles = [self.submit(p, max_new_tokens=max_new_tokens, **kw)
                   for p in prompts]
        self.run()
        for h in handles:
            if h.status != "done":
                raise RuntimeError(
                    f"request {h.request.request_id} ended "
                    f"{h.status}: {h.error!r}") from h.error
        return [h.tokens for h in handles]

    # -- park / resume delegation --------------------------------------

    def _fleet_of(self, h: RequestHandle) -> Optional[_Fleet]:
        """The live fleet currently owning ``h`` (queue, slot, or
        parked registry); None when router-queued or terminal."""
        rid = h.request.request_id
        for f in self._live_fleets():
            e = f.engine
            if (rid in e._parked
                    or (h.slot is not None
                        and e.sched.slots.get(h.slot) is h)
                    or any(x is h for x in e.sched.queue)):
                return f
        return None

    def park(self, h: RequestHandle) -> RequestHandle:
        f = self._fleet_of(h)
        if f is None:
            raise ValueError(
                f"request {h.request.request_id} is not running on "
                "any live fleet")
        return f.engine.park(h)

    def resume(self, h: RequestHandle) -> RequestHandle:
        rid = h.request.request_id
        for f in self._live_fleets():
            if rid in f.engine._parked:
                return f.engine.resume(h)
        raise ValueError(f"request {rid} is not parked on any live "
                         "fleet")

    # -- readout -------------------------------------------------------

    def decode_cache_sizes(self) -> List[int]:
        """Per-live-fleet decode jit-cache entries — the fleet-wide
        no-recompilation gate (every entry 1 after warmup)."""
        return [f.engine.decode_cache_size()
                for f in self._live_fleets()]

    def stats(self) -> dict:
        """Router counters + per-fleet summaries + the fleet-wide
        aggregates the bench reads (merged TTFT histogram, aggregate
        hot-set hit rate). Keys are nulled, never omitted."""
        from triton_dist_tpu.obs.hist import LatencyHistogram

        out = dict(self.counters)
        out["queue_depth"] = len(self.queue)
        out["fleets"] = []
        agg = {"completed": 0, "failed": 0, "timed_out": 0}
        # Fleet-wide sums of the per-engine counters the exit
        # summaries and bench read (an engine "failover" here is a
        # PREFILL-ROLE failover inside one fleet; fleet-level ones
        # are ``fleet_failovers`` above).
        agg_eng = {k: 0 for k in (
            "tokens_generated", "decode_dispatches", "retries",
            "failovers", "restored_requests", "offloaded_pages",
            "prefetched_pages", "tier_hits", "tier_misses",
            "parks", "resumes", "slo_preemptions")}
        parked_sessions = 0
        tier_pages = 0
        any_tiers = False
        hits = misses = 0
        merged: Optional[LatencyHistogram] = None
        seen_obs = set()
        for f in self.fleets:
            e = f.engine
            out["fleets"].append({
                "id": f.id, "dead": f.dead, "draining": f.draining,
                "queue_depth": len(e.sched.queue),
                "live_slots": len(e.sched.slots),
                "parked": len(e._parked),
                "completed": e.sched.counters["completed"],
                "health_failures": f.health.total_failures,
            })
            for k in agg:
                agg[k] += e.sched.counters.get(k, 0)
            for k in agg_eng:
                agg_eng[k] += e.stats_counters.get(k, 0)
            parked_sessions += len(e._parked)
            if e.tiers is not None:
                any_tiers = True
                ts = e.tiers.stats()
                tier_pages += (ts["host_pages_used"]
                               + ts["disk_pages_used"])
            if e.manager is not None:
                hits += e.manager.stats["prefix_hits"]
                misses += e.manager.stats["prefix_misses"]
            # Fleet-wide TTFT: merge per-fleet histograms (engines
            # sharing one Telemetry instance merge once).
            if id(e.obs) in seen_obs:
                continue
            seen_obs.add(id(e.obs))
            hh = e.obs.hist.get("ttft")
            if hh is not None:
                if merged is None:
                    merged = LatencyHistogram()
                merged.merge(hh)
        out.update(agg)
        out.update(agg_eng)
        out["parked_sessions"] = parked_sessions
        out["tier_pages"] = tier_pages if any_tiers else None
        out["live_fleets"] = len(self._live_fleets())
        out["dead_fleets"] = sum(1 for f in self.fleets if f.dead)
        out["router_affinity_hit_rate"] = (
            round(self.counters["affinity_hits"]
                  / self.counters["routed"], 4)
            if self.counters["routed"] else None)
        out["kv_hot_hit_rate"] = (
            round(hits / (hits + misses), 4)
            if hits + misses else None)
        out["fleet_ttft_ms"] = (merged.summary()
                                if merged is not None else None)
        # Multi-tenant SLO aggregation: per-fleet quota views merge
        # into one cross-fleet tenant table + the fleet-wide
        # attainment fraction. Nulled, never omitted, with SLO off.
        out["shed_by_tenant"] = dict(self.shed_by_tenant)
        views = [(f.id, f.engine.slo.stats()) for f in self.fleets
                 if getattr(f.engine, "slo", None) is not None]
        if views:
            met = sum(v["slo_met"] for _, v in views)
            missed = sum(v["slo_missed"] for _, v in views)
            tenants: Dict[str, Dict[str, float]] = {}
            for _, v in views:
                for name, tv in v["tenants"].items():
                    agg_t = tenants.setdefault(name, {k: 0 for k in (
                        "queued", "admitted", "rejected", "released",
                        "preempted", "met", "missed",
                        "charged_tokens")})
                    for k in agg_t:
                        agg_t[k] += tv[k]
            out["slo"] = {
                "fleets": {fid: v for fid, v in views},
                "tenants": tenants,
                "preemptions": sum(v["slo_preemptions"]
                                   for _, v in views),
                "attainment": (met / (met + missed)
                               if (met + missed) else None),
            }
            out["slo_attainment"] = out["slo"]["attainment"]
        else:
            out["slo"] = None
            out["slo_attainment"] = None
        out["latency"] = self.obs.latency_summary()
        return out
