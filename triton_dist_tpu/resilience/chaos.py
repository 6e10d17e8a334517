"""Seeded chaos soak for the serving stack: randomized (but
seed-reproducible) fault schedules over a long mixed-traffic run, with
a full invariant sweep after every tick.

The fault-plan registry (:mod:`~triton_dist_tpu.resilience.faults`)
makes single failures injectable; this module composes them into a
SOAK — the test shape production incidents actually have: transients
and hard faults arriving at random points of a live workload, workers
dying mid-stream, the process checkpointing and restarting in the
middle. One ``seed`` fixes the arrival trace, every fault's tick and
kind, and every retry-backoff jitter, so a failing soak replays
bit-for-bit.

What a passing soak proves (the checker raises
:class:`InvariantViolation` otherwise):

- **no leaked pages** — every page is free xor referenced, refcounts
  equal the observable holders (slot lists + the prefix cache's own
  ref), free list has no duplicates, the scratch page is never
  allocated;
- **prefix publication is sound** — committed (published) entries are
  content-resident by construction of the two-phase protocol, and no
  page is simultaneously staged and published;
- **host mirrors cohere** — slot/handle bijection, live mask, and the
  length mirrors agree with the allocator's token accounting (up to
  the bounded skew a failed tick's idempotent pre-append leaves);
- **every submitted request terminally resolves** — done, failed, or
  timeout; nothing wedges or leaks a slot;
- **survivors are token-exact** — every ``done`` request's tokens
  equal the fault-free oracle (``Engine.serve`` on the same weights).

Usage (the tier-1 subset in ``tests/test_chaos.py`` and the
``chaos_survived_faults`` bench key both drive this)::

    from triton_dist_tpu.resilience import chaos
    report = chaos.run_soak(make_engine, seed=7, ticks=200,
                            n_faults=12, restore_at=90)
    assert report.survived_faults >= 10
"""

from __future__ import annotations

import dataclasses
import time
from collections import Counter
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from triton_dist_tpu.resilience import faults

__all__ = ["ChaosEvent", "ChaosReport", "FleetChaosReport",
           "SupervisedChaosReport", "InvariantViolation",
           "DEFAULT_FAULT_KINDS", "TIER_FAULT_KINDS",
           "FLEET_FAULT_KINDS", "MK_FAULT_KINDS",
           "INTEGRITY_FAULT_KINDS", "SUPERVISED_FAULT_KINDS",
           "check_invariants", "check_fleet_invariants",
           "run_soak", "run_fleet_soak", "run_integrity_drill",
           "run_supervised_soak", "supervised_tiny_factory"]


class InvariantViolation(AssertionError):
    """A serving invariant broke under the soak — the bug class this
    harness exists to catch (leaked page, drifted refcount, corrupted
    mirror, unresolved request, token divergence)."""


# (name, op, fault_kind): the injectable menu. ``fail_call`` models a
# dropped transfer/dispatch; ``timeout_call`` a wedged one (the
# deterministic watchdog-miss stand-in — see faults.py); transient
# events target only the FIRST call of the tick (k=0: absorbed by one
# retry), hard events every call of the tick (k=None: retries exhaust,
# containment/failover takes over). ``kill_prefill_worker`` is the
# dead-role event (DisaggServingEngine.fail_prefill_worker).
DEFAULT_FAULT_KINDS: Tuple[Tuple[str, Optional[str], Optional[str]],
                           ...] = (
    ("drop_migration", "page_migration", "fail_call"),
    ("wedge_migration", "page_migration", "timeout_call"),
    ("drop_chunk", "chunked_prefill", "fail_call"),
    ("delay_chunk", "chunked_prefill", "timeout_call"),
    ("drop_decode", "serving_decode", "fail_call"),
    ("wedge_decode", "serving_decode", "timeout_call"),
    ("kill_prefill_worker", None, None),
)

# The tiered-KV additions (engines built with ``kv_tiers``): dropped /
# wedged tier transfers — a faulted demote drops the (recomputable)
# prefix content, a faulted prefetch falls back to recompute, a
# faulted park leaves the request running, a faulted resume re-enters
# via the deterministic re-prefill; all token-exact by construction.
# Kept separate so un-tiered soaks (and their seeded schedules) stay
# byte-identical; pass ``kinds=DEFAULT_FAULT_KINDS + TIER_FAULT_KINDS``
# for a tiered engine.
TIER_FAULT_KINDS: Tuple[Tuple[str, Optional[str], Optional[str]],
                        ...] = (
    ("drop_tier_transfer", "tier_transfer", "fail_call"),
    ("wedge_tier_transfer", "tier_transfer", "timeout_call"),
)

# The megakernel-lane menu (``run_soak`` over a paged
# ``MegaKernelEngine`` serving factory): the persistent lane has no
# migration/chunk/worker ops, so only the joint decode dispatch (the
# prefill LANE rides it too) is injectable — dropped and wedged
# decode/verification launches. Kept separate so layer-path soaks'
# seeded schedules stay byte-identical.
MK_FAULT_KINDS: Tuple[Tuple[str, Optional[str], Optional[str]],
                      ...] = (
    ("drop_decode", "serving_decode", "fail_call"),
    ("wedge_decode", "serving_decode", "timeout_call"),
    ("drop_verify", "spec_verify", "fail_call"),
    ("wedge_verify", "spec_verify", "timeout_call"),
)

# The fleet-level menu (``run_fleet_soak`` over a ``FleetRouter``):
# dropped / wedged router→fleet links (``fleet_route`` — the send that
# places a request on a fleet's queue), dropped / wedged cross-fleet
# session handoffs (``fleet_handoff`` — the parked-payload hop during
# failover and drain/restore), and whole-fleet kills — a seeded coin
# picks reachable (parked-tier handoff path) vs vanished (deterministic
# re-prefill path). Kept separate so ``run_soak``'s seeded schedules
# stay byte-identical.
FLEET_FAULT_KINDS: Tuple[Tuple[str, Optional[str], Optional[str]],
                         ...] = (
    ("kill_fleet", None, None),
    ("drop_route", "fleet_route", "fail_call"),
    ("wedge_route", "fleet_route", "timeout_call"),
    ("drop_handoff", "fleet_handoff", "fail_call"),
    ("wedge_handoff", "fleet_handoff", "timeout_call"),
)

# The payload-integrity menu (ISSUE 16): a seeded single-bit flip on
# the payload crossing each serialization boundary, detected by the
# crc32c digest check at the consuming edge (never by luck) and routed
# into that boundary's existing recovery path — tier get quarantines
# the entry and recomputes, a corrupted migration retries then
# re-prefills, a corrupted handoff hop retries against the victim's
# still-authoritative entry then re-prefills. Transient events (k=0)
# corrupt only the first attempt; hard ones (k=None) every attempt.
# Kept separate so existing soaks' seeded schedules stay
# byte-identical; compose per engine shape (tier kinds need
# ``kv_tiers``, handoff kinds a :class:`FleetRouter`).
INTEGRITY_FAULT_KINDS: Tuple[Tuple[str, Optional[str], Optional[str]],
                             ...] = (
    ("corrupt_tier_transfer", "tier_transfer", "corrupt_payload"),
    ("corrupt_migration", "page_migration", "corrupt_payload"),
    ("corrupt_handoff", "fleet_handoff", "corrupt_payload"),
)

# The process-level menu (``run_supervised_soak`` over a
# :class:`~triton_dist_tpu.resilience.supervisor.ServingSupervisor`):
# events fire at seeded ACK-COUNT thresholds (real child processes
# make tick counts nondeterministic; the acked-token stream is the
# deterministic clock the parent actually observes). ``kill_child``
# is a parent-side SIGKILL (the OOM-killer model), ``crash_child`` an
# in-child ``os._exit`` (the segfault model — exercises the nonzero
# exit path), ``stall_child`` a heartbeat stall (wedged thread),
# ``corrupt_migration`` a one-tick in-child payload corruption.
SUPERVISED_FAULT_KINDS: Tuple[Tuple[str, Optional[str],
                                    Optional[str]], ...] = (
    ("kill_child", None, None),
    ("crash_child", None, None),
    ("stall_child", None, None),
    ("corrupt_migration", "page_migration", "corrupt_payload"),
)


@dataclasses.dataclass
class ChaosEvent:
    """One scheduled fault: where, what, and what it observably did.

    ``at`` is the serving engine's clock reading when the fault fired
    (None until then) — soak runs are trace-inspectable: the same
    timestamp domain the request spans and retry events use, so a
    fault lines up against its victims in the merged timeline."""

    tick: int
    name: str
    op: Optional[str]
    kind: Optional[str]       # fail_call | timeout_call | None (kill)
    transient: bool
    fired: bool = False       # the fault had a chance to act this tick
    observed: bool = False    # a failure/retry counter moved this tick
    at: Optional[float] = None  # engine-clock stamp when fired


@dataclasses.dataclass
class ChaosReport:
    """What a completed soak measured (a completed soak already means:
    server alive, invariants held every tick, all requests terminal,
    survivors token-exact — violations raise instead)."""

    seed: int
    ticks: int
    events: List[ChaosEvent]
    faults_injected: int
    survived_faults: int
    requests: Dict[str, int]
    counters: Dict[str, int]
    invariant_checks: int
    token_exact_requests: int
    restored_at: Optional[int]


@dataclasses.dataclass
class FleetChaosReport:
    """What a completed fleet soak measured (completion already means:
    router alive, per-tick fleet invariants held, every request
    terminal, done requests token-exact vs the single-engine oracle).
    ``requests`` adds the ``shed`` class; ``router`` is the final
    router counter dict (failovers, handoff resumes, sheds...)."""

    seed: int
    ticks: int
    fleets: int
    events: List[ChaosEvent]
    faults_injected: int
    survived_faults: int
    requests: Dict[str, int]
    router: Dict[str, int]
    invariant_checks: int
    token_exact_requests: int
    scaled_at: Optional[int]


@dataclasses.dataclass
class SupervisedChaosReport:
    """What a completed supervised soak measured (completion already
    means: every request ``done`` and token-exact vs the in-process
    oracle across every child kill/stall/corruption — violations
    raise).  ``supervisor`` is the parent's final counter view
    (restarts, crashes, stalls, dedup_dropped, restore_fallbacks,
    acked_tokens, last_recovery_ms...)."""

    seed: int
    events: List["ChaosEvent"]
    faults_injected: int
    survived_faults: int
    requests: Dict[str, int]
    supervisor: Dict[str, object]
    token_exact_requests: int


# ---------------------------------------------------------------------------
# Invariant checker
# ---------------------------------------------------------------------------

def _check_manager(mgr, name: str) -> None:
    from triton_dist_tpu.serving.blocks import SCRATCH_PAGE

    free = list(mgr._free)
    if len(set(free)) != len(free):
        raise InvariantViolation(
            f"[{name}] duplicate page ids on the free list: {free}")
    if SCRATCH_PAGE in free:
        raise InvariantViolation(
            f"[{name}] the reserved scratch page leaked into the free "
            "list")
    held = Counter(pid for pages in mgr._slot_pages.values()
                   for pid in pages)
    if SCRATCH_PAGE in held:
        raise InvariantViolation(
            f"[{name}] the scratch page was allocated to a slot")
    prefix_pids = set(mgr._prefix.values())
    free_set = set(free)
    for pid in range(1, mgr.num_pages):
        want = held.get(pid, 0) + (1 if pid in prefix_pids else 0)
        have = mgr._refs.get(pid, 0)
        if have != want:
            raise InvariantViolation(
                f"[{name}] page {pid} refcount {have} != observable "
                f"holders {want} (slots={held.get(pid, 0)}, "
                f"prefix={pid in prefix_pids})")
        if (pid in free_set) == (want > 0):
            raise InvariantViolation(
                f"[{name}] page {pid} {'free but referenced' if want else 'unreferenced but not free — LEAKED'}")
    if len(free) + len(mgr._refs) != mgr.num_pages - 1:
        raise InvariantViolation(
            f"[{name}] page accounting broke: {len(free)} free + "
            f"{len(mgr._refs)} referenced != {mgr.num_pages - 1} "
            "usable pages")
    staged = {pid for pairs in mgr._pending_prefix.values()
              for _, pid in pairs}
    if staged & prefix_pids:
        raise InvariantViolation(
            f"[{name}] page(s) {staged & prefix_pids} both staged and "
            "published — the two-phase prefix protocol broke")
    for slot, pairs in mgr._pending_prefix.items():
        owned = set(mgr._slot_pages.get(slot, []))
        for _, pid in pairs:
            if pid not in owned:
                raise InvariantViolation(
                    f"[{name}] staged prefix page {pid} not owned by "
                    f"its staging slot {slot}")
    for slot, n_tok in mgr._slot_tokens.items():
        cap = len(mgr._slot_pages.get(slot, [])) * mgr.page
        if n_tok > cap:
            raise InvariantViolation(
                f"[{name}] slot {slot} accounts {n_tok} tokens over "
                f"{cap} allocated-page capacity")


def check_invariants(srv) -> None:
    """One full sweep of the serving invariants (see module
    docstring). Call between ticks — the structures are host-side, so
    this never syncs the device."""
    if srv.manager is not None:
        _check_manager(srv.manager, "decode-pool")
    workers = getattr(srv, "prefill_workers", None) or []
    for i, w in enumerate(workers):
        if not w.dead and w.manager is not srv.manager:
            _check_manager(w.manager, f"prefill-pool[{i}]")
    spec_slack = max(1, getattr(srv, "spec_k", 0) or 0)
    for s in range(srv.num_slots):
        h = srv.sched.slots.get(s)
        if h is None:
            if srv._live[s] != 0:
                raise InvariantViolation(
                    f"slot {s} live={srv._live[s]} with no handle")
            continue
        if h.slot != s:
            raise InvariantViolation(
                f"slot {s} handle claims slot {h.slot}")
        if h.status == "running":
            if srv._live[s] != 1:
                raise InvariantViolation(
                    f"running slot {s} has live={srv._live[s]}")
            # Tokens a launched tick owes the request have moved the
            # mirror on already (docs/serving.md, "The tick in two
            # halves").
            want = (len(h.request.prompt) + len(h.tokens)
                    + h.in_flight - 1)
            if srv._lens[s] != want:
                raise InvariantViolation(
                    f"slot {s} length mirror {srv._lens[s]} != "
                    f"prompt+generated-fed {want}")
            if srv.manager is not None:
                n = srv.manager._slot_tokens.get(s)
                if n is None or not (srv._lens[s] <= n
                                     <= srv._lens[s] + spec_slack):
                    raise InvariantViolation(
                        f"slot {s} allocator tokens {n} drifted from "
                        f"length mirror {srv._lens[s]} (allowed slack "
                        f"{spec_slack})")
        elif h.status in ("prefill", "migrating", "resuming"):
            if srv._live[s] != 0 and not srv.mega:
                raise InvariantViolation(
                    f"parked ({h.status}) slot {s} is marked live")
        else:
            raise InvariantViolation(
                f"slot {s} holds a terminal handle ({h.status})")
    for h in srv.sched.queue:
        if h.slot is not None:
            raise InvariantViolation(
                f"queued request {h.request.request_id} still holds "
                f"slot {h.slot}")
    _check_tiers(srv)
    _check_arena(srv)
    _check_slo(srv)


def _check_slo(srv) -> None:
    """Tenant-fairness sweep (engines built with ``slo=...``):

    - **single ownership**: a tenant-queued handle is ``"queued"``,
      holds no slot, and is never simultaneously in the scheduler
      queue or a slot (the relocation in ``SLOScheduler.submit`` /
      ``pump`` must move, not copy);
    - **bounded queues**: each tenant queue within its spec's
      ``max_queue``;
    - **bucket sanity**: the admission token bucket stays inside
      [0, burst];
    - **quota conservation**: ``tokens == granted - charged`` — the
      decode-quota bucket algebra neither mints nor leaks quota;
    - **no starvation under aging**: no quota-eligible queued handle
      has waited beyond ``slo.starve_limit_s`` (aging promotes it to
      the interactive rank long before that);
    - **preemption debt**: every park-path preemptee is still parked
      (and in the engine's parked registry) — it WILL be auto-resumed,
      so "preempted requests always reach a terminal status" holds.
    """
    slo = getattr(srv, "slo", None)
    if slo is None:
        return
    in_sched = {id(h) for h in srv.sched.queue}
    in_slots = {id(h) for h in srv.sched.slots.values()}
    now = srv.sched.now()
    for st in slo.registry.states():
        name = st.spec.name
        if len(st.queue) > st.spec.max_queue:
            raise InvariantViolation(
                f"tenant {name!r} queue {len(st.queue)} over its "
                f"bound {st.spec.max_queue}")
        if not (-1e-9 <= st.bucket <= st.spec.burst + 1e-9):
            raise InvariantViolation(
                f"tenant {name!r} admission bucket {st.bucket} left "
                f"[0, {st.spec.burst}]")
        if st.spec.decode_quota is not None:
            if abs(st.tokens - (st.granted - st.charged)) > 1e-6:
                raise InvariantViolation(
                    f"tenant {name!r} quota not conserved: bucket "
                    f"{st.tokens} != granted {st.granted} - charged "
                    f"{st.charged}")
            if st.tokens > st.quota_burst + 1e-9:
                raise InvariantViolation(
                    f"tenant {name!r} quota bucket {st.tokens} over "
                    f"its depth {st.quota_burst}")
        for h in st.queue:
            rid = h.request.request_id
            if h.status != "queued" or h.slot is not None:
                raise InvariantViolation(
                    f"tenant-queued request {rid} is {h.status!r} "
                    f"with slot {h.slot}")
            if id(h) in in_sched or id(h) in in_slots:
                raise InvariantViolation(
                    f"request {rid} owned by tenant {name!r} queue "
                    "AND the scheduler (dual ownership)")
            if st.quota_ok() and (now - h.queued_at
                                  > slo.starve_limit_s):
                raise InvariantViolation(
                    f"request {rid} (tenant {name!r}) starved: queued "
                    f"{now - h.queued_at:.3f}s > starve limit "
                    f"{slo.starve_limit_s}s with quota available")
    for h in slo._parked_by_slo:
        rid = h.request.request_id
        if h.status != "parked" or rid not in srv._parked:
            raise InvariantViolation(
                f"SLO-preempted request {rid} lost its park "
                f"(status={h.status!r}) — the auto-resume debt broke")


def _check_arena(srv) -> None:
    """Arena-coherence sweep (megakernel engines): the described
    memory layout must stay sound under faults —

    - **region disjointness**: the arena schema's in-arena regions
      tile [0, rows) with no overlap/gap (``ArenaSchema
      .check_disjoint``). The schema is build-time-frozen, so this
      half re-asserts a static invariant — it exists to catch a
      FUTURE builder change that starts mutating layouts at serve
      time, not a runtime fault (cheap: pure host arithmetic);
    - **scale/page consistency** (quantized pools): every
      per-(layer, page, kv_head) dequant scale is finite and > 0
      (write_kv's running-amax maintenance can never produce 0 or a
      NaN — either would silently zero or poison a page's dequant);
    - **monotonic counters**: the in-arena MoE router counters only
      ever grow between sweeps (the epilogue accumulates; a decrease
      means a clobbered counter region).
    """
    if not getattr(srv, "mega", False):
        return
    eng = srv.engine
    for b in (eng.builder, getattr(eng, "verify_builder", None)):
        if b is None:
            continue
        try:
            b.schema.check_disjoint()
        except ValueError as e:
            raise InvariantViolation(f"arena schema broke: {e}") from e
    if getattr(eng, "k_scale", None) is not None:
        for name in ("k_scale", "v_scale"):
            a = np.asarray(getattr(eng, name))
            if not np.isfinite(a).all() or (a <= 0).any():
                raise InvariantViolation(
                    f"quantized pool {name} left the sane range "
                    f"(finite, > 0): min={a.min()}, "
                    f"finite={np.isfinite(a).all()}")
    if getattr(srv.cfg, "is_moe", False) and hasattr(eng,
                                                     "expert_counts"):
        counts = eng.expert_counts()
        prev = getattr(srv, "_mk_counts_sweep", None)
        if prev is not None and (counts < prev).any():
            raise InvariantViolation(
                f"megakernel expert counters went BACKWARDS: "
                f"{prev.tolist()} -> {counts.tolist()}")
        srv._mk_counts_sweep = counts


def _check_tiers(srv) -> None:
    """Tier-coherence sweep (engines built with ``kv_tiers``): every
    payload lives in exactly ONE authoritative tier, no HBM free-list
    entry is backed by a pending (uncommitted) demotion, and the
    parked registry and tier store agree."""
    tiers = getattr(srv, "tiers", None)
    if tiers is None:
        return
    try:
        # Staged-demotion window empty between ticks + host/disk
        # disjoint + capacity bounds (the store's own algebra).
        tiers.check_coherence()
    except AssertionError as e:
        raise InvariantViolation(str(e)) from e
    # Exactly-one-tier across the hierarchy: a key committed in the
    # HBM prefix cache must not ALSO be tier-resident (demotion pops
    # it from HBM, promotion pops it from the tier).
    if srv.manager is not None:
        hbm_keys = set(srv.manager._prefix)
        for k in tiers.keys():
            k = tuple(k)
            if k[0] == "prefix" and k[1] in hbm_keys:
                raise InvariantViolation(
                    f"prefix key resident in BOTH the HBM cache and "
                    f"the tier store: {k[1]!r}")
    parked = getattr(srv, "_parked", {})
    for rid, h in parked.items():
        if h.status != "parked" or h.slot is not None:
            raise InvariantViolation(
                f"parked registry holds request {rid} in state "
                f"{h.status!r} (slot={h.slot})")
        if ("session", rid) not in tiers:
            raise InvariantViolation(
                f"parked request {rid} has no tier payload — its KV "
                "is unrecoverable")
        if h in srv.sched.queue:
            raise InvariantViolation(
                f"parked request {rid} is also queued")
    for k in tiers.keys():
        k = tuple(k)
        if k[0] != "session":
            continue
        e = tiers.entry(k)
        if e.pinned and k[1] not in parked and not any(
                getattr(h, "resume_key", None) == k
                for h in list(srv.sched.queue)
                + list(srv.sched.slots.values())):
            raise InvariantViolation(
                f"pinned session payload {k[1]!r} has no parked or "
                "resuming owner — leaked tier pages")


def check_fleet_invariants(router, tracked=None) -> None:
    """Fleet-level sweep over a :class:`~triton_dist_tpu.serving.
    router.FleetRouter` — the per-fleet :func:`check_invariants` plus
    the cross-fleet algebra:

    - every in-flight request is owned by exactly ONE place (the
      router queue, or one live fleet's queue / slots / parked
      registry) — never two;
    - no session payload is pinned in two fleets' tier stores at once
      (the cross-fleet handoff pops the source before the target
      resumes);
    - the router's health view is consistent with liveness (a fleet
      marked dead carries a dead health verdict; a declared-dead
      health verdict on a live fleet means the failover was skipped);
    - the drain gate holds: a draining fleet admits nothing (its
      queue stays empty);
    - router-queued handles are slotless and non-terminal.

    ``tracked`` (optional handles) must each be terminal or owned
    somewhere.
    """
    seen: Dict[str, str] = {}

    def note(h, where):
        rid = h.request.request_id
        if rid in seen:
            raise InvariantViolation(
                f"request {rid} owned by BOTH {seen[rid]} and {where}")
        seen[rid] = where

    # Cross-fleet session uniqueness first: a payload pinned on two
    # fleets is its own violation class (a handoff that copied
    # without popping), reported before the ownership scan can fold
    # it into a generic double-ownership message.
    session_owner: Dict[tuple, int] = {}
    for f in router.fleets:
        if f.dead or f.engine.tiers is None:
            continue
        for k in f.engine.tiers.keys():
            k = tuple(k)
            if k[0] != "session":
                continue
            if k in session_owner:
                raise InvariantViolation(
                    f"session payload {k[1]!r} pinned on BOTH fleet "
                    f"{session_owner[k]} and fleet {f.id}")
            session_owner[k] = f.id
    for h in router.queue:
        if h.slot is not None:
            raise InvariantViolation(
                f"router-queued request {h.request.request_id} still "
                f"holds slot {h.slot}")
        if h.done:
            raise InvariantViolation(
                f"terminal request {h.request.request_id} "
                f"({h.status}) sits in the router queue")
        note(h, "router-queue")
    for f in router.fleets:
        if f.dead:
            if not f.health.dead:
                raise InvariantViolation(
                    f"fleet {f.id} marked dead without a dead health "
                    "verdict")
            continue
        if f.health.dead:
            raise InvariantViolation(
                f"fleet {f.id} health declared dead "
                f"({f.health.cause!r}) but the router still routes to "
                "it — failover skipped")
        check_invariants(f.engine)
        if f.draining and f.engine.sched.queue:
            raise InvariantViolation(
                f"draining fleet {f.id} admitted new work (drain gate "
                f"broke): queue={[h.request.request_id for h in f.engine.sched.queue]}")
        for h in f.engine.sched.queue:
            note(h, f"fleet{f.id}-queue")
        for h in f.engine.sched.slots.values():
            note(h, f"fleet{f.id}-slot")
        for h in f.engine._parked.values():
            note(h, f"fleet{f.id}-parked")
        if getattr(f.engine, "slo", None) is not None:
            for h in f.engine.slo.queued_handles():
                note(h, f"fleet{f.id}-slo-queue")
    for h in tracked or ():
        if not h.done and h.request.request_id not in seen:
            raise InvariantViolation(
                f"in-flight request {h.request.request_id} "
                f"({h.status}) owned by NO fleet and not router-"
                "queued — lost")


# ---------------------------------------------------------------------------
# The soak
# ---------------------------------------------------------------------------

def _oracle_tokens(engine, prompt: Sequence[int], gen_len: int,
                   cache: Dict) -> List[int]:
    import jax.numpy as jnp

    key = (tuple(prompt), gen_len)
    if key not in cache:
        n = engine.mesh.shape[engine.axis]
        ids = np.tile(np.asarray([list(prompt)], np.int32), (n, 1))
        cache[key] = np.asarray(
            engine.serve(jnp.asarray(ids),
                         gen_len=gen_len))[0].tolist()
    return cache[key]


def _note_fault(srv, ev: ChaosEvent) -> None:
    """Land the injected fault in the engine's telemetry event log —
    the soak's faults and the serving spans share ONE timeline, so a
    retry burst or a failover reads directly against the fault that
    caused it."""
    srv.obs.event("chaos_fault", tick=ev.tick, name=ev.name,
                  op=ev.op, fault_kind=ev.kind,
                  transient=ev.transient)


def _plan_for(ev: ChaosEvent) -> faults.FaultPlan:
    k = 0 if ev.transient else None
    return faults.FaultPlan(
        name=f"chaos-{ev.name}",
        faults=(faults.Fault(ev.kind, op=ev.op, k=k),))


def run_soak(factory: Callable[[], object], *, seed: int = 0,
             ticks: int = 200, n_faults: int = 10,
             arrival_p: float = 0.35,
             kinds: Sequence = DEFAULT_FAULT_KINDS,
             transient_p: float = 0.5,
             gen_choices: Sequence[int] = (2, 3, 4, 6, 8),
             prompt_reuse_p: float = 0.3,
             restore_at: Optional[int] = None,
             max_drain_steps: Optional[int] = None,
             park_p: float = 0.0,
             tenants: Sequence[str] = ()) -> ChaosReport:
    """Drive ``ticks`` serving steps of seeded mixed traffic under
    ``n_faults`` seeded fault events, checking every invariant after
    every tick, then drain fault-free and verify terminal resolution +
    token-exactness of all survivors against the fault-free oracle.

    ``factory`` builds the serving engine (a fresh, identically-
    configured one each call — ``restore_at`` uses it again for the
    mid-soak kill/checkpoint/restore drill). Greedy traffic only (the
    exactness oracle is ``Engine.serve``; megakernel factories get a
    fresh fault-free serving engine instead — pass
    ``kinds=MK_FAULT_KINDS`` there, and the per-tick sweep adds the
    arena-coherence check). Raises
    :class:`InvariantViolation` (or the server's own crash) on any
    violation; returns a :class:`ChaosReport` otherwise.

    ``park_p`` > 0 (engines built with ``kv_tiers``) additionally
    parks a seeded-random running request with that per-tick
    probability and resumes it 1–4 ticks later — resumed sessions
    flow through the same token-exactness gate as everything else, so
    a park/resume byte drift fails the soak. Anything still parked
    when the soak ends resumes before the drain.

    ``tenants`` non-empty labels each submission with a seeded-random
    tenant from the list and a seeded-random ``slo_class`` — the
    multi-tenant soak mode for engines built with ``slo=...`` (the
    per-tick sweep then exercises the tenant-fairness invariants:
    quota conservation, bounded queues, no starvation, preemption
    debt). The extra rng draws are gated on the parameter, so a
    ``tenants=()`` soak's schedule stays byte-identical to the
    pre-SLO soaks. Greedy decoding means scheduling order never
    changes tokens — the oracle gate is unchanged.
    """
    rng = np.random.RandomState(seed)
    srv = factory()
    # Megakernel engines soak too (pass kinds=MK_FAULT_KINDS — the
    # persistent lane has no migration/chunk ops): the oracle is a
    # fresh fault-free serving engine from the same factory (the mk
    # engine has no Engine.serve), and the per-tick sweep additionally
    # runs the arena-coherence check (_check_arena).
    mk_oracle = {"srv": None} if srv.mega else None
    vocab = srv.cfg.vocab_size
    cap = min(srv.p_max * srv.page, srv.max_len)
    max_gen = max(g for g in gen_choices)
    max_prompt = max(1, min(12, cap - max_gen - 1))
    kinds = list(kinds)
    fault_ticks = sorted(
        int(t) for t in rng.choice(np.arange(1, max(ticks, 2)),
                                   size=min(n_faults, ticks - 1),
                                   replace=False))
    schedule: Dict[int, ChaosEvent] = {}
    for t in fault_ticks:
        name, op, kind = kinds[int(rng.randint(len(kinds)))]
        schedule[t] = ChaosEvent(
            tick=t, name=name, op=op, kind=kind,
            transient=bool(rng.rand() < transient_p))

    tracked: List[Tuple[Tuple[int, ...], int, object]] = []
    prior_prompts: List[List[int]] = []
    oracle_cache: Dict = {}
    invariant_checks = 0
    restored_tick = None

    def _submit_maybe():
        nonlocal prior_prompts
        if rng.rand() >= arrival_p:
            return
        if prior_prompts and rng.rand() < prompt_reuse_p:
            prompt = list(prior_prompts[
                int(rng.randint(len(prior_prompts)))])
        else:
            n = int(rng.randint(1, max_prompt + 1))
            prompt = [int(x) for x in rng.randint(0, vocab, n)]
            prior_prompts.append(prompt)
        gen = int(gen_choices[int(rng.randint(len(gen_choices)))])
        kw = {}
        if tenants:
            # Gated draws: a tenants=() soak never reaches these, so
            # its schedule stays byte-identical to pre-SLO soaks.
            kw["tenant"] = str(tenants[int(rng.randint(len(tenants)))])
            kw["slo_class"] = ("interactive", "standard",
                              "batch")[int(rng.randint(3))]
        from triton_dist_tpu.serving.scheduler import QueueFullError

        try:
            h = srv.submit(prompt, max_new_tokens=gen, **kw)
        except QueueFullError:
            return      # backpressure is correct behaviour, not a bug
        tracked.append((tuple(prompt), gen, h))

    def _tick_counters():
        return {k: srv.stats_counters[k] for k in
                ("retries", "comm_timeouts", "failovers")} | {
                    k: srv.sched.counters[k] for k in
                    ("failed", "timed_out")}

    # Seeded park/resume drill state: parked handles and the tick
    # each one resumes at. All rng draws are gated on park_p, so a
    # park_p=0 soak's random sequence (and therefore its entire
    # schedule) is byte-identical to the pre-tier soaks.
    resume_at: Dict[int, List[object]] = {}
    parked: List[object] = []

    def _park_maybe(tick: int):
        if not park_p or getattr(srv, "tiers", None) is None:
            return
        for h in resume_at.pop(tick, []):
            if h.status == "parked":
                srv.resume(h)
                parked.remove(h)
        if rng.rand() >= park_p:
            return
        cands = [h for h in srv.sched.running()
                 if h.status == "running" and h.tokens]
        if not cands:
            return
        h = cands[int(rng.randint(len(cands)))]
        from triton_dist_tpu.resilience.watchdog import CommTimeoutError
        from triton_dist_tpu.serving.tiers import TierFullError

        try:
            srv.park(h)
        except (TierFullError, CommTimeoutError,
                faults.InjectedFault):
            # Correct containment, not a bug: a full tier or a
            # dropped/wedged offload transfer aborts the park and the
            # request KEEPS RUNNING (the two-phase offload frees
            # nothing before the transfer commits) — on fault ticks
            # _park_maybe runs INSIDE the injection scope precisely
            # to exercise this.
            return
        parked.append(h)
        resume_at.setdefault(
            tick + 1 + int(rng.randint(4)), []).append(h)

    for tick in range(ticks):
        if restore_at is not None and tick == restore_at:
            # The mid-run kill/restore drill: snapshot, throw the
            # engine away, restore into a fresh one (same weights by
            # construction of the factory), rebind tracked handles.
            snap = srv.checkpoint()
            srv = factory()
            revived = {h.request.request_id: h
                       for h in srv.restore(snap)}
            tracked = [(p, g, revived.get(h.request.request_id, h))
                       for p, g, h in tracked]
            parked = [revived.get(h.request.request_id, h)
                      for h in parked]
            resume_at = {t: [revived.get(h.request.request_id, h)
                             for h in hs]
                         for t, hs in resume_at.items()}
            restored_tick = tick
            srv.obs.event("chaos_restore", tick=tick,
                          revived=len(revived))
        _submit_maybe()
        ev = schedule.get(tick)
        if ev is None:
            _park_maybe(tick)
            srv.step()
        elif ev.name == "kill_prefill_worker":
            ev.at = srv.sched.now()
            _note_fault(srv, ev)
            killed = bool(getattr(srv, "fail_prefill_worker",
                                  lambda: False)())
            ev.fired, ev.observed = True, killed
            _park_maybe(tick)
            srv.step()
        else:
            before = _tick_counters()
            ev.at = srv.sched.now()
            _note_fault(srv, ev)
            with faults.inject(_plan_for(ev)):
                # The park drill runs INSIDE the fault scope: a tier
                # fault can hit the park offload itself (aborted park,
                # request keeps running) as well as the step's
                # demotes/prefetches.
                _park_maybe(tick)
                srv.step()
            ev.fired = True
            ev.observed = _tick_counters() != before
        check_invariants(srv)
        invariant_checks += 1

    # Drain fault-free: everything still in flight must resolve —
    # parked sessions resume first (a park with no resume is a
    # deliberate suspension, not a drain blocker; the drill resumes
    # everything so its token-exactness is checked).
    for h in parked:
        if h.status == "parked":
            srv.resume(h)
    parked.clear()
    budget = max_drain_steps or (ticks * 4 + 200)
    for _ in range(budget):
        if srv._drained():
            break
        srv.step()
        check_invariants(srv)
        invariant_checks += 1
    else:
        raise InvariantViolation(
            f"serving loop failed to drain within {budget} post-soak "
            f"steps (queue={len(srv.sched.queue)}, "
            f"slots={sorted(srv.sched.slots)})")

    statuses = Counter(h.status for _, _, h in tracked)
    unresolved = [h.request.request_id for _, _, h in tracked
                  if not h.done]
    if unresolved:
        raise InvariantViolation(
            f"request(s) never terminally resolved: {unresolved}")
    token_exact = 0
    for prompt, gen, h in tracked:
        if h.status != "done":
            continue
        if mk_oracle is not None:
            key = (tuple(prompt), gen)
            if key not in oracle_cache:
                if mk_oracle["srv"] is None:
                    mk_oracle["srv"] = factory()
                oracle_cache[key] = mk_oracle["srv"].generate(
                    [list(prompt)], max_new_tokens=gen)[0]
            want = oracle_cache[key]
        else:
            want = _oracle_tokens(srv.engine, prompt, gen,
                                  oracle_cache)
        if list(h.tokens) != list(want):
            raise InvariantViolation(
                f"survivor {h.request.request_id} diverged from the "
                f"fault-free oracle: {h.tokens} != {want} "
                f"(prompt={list(prompt)})")
        token_exact += 1

    events = [schedule[t] for t in fault_ticks]
    return ChaosReport(
        seed=seed, ticks=ticks, events=events,
        faults_injected=len(events),
        survived_faults=sum(1 for e in events if e.fired),
        requests={"submitted": len(tracked), **{
            k: statuses.get(k, 0)
            for k in ("done", "failed", "timeout")}},
        counters={k: srv.stats_counters[k] for k in
                  ("retries", "failovers", "comm_timeouts",
                   "preemptions", "slo_preemptions",
                   "restored_requests", "parks", "resumes")},
        invariant_checks=invariant_checks,
        token_exact_requests=token_exact,
        restored_at=restored_tick)


def run_fleet_soak(factory: Callable[[], object], *,
                   fleets: int = 2, seed: int = 0, ticks: int = 200,
                   n_faults: int = 10, arrival_p: float = 0.35,
                   kinds: Sequence = (FLEET_FAULT_KINDS
                                      + TIER_FAULT_KINDS),
                   transient_p: float = 0.5,
                   gen_choices: Sequence[int] = (2, 3, 4, 6, 8),
                   prompt_reuse_p: float = 0.4,
                   deadline_p: float = 0.5,
                   scale_at: Optional[Tuple[int, int]] = None,
                   max_drain_steps: Optional[int] = None,
                   router_kw: Optional[Dict] = None
                   ) -> FleetChaosReport:
    """Fleet-level chaos soak: drive ``ticks`` router steps of seeded
    mixed traffic through a :class:`~triton_dist_tpu.serving.router.
    FleetRouter` over ``fleets`` replicas of ``factory()``, under a
    seeded schedule of whole-fleet kills (a seeded coin picks
    reachable — the parked-tier handoff path — vs vanished — the
    re-prefill path; never the last live fleet), dropped/wedged
    ``fleet_route`` / ``fleet_handoff`` links, and tier faults.
    :func:`check_fleet_invariants` sweeps after EVERY tick, the run
    drains fault-free, every request must reach a terminal state
    (``shed`` counts — graceful degradation is a terminal verdict,
    not a hang), and every ``done`` request's tokens must equal the
    single-engine ``Engine.serve`` oracle.

    ``deadline_p``: fraction of requests submitted with a (far)
    deadline — the interactive class, so fleet-loss shedding has both
    classes to discriminate. ``scale_at=(tick, R')`` additionally
    runs the drain/restore autoscale drill mid-soak. Raises
    :class:`InvariantViolation` on any violation; returns a
    :class:`FleetChaosReport` otherwise.
    """
    from triton_dist_tpu.serving.router import FleetRouter
    from triton_dist_tpu.serving.scheduler import QueueFullError

    rng = np.random.RandomState(seed)
    router = FleetRouter(factory, fleets=fleets, **(router_kw or {}))
    oracle_engine = router.fleets[0].engine.engine
    vocab = router.fleets[0].engine.cfg.vocab_size
    ref = router.fleets[0].engine
    cap = min(ref.p_max * ref.page, ref.max_len)
    max_gen = max(g for g in gen_choices)
    max_prompt = max(1, min(12, cap - max_gen - 1))
    kinds = list(kinds)
    fault_ticks = sorted(
        int(t) for t in rng.choice(np.arange(1, max(ticks, 2)),
                                   size=min(n_faults, ticks - 1),
                                   replace=False))
    schedule: Dict[int, ChaosEvent] = {}
    for t in fault_ticks:
        name, op, kind = kinds[int(rng.randint(len(kinds)))]
        schedule[t] = ChaosEvent(
            tick=t, name=name, op=op, kind=kind,
            transient=bool(rng.rand() < transient_p))

    tracked: List[Tuple[Tuple[int, ...], int, object]] = []
    prior_prompts: List[List[int]] = []
    oracle_cache: Dict = {}
    invariant_checks = 0
    scaled_tick = None

    def _submit_maybe():
        if rng.rand() >= arrival_p:
            return
        if prior_prompts and rng.rand() < prompt_reuse_p:
            # Prompt reuse = the affinity signal: same-prefix traffic
            # should keep landing on the fleet holding the pages.
            prompt = list(prior_prompts[
                int(rng.randint(len(prior_prompts)))])
        else:
            n = int(rng.randint(1, max_prompt + 1))
            prompt = [int(x) for x in rng.randint(0, vocab, n)]
            prior_prompts.append(prompt)
        gen = int(gen_choices[int(rng.randint(len(gen_choices)))])
        # Interactive (far-deadline) vs batch class — both present so
        # fleet-loss shedding has an ordering to exercise.
        deadline = (router.obs.now() + 1e6
                    if rng.rand() < deadline_p else None)
        try:
            h = router.submit(prompt, max_new_tokens=gen,
                              deadline=deadline)
        except QueueFullError:
            return      # backpressure is correct behaviour, not a bug
        tracked.append((tuple(prompt), gen, h))

    def _fault_tick(ev: ChaosEvent):
        before = (dict(router.counters),
                  tuple(f.health.total_failures
                        for f in router.fleets))
        ev.at = router.obs.now()
        router.obs.event("chaos_fault", tick=ev.tick, name=ev.name,
                         op=ev.op, fault_kind=ev.kind,
                         transient=ev.transient)
        if ev.name == "kill_fleet":
            live = router._live_fleets()
            if len(live) < 2:
                ev.fired = False        # nothing safely killable
                _submit_maybe()
                router.step()
                return
            victim = live[int(rng.randint(len(live)))]
            reachable = bool(rng.rand() < 0.5)
            router.kill_fleet(victim.id, reachable=reachable)
            ev.fired = ev.observed = True
            _submit_maybe()
            router.step()
            return
        # Route/handoff/tier faults: the injection window covers the
        # SUBMIT (where routing happens) and the step (queue drain,
        # failover handoffs, tier traffic).
        with faults.inject(_plan_for(ev)):
            _submit_maybe()
            router.step()
        ev.fired = True
        ev.observed = (dict(router.counters),
                       tuple(f.health.total_failures
                             for f in router.fleets)) != before

    for tick in range(ticks):
        if scale_at is not None and tick == scale_at[0]:
            router.scale_to(scale_at[1])
            scaled_tick = tick
            router.obs.event("chaos_scale", tick=tick, to=scale_at[1])
        ev = schedule.get(tick)
        if ev is None:
            _submit_maybe()
            router.step()
        else:
            _fault_tick(ev)
        check_fleet_invariants(router, [h for _, _, h in tracked])
        invariant_checks += 1

    budget = max_drain_steps or (ticks * 4 + 200)
    for _ in range(budget):
        if router.drained:
            break
        router.step()
        check_fleet_invariants(router, [h for _, _, h in tracked])
        invariant_checks += 1
    else:
        raise InvariantViolation(
            f"fleet serving failed to drain within {budget} post-soak "
            f"steps (router queue={len(router.queue)})")

    statuses = Counter(h.status for _, _, h in tracked)
    unresolved = [h.request.request_id for _, _, h in tracked
                  if not h.done]
    if unresolved:
        raise InvariantViolation(
            f"request(s) never terminally resolved: {unresolved}")
    token_exact = 0
    for prompt, gen, h in tracked:
        if h.status != "done":
            continue
        want = _oracle_tokens(oracle_engine, prompt, gen, oracle_cache)
        if list(h.tokens) != list(want):
            raise InvariantViolation(
                f"survivor {h.request.request_id} diverged from the "
                f"single-engine oracle: {h.tokens} != {want} "
                f"(prompt={list(prompt)})")
        token_exact += 1

    events = [schedule[t] for t in fault_ticks]
    return FleetChaosReport(
        seed=seed, ticks=ticks, fleets=fleets, events=events,
        faults_injected=len(events),
        survived_faults=sum(1 for e in events if e.fired),
        requests={"submitted": len(tracked), **{
            k: statuses.get(k, 0)
            for k in ("done", "failed", "timeout", "shed")}},
        router=dict(router.counters),
        invariant_checks=invariant_checks,
        token_exact_requests=token_exact,
        scaled_at=scaled_tick)


# ---------------------------------------------------------------------------
# Supervised soak: a REAL child process under seeded kills / stalls /
# corruption (ISSUE 16)
# ---------------------------------------------------------------------------

def supervised_tiny_factory(num_slots: int = 2, max_len: int = 32,
                            page: int = 8):
    """Importable child-side factory for the supervised soak: the
    tiny-model colocated disagg engine on one CPU device (chunked
    prefill + migration + retry reachable, deterministic greedy
    decode).  Module-level on purpose — the supervisor child resolves
    it by ``module:qualname`` string."""
    import jax
    from jax.sharding import Mesh

    from triton_dist_tpu.models import Engine, ModelConfig
    from triton_dist_tpu.resilience.policy import RetryPolicy
    from triton_dist_tpu.serving import DisaggServingEngine

    cfg = ModelConfig.tiny(vocab_size=64, hidden_size=32,
                           intermediate_size=32, num_hidden_layers=2,
                           num_attention_heads=4,
                           num_key_value_heads=4, head_dim=8)
    mesh = Mesh(np.array(jax.devices()[:1]), ("tp",))
    eng = Engine(cfg, mesh, mode="xla", max_len=max_len, seed=0)
    return DisaggServingEngine(
        eng, num_slots=num_slots, page=page, prefill_buckets=(4, 8),
        prefix_reuse=True, retry=RetryPolicy(max_attempts=2),
        worker_fail_threshold=2)


def run_supervised_soak(
        *, checkpoint_dir: str, seed: int = 0, n_requests: int = 8,
        n_faults: int = 6,
        factory: str = ("triton_dist_tpu.resilience.chaos:"
                        "supervised_tiny_factory"),
        factory_kwargs: Optional[Dict] = None, vocab: int = 64,
        gen_choices: Sequence[int] = (3, 4, 6, 8),
        kinds: Sequence = SUPERVISED_FAULT_KINDS,
        checkpoint_every: int = 2, heartbeat_timeout_s: float = 60.0,
        stall_detect_s: float = 2.0, tick_throttle_s: float = 0.04,
        deadline_s: float = 600.0) -> SupervisedChaosReport:
    """Drive a REAL supervised child process through ``n_faults``
    seeded kills / crashes / stalls / corruptions while it serves
    ``n_requests`` streams, then gate every finished stream token-
    exact against the in-process oracle (``Engine.serve`` on the same
    factory's weights — same seed, same weights by construction).

    Events fire when the parent's acked-token count crosses seeded
    thresholds (real process timing makes tick counts nondeterministic
    — the ack stream is the clock the parent actually observes), so
    one ``seed`` fixes the traffic AND where in each stream every
    fault lands.  A ``stall_child`` event tightens the heartbeat
    timeout to ``stall_detect_s`` until the recovery lands (child
    startup/compile gaps make a permanently-tight timeout
    false-trigger); a false stall during that window just becomes one
    more survived restart — the gate is token-exactness, not fault
    attribution.

    Raises :class:`InvariantViolation` on any non-``done`` request or
    token divergence; returns a :class:`SupervisedChaosReport`.
    """
    from triton_dist_tpu.resilience.supervisor import (
        ServingSupervisor, _resolve_factory)

    rng = np.random.RandomState(seed)
    fkw = dict(factory_kwargs or {})

    # Seeded traffic first (all rng draws in a fixed order).
    gen_choices = list(gen_choices)
    reqs = []
    for i in range(n_requests):
        n = int(rng.randint(1, 9))
        prompt = [int(x) for x in rng.randint(0, vocab, n)]
        gen = int(gen_choices[int(rng.randint(len(gen_choices)))])
        reqs.append((f"soak-{i}", prompt, gen))
    total = sum(g for _, _, g in reqs)
    # Thresholds stay under ~85% of the total stream so every event
    # fires while work is still in flight.
    hi = max(2, int(total * 0.85))
    thresholds = sorted(int(t) for t in rng.choice(
        np.arange(1, hi), size=min(n_faults, hi - 1), replace=False))
    events = []
    for t in thresholds:
        name, op, kind = kinds[int(rng.randint(len(kinds)))]
        events.append(ChaosEvent(tick=t, name=name, op=op, kind=kind,
                                 transient=True))

    # In-process oracle: same factory, same seed -> same weights.
    oracle_srv = _resolve_factory(factory)(**fkw)
    oracle_cache: Dict = {}
    want = {rid: _oracle_tokens(oracle_srv.engine, prompt, gen,
                                oracle_cache)
            for rid, prompt, gen in reqs}

    sup = ServingSupervisor(
        factory, checkpoint_dir=checkpoint_dir,
        heartbeat_timeout_s=heartbeat_timeout_s,
        checkpoint_every=checkpoint_every, factory_kwargs=fkw,
        tick_throttle_s=tick_throttle_s)
    sup.start()
    handles = {}
    stall_restore_at: Optional[int] = None
    try:
        for rid, prompt, gen in reqs:
            handles[rid] = sup.submit(prompt, request_id=rid,
                                      max_new_tokens=gen)
        pending = list(events)
        t0 = time.monotonic()
        while True:
            sup.pump()
            if (stall_restore_at is not None
                    and sup.counters["restarts"] >= stall_restore_at):
                # The stall (or a coincident crash) was detected and
                # recovered — relax the timeout before the restored
                # child's cold compile gap can false-trigger again.
                sup.heartbeat_timeout_s = heartbeat_timeout_s
                stall_restore_at = None
            acked = sup.counters["acked_tokens"]
            all_done = all(h.done for h in handles.values())
            while (pending and pending[0].tick <= acked
                   and not all_done):
                ev = pending.pop(0)
                ev.fired = True
                if ev.name == "kill_child":
                    sup.kill_child()
                elif ev.name == "crash_child":
                    sup.inject_crash()
                elif ev.name == "stall_child":
                    stall_restore_at = sup.counters["restarts"] + 1
                    sup.heartbeat_timeout_s = stall_detect_s
                    sup.inject_stall()
                else:
                    sup.inject_fault(
                        "corrupt_payload", op=ev.op,
                        k=0 if ev.transient else None)
            if all_done and not pending:
                break
            if all_done and pending:
                # Streams finished under the last thresholds — the
                # remaining events have nothing left to disrupt.
                break
            if time.monotonic() - t0 > deadline_s:
                open_rids = [r for r, h in handles.items()
                             if not h.done]
                raise InvariantViolation(
                    f"supervised soak exceeded {deadline_s}s with "
                    f"open requests {open_rids[:8]} "
                    f"(stats={sup.stats()})")
            time.sleep(0.02)

        statuses = Counter(h.status for h in handles.values())
        token_exact = 0
        for rid, prompt, gen in reqs:
            h = handles[rid]
            if h.status != "done":
                raise InvariantViolation(
                    f"supervised request {rid} ended {h.status!r} "
                    f"(error={h.error!r})")
            if list(h.tokens) != list(want[rid]):
                raise InvariantViolation(
                    f"supervised stream {rid} diverged from the "
                    f"oracle across restarts: {h.tokens} != "
                    f"{want[rid]} (prompt={prompt})")
            token_exact += 1
        stats = sup.stats()
    finally:
        sup.stop()

    return SupervisedChaosReport(
        seed=seed, events=events, faults_injected=len(events),
        survived_faults=sum(1 for e in events if e.fired),
        requests={"submitted": len(reqs), **{
            k: statuses.get(k, 0)
            for k in ("done", "failed", "timeout")}},
        supervisor=stats, token_exact_requests=token_exact)


# ---------------------------------------------------------------------------
# Integrity drill: deterministic corruption at each serialization
# boundary, in-process (the bench's integrity evidence)
# ---------------------------------------------------------------------------

def run_integrity_drill(engine=None, *, seed: int = 0) -> Dict:
    """Deterministically corrupt the KV payload at each of the three
    serving serialization boundaries — tier transfer (park/resume
    round trip), page migration (prefill->decode handoff), and the
    cross-fleet session handoff — and prove each one is DETECTED at
    the consuming edge (quarantine / integrity counters move) and
    RECOVERED through that boundary's existing path with the final
    stream token-exact.  Raises :class:`InvariantViolation` on a
    missed detection or a wrong token; returns the evidence counters
    (the ``integrity_checks`` bench key sums them).

    ``engine`` (optional) is a prebuilt tiny layer
    :class:`~triton_dist_tpu.models.Engine` to reuse (the tests pass
    their module fixture); built fresh otherwise.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from triton_dist_tpu.models import Engine, ModelConfig
    from triton_dist_tpu.serving import (
        DisaggServingEngine, FleetRouter, ServingEngine)

    if engine is None:
        cfg = ModelConfig.tiny(vocab_size=64, hidden_size=32,
                               intermediate_size=32,
                               num_hidden_layers=2,
                               num_attention_heads=4,
                               num_key_value_heads=4, head_dim=8)
        mesh = Mesh(np.array(jax.devices()[:1]), ("tp",))
        engine = Engine(cfg, mesh, mode="xla", max_len=32, seed=0)

    def oracle(prompt, gen):
        ids = jnp.asarray(np.asarray([list(prompt)], np.int32))
        return np.asarray(engine.serve(ids, gen_len=gen))[0].tolist()

    def corrupt_plan(op, k=None):
        return faults.FaultPlan(
            name=f"drill-corrupt-{op}",
            faults=(faults.Fault("corrupt_payload", op=op, k=k,
                                 iters=seed),))

    out = {"tier_checks": 0, "tier_quarantined": 0,
           "migration_integrity_failures": 0,
           "handoff_integrity_failures": 0,
           "token_exact_requests": 0, "wrong_tokens": 0}

    # -- boundary 1: tier transfer (park -> corrupt resume fetch) ----
    srv = ServingEngine(engine, num_slots=2, page=4, num_pages=16,
                        prefix_reuse=True,
                        kv_tiers={"host_pages": 128})
    prompt, gen = [5, 3, 5, 3, 5, 3], 6
    h = srv.submit(prompt, max_new_tokens=gen)
    for _ in range(64):
        if h.status == "running" and h.tokens:
            break
        srv.step()
    srv.park(h)
    srv.resume(h)
    with faults.inject(corrupt_plan("tier_transfer")):
        # The admit-side tier get sees a corrupted payload: digest
        # mismatch -> quarantine -> miss -> deterministic re-prefill.
        srv.step()
    srv.run()
    if h.status != "done":
        raise InvariantViolation(
            f"tier-corruption drill ended {h.status!r}: {h.error!r}")
    if list(h.tokens) != oracle(prompt, gen):
        out["wrong_tokens"] += 1
        raise InvariantViolation(
            f"tier-corruption drill emitted wrong tokens: "
            f"{h.tokens} != {oracle(prompt, gen)}")
    out["token_exact_requests"] += 1
    out["tier_checks"] = srv.tiers.stats_counters["integrity_checks"]
    out["tier_quarantined"] = \
        srv.tiers.stats_counters["integrity_quarantined"]
    if out["tier_quarantined"] < 1:
        raise InvariantViolation(
            "tier-corruption drill: the corrupted payload was never "
            "quarantined — detection missed")

    # -- boundary 2: page migration (prefill -> decode handoff) ------
    dsrv = DisaggServingEngine(engine, num_slots=2, page=8,
                               prefill_buckets=(4, 8))
    prompt2, gen2 = [7, 1, 7, 1], 6
    h2 = dsrv.submit(prompt2, max_new_tokens=gen2)
    for _ in range(64):
        if dsrv._pending:
            break
        dsrv.step()
    with faults.inject(corrupt_plan("page_migration")):
        # Every migration attempt this tick is corrupted (k=None):
        # verify fails at the consuming edge before anything reaches
        # the decode pool, retries exhaust, the request re-queues for
        # a clean re-prefill.
        dsrv.step()
    dsrv.run()
    if h2.status != "done":
        raise InvariantViolation(
            f"migration-corruption drill ended {h2.status!r}: "
            f"{h2.error!r}")
    if list(h2.tokens) != oracle(prompt2, gen2):
        out["wrong_tokens"] += 1
        raise InvariantViolation(
            f"migration-corruption drill emitted wrong tokens: "
            f"{h2.tokens} != {oracle(prompt2, gen2)}")
    out["token_exact_requests"] += 1
    out["migration_integrity_failures"] = \
        dsrv.stats_counters["integrity_failures"]
    if out["migration_integrity_failures"] < 1:
        raise InvariantViolation(
            "migration-corruption drill: no integrity failure was "
            "recorded — detection missed")

    # -- boundary 3: cross-fleet session handoff ---------------------
    def fleet_factory():
        return ServingEngine(engine, num_slots=2, page=4,
                             num_pages=16, prefix_reuse=True,
                             kv_tiers={"host_pages": 128})

    router = FleetRouter(fleet_factory, fleets=2)
    prompt3, gen3 = [9, 2, 9, 2, 9, 2, 9, 2], 8
    h3 = router.submit(prompt3, max_new_tokens=gen3)
    for _ in range(64):
        if h3.status == "running" and h3.tokens:
            break
        router.step()
    victim = router._fleet_of(h3)
    with faults.inject(corrupt_plan("fleet_handoff")):
        # kill_fleet fails the victim's sessions over SYNCHRONOUSLY,
        # so the handoff hop happens inside this scope: every hop is
        # corrupted, the survivor's verify rejects the payload,
        # retries exhaust, and failover falls back to the
        # deterministic re-prefill path.
        router.kill_fleet(victim.id, reachable=True)
    router.run()
    if h3.status != "done":
        raise InvariantViolation(
            f"handoff-corruption drill ended {h3.status!r}: "
            f"{h3.error!r}")
    if list(h3.tokens) != oracle(prompt3, gen3):
        out["wrong_tokens"] += 1
        raise InvariantViolation(
            f"handoff-corruption drill emitted wrong tokens: "
            f"{h3.tokens} != {oracle(prompt3, gen3)}")
    out["token_exact_requests"] += 1
    out["handoff_integrity_failures"] = \
        router.counters["integrity_failures"]
    if out["handoff_integrity_failures"] < 1:
        raise InvariantViolation(
            "handoff-corruption drill: no integrity failure was "
            "recorded — detection missed")
    return out
