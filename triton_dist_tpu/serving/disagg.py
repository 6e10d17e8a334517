"""Disaggregated prefill/decode serving with KV page migration.

The serving split the source paper's Engine/MegaTritonKernel pairing
implies (PAPER.md L7/L7′) and the megakernel-decode serving analysis
of arXiv 2605.00686 argues for explicitly: keep decode on a
never-respecializing hot path, and move prefill's variable-shape work
onto a separate worker so prefill-heavy traffic can never stall the
fixed-shape decode batch. Two roles in one process group:

- :class:`PrefillWorker` — a layer engine on its own mesh slice with a
  private staging page pool; prompts stream through it in bucketed
  fixed-shape chunks (:mod:`~triton_dist_tpu.serving.chunked`), so its
  jit cache is bounded by the bucket count.
- decode worker — the plain continuous-batching
  :class:`~triton_dist_tpu.serving.server.ServingEngine` machinery
  (``DisaggServingEngine`` *is* one), driving the fixed-shape decode
  dispatch on its own mesh slice.

Completed prefills hand their KV over as WHOLE PAGES — the pool's
natural transfer unit: the decode worker's
:class:`~triton_dist_tpu.serving.blocks.BlockManager` allocates fresh
page ids and the block table is rewritten on the receiver, so page ids
never need to agree across roles; refcounted prefix pages migrate once
(a decode-side prefix hit skips the transfer AND protects pages a live
reader holds from being re-blitted). When the roles sit on disjoint
device sets the payload rides the one-sided
:func:`~triton_dist_tpu.ops.p2p.migrate_pages_host` remote-DMA edge
over a 2-rank bridge mesh; the single-role degenerate mode (both roles
on one mesh) blits locally through the same fixed-shape scatter. The
migration is issued asynchronously when the final chunk completes and
collected at the START of the next tick, so the transfer overlaps the
next chunk's compute and the decode dispatch in between.

Failure containment mirrors the decode path, now in three escalating
tiers (docs/resilience.md, "Failure semantics"):

1. **retry** — with a ``retry=RetryPolicy(...)`` the migration and the
   chunk dispatch are replayed with deterministic exponential backoff
   (both are replay-idempotent: staging pages, two-phase prefix
   publication, scratch-routed rewrites), absorbing transients;
2. **fail-one** — retries exhausted, the migration still wrapped in
   ``faults.on_op_call("page_migration")`` and the resilience watchdog
   (``timeout_s``): one request fails, never the server;
3. **failover** — ``worker_fail_threshold`` CONSECUTIVE post-retry
   prefill-side failures (or an operator
   :meth:`DisaggServingEngine.fail_prefill_worker`) declare the
   active :class:`PrefillWorker` dead: its in-flight handles requeue
   (token-preserving — the deterministic re-prefill contract keeps
   them token-exact) and prefill moves to the next surviving worker
   (``prefill_engines=[...]``), or onto the decode worker's own
   in-place chunked path when none survives.
"""

from __future__ import annotations

from typing import List, Optional, Sequence

import numpy as np

from triton_dist_tpu.serving.blocks import (
    SCRATCH_PAGE, BlockManager, OutOfPagesError, PagedKVCache,
)
from triton_dist_tpu.serving.chunked import DEFAULT_BUCKETS, ChunkedPrefill
from triton_dist_tpu.serving.scheduler import RequestHandle
from triton_dist_tpu.serving.server import ServingEngine

__all__ = ["PrefillWorker", "DisaggServingEngine"]


class PrefillWorker:
    """The prefill role: one layer engine + a private staging page
    pool + the bucketed chunk dispatch. Duck-types the ``_prefiller``
    contract the base :class:`ServingEngine` chunk loop drives
    (``engine`` / ``manager`` / ``cache`` / ``chunker``), plus the
    fixed-shape page EXTRACT the migration reads (always ``p_max``
    pages, scratch-padded — one jit entry regardless of prompt
    length)."""

    def __init__(self, engine, *, page: int, p_max: int, num_slots: int,
                 num_pages: Optional[int] = None,
                 buckets: Sequence[int] = DEFAULT_BUCKETS,
                 prefix_reuse: bool = False, kv_dtype: str = "bf16",
                 attn_impl: str = "ref", telemetry=None):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from triton_dist_tpu.megakernel.engine import MegaKernelEngine

        if isinstance(engine, MegaKernelEngine):
            raise ValueError("the prefill worker is a layer-path role; "
                             "the megakernel's prefill lane already "
                             "rides its decode batch")
        pool = engine.model.paged_pool(engine.cfg)
        if pool[0] is not PagedKVCache or len(pool) > 2:
            raise NotImplementedError(
                "the prefill worker stages and migrates K and V pages of "
                f"every layer; model {engine.model.__name__!r} states "
                "another pool")
        self.engine = engine
        self.page, self.p_max = page, p_max
        self.kv_dtype = kv_dtype
        cfg, mesh, axis = engine.cfg, engine.mesh, engine.axis
        dtype_bytes = np.dtype(
            jax.tree.leaves(engine.params)[0].dtype).itemsize
        plan = cfg.kv_cache_plan(max_len=p_max * page, page=page,
                                 num_slots=num_slots,
                                 tp=mesh.shape[axis],
                                 dtype_bytes=dtype_bytes,
                                 kv_dtype=kv_dtype)
        self.num_pages = num_pages or plan["num_pages"]
        self.manager = BlockManager(
            self.num_pages, page, p_max, prefix_reuse=prefix_reuse,
            page_bytes=plan["page_bytes_per_rank"],
            native_page_bytes=plan["native_page_bytes_per_rank"])
        # The staging pool quantizes with the SAME kv_dtype as the
        # decode pool: pages migrate as their stored bytes (+ scales),
        # so the handoff is bit-exact and the decode side never
        # re-quantizes.
        self.cache, self.shardings = PagedKVCache.empty_sharded(
            mesh, engine.model.paged_cache_specs, axis,
            cfg.num_hidden_layers, self.num_pages, page,
            cfg.num_key_value_heads, cfg.head_dim, num_slots=num_slots,
            p_max=p_max,
            dtype=jax.tree.leaves(engine.params)[0].dtype,
            kv_dtype=kv_dtype)
        self.quantized = self.cache.quantized
        self.chunker = ChunkedPrefill(engine, self.shardings, buckets,
                                      attn_impl=attn_impl,
                                      telemetry=telemetry)
        # Liveness + transport, managed by the owning engine: ``dead``
        # flips on a declared failover; ``migration``/``bridge`` are
        # the per-worker payload transport (each worker's mesh slice
        # gets its own verdict and, for p2p, its own 2-rank bridge).
        self.dead = False
        self.migration = "local"
        self.bridge = None
        # Fixed-shape payload extract: (L, p_max, KV_full, page, hd),
        # gathered replicated so the payload can leave this mesh
        # (quantized pools add the two (L, p_max, KV) scale planes).
        rep = NamedSharding(mesh, P())
        self._extract = jax.jit(
            lambda c, ids: c.gather_pages(ids),
            out_shardings=((rep, rep, rep, rep) if self.quantized
                           else (rep, rep)))
        # The reverse edge: a fixed-shape scatter INTO the staging
        # pool (donated, pinned to the pool's one sharding spelling)
        # — tier-resident leading prefix pages land here at
        # chunk-stream start so the worker skips their compute, the
        # dual of the decode-side handoff fetch. One jit entry: the
        # payload is always scratch-padded to p_max pages.
        if self.quantized:
            self._inject = jax.jit(
                lambda c, k, v, ks, vs, ids: c.scatter_pages(
                    k, v, ids, ks, vs),
                donate_argnums=(0,), out_shardings=self.shardings)
        else:
            self._inject = jax.jit(
                lambda c, k, v, ids: c.scatter_pages(k, v, ids),
                donate_argnums=(0,), out_shardings=self.shardings)

    def extract(self, page_ids: np.ndarray):
        """Dispatch the (async) payload gather for ``page_ids``
        ((p_max,) int32, scratch-padded). Returns device arrays on the
        prefill mesh — the caller overlaps their readout against later
        chunk compute."""
        import jax.numpy as jnp

        return self._extract(self.cache, jnp.asarray(page_ids,
                                                     jnp.int32))

    def release(self, slot: int):
        """Free a slot's staging pages (no-op if none staged)."""
        self.manager.free_slot(slot)

    def inject(self, arrays, dst_ids) -> None:
        """Blit a tier payload into staging-pool pages: ``arrays``
        hold ``n`` pages along axis 1, ``dst_ids`` the ``n`` target
        page ids. Scratch-padded to ``p_max`` — one fixed-shape
        dispatch whatever the payload size."""
        import jax.numpy as jnp

        n = int(arrays[0].shape[1])
        ids = np.full((self.p_max,), SCRATCH_PAGE, np.int32)
        ids[:n] = np.asarray(dst_ids, np.int32)
        padded = []
        for a in arrays:
            a = np.asarray(a)
            pad = np.zeros(a.shape[:1] + (self.p_max - n,)
                           + a.shape[2:], a.dtype)
            padded.append(jnp.asarray(
                np.concatenate([a, pad], axis=1)))
        self.cache = self._inject(self.cache, *padded,
                                  jnp.asarray(ids))


class DisaggServingEngine(ServingEngine):
    """Disaggregated serving front end: the decode-worker
    :class:`ServingEngine` plus a :class:`PrefillWorker`, same public
    API (``submit`` / ``step`` / ``run`` / ``generate`` / ``stats``).

    ``engine`` is the DECODE role's layer engine; ``prefill_engine``
    the prefill role's (same config and weights — pass the same host
    ``params`` to both ``Engine`` constructors). Omitting it is the
    single-role degenerate mode: one engine plays both roles on one
    mesh, chunked prefill and page migration still exercised (local
    scatter instead of the bridge put). ``prefill_engines=[...]``
    instead builds N > 1 prefill workers (one active at a time;
    standbys are failover targets). ``migration`` picks the payload
    transport: ``"p2p"`` (one-sided put over a 2-rank bridge mesh —
    requires disjoint role device sets), ``"local"``, or ``"auto"``
    (p2p iff that worker's devices are disjoint from the decode
    mesh's — resolved per worker).

    ``failover`` (default on) arms the prefill-role health tracker:
    ``worker_fail_threshold`` consecutive post-retry chunk/migration
    failures declare the active worker dead and fail prefill over to
    the next surviving worker, or to the decode engine's own in-place
    chunked path (the degenerate local mode) when none survives —
    in-flight requests requeue token-preserving instead of failing.
    """

    def __init__(self, engine, *, prefill_engine=None,
                 prefill_engines: Optional[Sequence] = None,
                 prefill_buckets: Sequence[int] = DEFAULT_BUCKETS,
                 prefill_num_pages: Optional[int] = None,
                 migration: str = "auto", prefix_reuse: bool = False,
                 failover: bool = True, worker_fail_threshold: int = 3,
                 **kw):
        from triton_dist_tpu.megakernel.engine import MegaKernelEngine
        from triton_dist_tpu.resilience.watchdog import HealthTracker

        if isinstance(engine, MegaKernelEngine):
            raise ValueError(
                "disaggregated serving splits the LAYER path; the "
                "megakernel is already a single fused decode role")
        super().__init__(engine, prefix_reuse=prefix_reuse, **kw)
        if prefill_engine is not None and prefill_engines is not None:
            raise ValueError("pass prefill_engine OR prefill_engines, "
                             "not both")
        pf_engines = (list(prefill_engines) if prefill_engines
                      else [prefill_engine if prefill_engine is not None
                            else engine])
        if not pf_engines:
            raise ValueError("prefill_engines must name at least one "
                             "engine")
        if migration not in ("auto", "p2p", "local"):
            raise ValueError(f"migration must be 'auto'|'p2p'|'local', "
                             f"got {migration!r}")
        self._pf_buckets = tuple(prefill_buckets)
        self.failover = bool(failover)
        self.worker_fail_threshold = int(worker_fail_threshold)
        self.prefill_workers: List[PrefillWorker] = []
        for pf_eng in pf_engines:
            if pf_eng.cfg != engine.cfg:
                raise ValueError(
                    "prefill and decode engines must share one "
                    "ModelConfig (and the same weights)")
            if pf_eng.max_len != engine.max_len:
                raise ValueError(
                    f"prefill max_len {pf_eng.max_len} != decode "
                    f"max_len {engine.max_len}: the chunked writer "
                    "addresses pages by global position, the bounds "
                    "must agree")
            w = PrefillWorker(
                pf_eng, page=self.page, p_max=self.p_max,
                num_slots=self.num_slots, num_pages=prefill_num_pages,
                buckets=prefill_buckets, prefix_reuse=prefix_reuse,
                kv_dtype=self.kv_dtype, attn_impl=self.chunk_attn,
                telemetry=self.obs)
            self._setup_transport(w, migration)
            self.prefill_workers.append(w)
        self._prefiller = self.prefill_workers[0]
        self._pf_health = self._make_pf_health()

        import jax

        # Fixed-shape receiver scatter into the decode pool — donated,
        # pinned to the pool's one sharding spelling (the decode
        # dispatch never re-specializes on a migration). Quantized
        # pools scatter the payload's scales alongside its bytes.
        if self.prefill_workers[0].quantized:
            self._scatter = jax.jit(
                lambda c, k, v, ks, vs, ids: c.scatter_pages(
                    k, v, ids, ks, vs),
                donate_argnums=(0,),
                out_shardings=self._cache_shardings)
        else:
            self._scatter = jax.jit(
                lambda c, k, v, ids: c.scatter_pages(k, v, ids),
                donate_argnums=(0,),
                out_shardings=self._cache_shardings)
        self._pending: List[tuple] = []
        self._handoff_stalled: List[RequestHandle] = []

    def _make_pf_health(self):
        """Fresh prefill-role health tracker wired into the telemetry
        event log: every post-retry failure and death verdict lands in
        the same timeline the request spans live on."""
        from triton_dist_tpu.resilience.watchdog import HealthTracker

        def _on_event(kind, at, cause):
            self.obs.event(f"role_{kind}", role="prefill", cause=cause)

        return HealthTracker(
            fail_threshold=self.worker_fail_threshold,
            clock=self.sched.clock, on_event=_on_event)

    def _setup_transport(self, w: PrefillWorker, migration: str):
        """Resolve one worker's payload transport against the decode
        mesh; p2p workers get their own 2-rank bridge (one device per
        role carries the page payload over the one-sided put edge —
        the DCN/ICI hop of a real deployment)."""
        pf_devs = set(d.id for d in w.engine.mesh.devices.flat)
        dec_devs = set(d.id for d in self.engine.mesh.devices.flat)
        disjoint = not (pf_devs & dec_devs)
        if migration == "p2p" and not disjoint:
            raise ValueError(
                "migration='p2p' needs disjoint prefill/decode mesh "
                "slices (the bridge put is a remote DMA edge); "
                "colocated roles use migration='local'")
        w.migration = ("p2p" if migration == "auto" and disjoint
                       else migration if migration != "auto"
                       else "local")
        if w.migration == "p2p":
            from jax.sharding import Mesh

            w.bridge = Mesh(
                np.array([w.engine.mesh.devices.flat[0],
                          self.engine.mesh.devices.flat[0]]), ("role",))

    # -- role topology (live view: failover moves the active role) ---

    @property
    def prefill_worker(self) -> Optional[PrefillWorker]:
        """The ACTIVE prefill worker (None once prefill has failed
        over onto the decode engine's local path)."""
        return (self._prefiller
                if isinstance(self._prefiller, PrefillWorker) else None)

    @property
    def migration(self) -> str:
        """The active handoff transport (``"local"`` covers both the
        colocated worker and the post-failover in-place path)."""
        w = self.prefill_worker
        return w.migration if w is not None else "local"

    # -- admission: route to the prefill worker ----------------------

    # Admission rides the inherited ServingEngine._admit: with
    # ``_prefiller`` set it routes to _admit_chunked, which allocates
    # in the prefill worker's STAGING pool; decode-pool pages are only
    # claimed at handoff time (_finish_prefill below).

    def _tier_worker_fetch(self, h: RequestHandle, slot: int) -> int:
        """Extend ``slot``'s resident leading-page run in the PREFILL
        WORKER's staging pool with tier-resident prefix pages — the
        worker-side dual of ``_tier_prefill_fetch``: the chunk stream
        starts past the fetched pages, skipping their compute (the
        PR 12 known limit: only the decode-side handoff consulted the
        tier). The tier entry is PEEKED, never popped — the staging
        pool is transient (abandoned wholesale on failover), so the
        tier copy stays authoritative until the decode-side handoff
        fetch publishes the key in the decode pool. Stops at the
        first genuinely cold page (hits must stay a leading run)."""
        if self.tiers is None or self._prefiller is self:
            return 0
        from triton_dist_tpu.resilience import faults
        from triton_dist_tpu.resilience.integrity import IntegrityError
        from triton_dist_tpu.resilience.watchdog import CommTimeoutError

        pw = self._prefiller
        pend = pw.manager._pending_prefix.get(slot)
        if not pend:
            return 0
        pend_by_pid = {pid: key for key, pid in pend}
        pages = pw.manager._slot_pages[slot]
        pos = pw.manager.prefix_hits(slot)
        fetch = []                          # (pid, payload arrays)
        while pos < len(pages):
            pid = pages[pos]
            key = pend_by_pid.get(pid)
            if key is None:
                if pw.manager._refs.get(pid, 0) > 1:
                    pos += 1                # shared: already resident
                    continue
                break
            if not self._tier_resident_prefix(key):
                break
            try:
                arrays = self._tier_fetch_prefix(key)
            except IntegrityError as e:
                # Quarantined: a miss — the chunk stream recomputes.
                self._note_integrity_failure(
                    "tier_get", e, request_id=h.request.request_id)
                arrays = None
            except (CommTimeoutError, faults.InjectedFault):
                arrays = None            # faulted past retries: a miss
            if arrays is None:
                self.stats_counters["tier_misses"] += 1
                break
            fetch.append((pid, arrays))
            pos += 1
        if not fetch:
            return 0
        with self.obs.span("kv_prefetch",
                           request_id=h.request.request_id, slot=slot,
                           tenant=h.request.tenant, pages=len(fetch),
                           payload="worker"):
            stacked = tuple(
                np.concatenate([arr[i] for _, arr in fetch], axis=1)
                for i in range(len(fetch[0][1])))
            pw.inject(stacked, [pid for pid, _ in fetch])
        # Publish in the STAGING prefix cache (no on_commit hook there
        # — the tier copy survives for the decode-side handoff fetch)
        # and extend the resident run so the chunk stream skips the
        # fetched pages.
        pw.manager.commit_pages(slot, [pid for pid, _ in fetch])
        pw.manager.note_tier_hits(slot, pos)
        self.stats_counters["tier_hits"] += len(fetch)
        self.stats_counters["worker_prefetched_pages"] += len(fetch)
        return len(fetch)

    # -- handoff: allocate decode pages, migrate, activate -----------

    def _finish_prefill(self, h: RequestHandle, last):
        """Final chunk done: claim decode-side pages, issue the page
        extract (async — collected next tick so the transfer overlaps
        whatever dispatches next), and park the handle as
        ``"migrating"``; ``last``, that chunk's picked token and logits,
        waits with it for :meth:`_activate`. After a failover onto the
        decode engine's in-place path there is nothing to migrate — the
        chunks wrote the serving pool directly and the base activation
        applies."""
        if self._prefiller is self:
            return super()._finish_prefill(h, last)
        pw = self._prefiller
        slot, seq = h.slot, h.lane
        # The staging pool's pages are fully written — publish them to
        # the prefill side's prefix cache (the decode pool's entries
        # are committed by _activate, AFTER the scatter lands).
        pw.manager.commit_prefix(slot)
        try:
            pages = self.manager.alloc_prefill(slot, seq)
        except OutOfPagesError as e:
            # Decode pool dry: release the staging pages and requeue at
            # the head (or fail if nothing can ever free pages). The
            # requeue is DEFERRED to end-of-step so two stalls in one
            # tick keep their order — the same invariant step() holds
            # for admission stalls.
            pw.release(slot)
            self.sched.slots.pop(slot, None)
            h.slot = None
            if not self.sched.slots:
                self._fail(h, "failed", e)
                return
            h.status = "queued"
            h.queued_at = self.sched.now()
            self._handoff_stalled.append(h)
            self.stats_counters["admit_stalls"] += 1
            return
        # Decode-side tier hits: prefix pages demoted out of the
        # decode pool earlier prefetch back from the host/disk tier
        # here, extending the resident run — those rows skip the
        # migration payload exactly like warm prefix hits (the chunk
        # compute already happened on the prefill worker; the saving
        # is transfer bytes + decode-pool churn).
        self._tier_prefill_fetch(h, slot)
        hits = self.manager.prefix_hits(slot)
        src_ids = np.asarray(pw.manager.table_row(slot), np.int32)
        dst_ids = np.full((self.p_max,), SCRATCH_PAGE, np.int32)
        # Rows below the decode-side prefix hit keep the resident
        # pages a live reader may hold (never re-blitted); rows past
        # the allocation are payload padding — both land in scratch.
        dst_ids[hits:len(pages)] = pages[hits:]
        payload = pw.extract(src_ids)   # (K, V[, K_scale, V_scale])
        # Producing-edge digest (docs/resilience.md, "Payload
        # integrity"): computed over the extracted bytes before the
        # hop; _complete_migrations re-verifies at the scatter edge.
        from triton_dist_tpu.resilience.integrity import payload_digest

        digest = payload_digest(payload)
        h.status = "migrating"
        self._pending.append((h, last, payload, dst_ids,
                              len(pages) - hits, pw, digest))

    def _in_order_by(self):
        # The handoff activates a slot whole, with its first token's
        # value (``_activate`` at ``_complete_migrations``), and a
        # failover requeues what is in flight: every tick lands in the
        # step() that launched it.
        return super()._in_order_by() or "disaggregated"

    def step(self) -> int:
        # Collect LAST tick's migrations first: their extracts (and
        # the bridge put) have been in flight across this gap —
        # overlapped with the chunks and the decode dispatch issued
        # since.
        self._complete_migrations()
        n = super().step()
        # Handoff stalls requeue at the HEAD in their processing order
        # (reversed appendleft — no leapfrogging between two stalls of
        # one tick).
        for h in reversed(self._handoff_stalled):
            self.sched.queue.appendleft(h)
        self._handoff_stalled.clear()
        return n

    def _complete_migrations(self):
        from triton_dist_tpu.resilience import faults, integrity
        from triton_dist_tpu.resilience.watchdog import (
            CommTimeoutError, block_until_ready)

        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        pending, self._pending = self._pending, []
        for h, last, payload, dst_ids, n_mig, pw, digest in pending:
            if h.status != "migrating":
                continue    # failed/requeued meanwhile (deadline,
                            # worker failover)
            slot = h.slot

            def _attempt(payload=payload, dst_ids=dst_ids, pw=pw,
                         slot=slot, h=h, n_mig=n_mig, digest=digest):
                # Replay-idempotent: re-staging the same source pages
                # and re-scattering the same bytes (+ scales) into the
                # same dst ids — prefix rows stay scratch-routed, and
                # the two-phase prefix publication means no other
                # request can be reading the target pages yet. One
                # span per ATTEMPT (retries repeat it).
                # Consuming-edge digest check against the extract-time
                # digest, AFTER the (corruptible) staging hop and
                # BEFORE anything reaches the decode pool: a flipped
                # bit raises IntegrityError and the retry re-stages
                # from the worker's still-authoritative staging pool
                # (maybe_corrupt's per-op counter advances per
                # attempt, so a k=0 fault corrupts only once).
                staged = integrity.maybe_corrupt(
                    payload, "page_migration")
                integrity.verify_payload(
                    staged, digest, boundary="page_migration",
                    key=h.request.request_id)
                k_pay, v_pay = staged[:2]
                scales = staged[2:]     # () or (k_scale, v_scale)
                with self.obs.span(
                        "migration", request_id=h.request.request_id,
                        slot=slot, tenant=h.request.tenant,
                        pages=n_mig, transport=pw.migration), \
                        faults.on_op_call("page_migration"):
                    if pw.migration == "p2p":
                        from triton_dist_tpu.ops.p2p import (
                            migrate_pages_host)

                        k_pay, v_pay = migrate_pages_host(
                            k_pay, v_pay, pw.bridge, axis="role",
                            src=0, dst=1)
                    rep = NamedSharding(self.engine.mesh, P())
                    k_pay = jax.device_put(k_pay, rep)
                    v_pay = jax.device_put(v_pay, rep)
                    # Quantized handoff: the tiny (L, p_max, KV) scale
                    # planes ride the host-staged hop alongside the
                    # page bytes (the bridge put carries the bulk
                    # payload; scales are <1% of it).
                    scales = tuple(jax.device_put(s, rep)
                                   for s in scales)
                    self.cache = self._scatter(
                        self.cache, k_pay, v_pay, *scales,
                        jnp.asarray(dst_ids, jnp.int32))
                    if self.timeout_s is not None:
                        block_until_ready(
                            self.cache, timeout_s=self.timeout_s,
                            op="serving.page_migration",
                            progress_fn=lambda: {
                                "slot": slot,
                                "migrated_pages":
                                    self.stats_counters[
                                        "migrated_pages"]})

            try:
                self._run_op_with_retry(
                    "page_migration", _attempt,
                    retry_on=(CommTimeoutError, faults.InjectedFault,
                              integrity.IntegrityError))
            except integrity.IntegrityError as e:
                # Corruption survived every retry (a persistent
                # corruptor, or no retry policy): never scatter the
                # bytes — requeue token-preserving for the
                # deterministic re-prefill (docs/resilience.md,
                # "Payload integrity").
                self._note_integrity_failure(
                    "page_migration", e,
                    request_id=h.request.request_id)
                self._requeue_corrupt_migration(h, pw)
                continue
            except (CommTimeoutError, faults.InjectedFault) as e:
                # Retries exhausted. A worker being declared dead
                # fails over (this handle requeues, token-preserving);
                # otherwise one wedged / dropped migration fails ONE
                # request: decode pages + slot released by _retire,
                # staging pages by the _retire override below.
                if isinstance(e, CommTimeoutError):
                    self.stats_counters["comm_timeouts"] += 1
                if self._note_role_failure("prefill", e):
                    continue
                self._fail(h, "timeout"
                           if isinstance(e, CommTimeoutError)
                           else "failed", e)
                continue
            except Exception as e:  # noqa: BLE001 — release, surface
                self._fail(h, "failed", e)
                raise
            pw.release(slot)
            self._note_role_ok("prefill")
            self.stats_counters["migrated_pages"] += n_mig
            self._activate(h, last)

    def _requeue_corrupt_migration(self, h, pw) -> None:
        """A migration payload failed its digest past retries: requeue
        the ONE affected handle token-preserving at the queue head —
        the per-handle slice of the failover requeue. Its re-prefill
        re-derives the KV deterministically (token-exact, the PR-4
        preemption contract); the suspect staging copy is abandoned
        and the decode pages claimed at handoff are released."""
        slot = h.slot
        pw.release(slot)
        self.sched.slots.pop(slot, None)
        h.slot = None
        self.manager.free_slot(slot)
        self._lens[slot] = self._live[slot] = self._toks[slot] = 0
        h.status = "queued"
        h.queued_at = self.sched.now()
        h.prompt_pos, h.lane, h.resident = 0, None, 0
        h.chunks = []
        self.sched.queue.appendleft(h)

    # -- prefill-worker failover --------------------------------------

    def _note_role_ok(self, role: str) -> None:
        if role == "prefill" and self._prefiller is not self:
            self._pf_health.beat()

    def _note_role_failure(self, role: str, exc) -> bool:
        """Fold one exhausted-retries prefill-side failure into the
        role's health; True when it crossed the death threshold and
        the failover (which requeues every in-flight handle,
        INCLUDING the one whose failure tripped this) handled it."""
        if (role != "prefill" or not self.failover
                or self._prefiller is self):
            return False
        if self._pf_health.fail(repr(exc)):
            return self._failover_prefill(self._pf_health.cause)
        return False

    def fail_prefill_worker(self) -> bool:
        """Operator/chaos kill switch: declare the ACTIVE prefill
        worker dead and fail over immediately (next surviving worker,
        else the decode engine's in-place path). True iff a live
        worker was killed."""
        if self._prefiller is self:
            return False
        self._pf_health.declare_dead("operator/chaos kill")
        return self._failover_prefill(self._pf_health.cause)

    def _failover_prefill(self, cause) -> bool:
        """The active prefill worker is dead: requeue its in-flight
        work token-preserving and move the prefill role.

        Every handle mid-chunk-stream or mid-migration goes back to
        the queue HEAD in slot order with its generated-so-far tokens
        intact — the deterministic re-prefill contract (the PR-4
        preemption path) re-derives their cache on the new role, so
        survivors stay token-exact. The dead worker's staging pool is
        abandoned wholesale (a real dead worker's memory is gone; the
        host bookkeeping is cleared so pool invariants stay
        checkable). Decode-side pages already claimed by a migrating
        handle are released — its re-prefill re-allocates."""
        dead = self._prefiller
        if not isinstance(dead, PrefillWorker):
            return False
        dead.dead = True
        self.stats_counters["failovers"] += 1
        requeue = [h for h in self.sched.running()
                   if h.status in ("prefill", "migrating")]
        for h in requeue:
            slot = h.slot
            self.sched.slots.pop(slot, None)
            h.slot = None
            if h.status == "migrating":
                # Decode pages were claimed at handoff; the re-prefill
                # claims fresh ones.
                self.manager.free_slot(slot)
            self._lens[slot] = self._live[slot] = self._toks[slot] = 0
            h.status = "queued"
            h.queued_at = self.sched.now()
            h.prompt_pos, h.lane, h.resident = 0, None, 0
            h.chunks = []
        for h in reversed(requeue):
            self.sched.queue.appendleft(h)
        # In-flight payload extracts from the dead worker are void
        # (their handles just left "migrating"; _complete_migrations
        # skips them).
        self._pending = [t for t in self._pending
                         if t[0].status == "migrating"]
        for slot in list(dead.manager._slot_pages):
            dead.manager.free_slot(slot)
        survivor = next((w for w in self.prefill_workers if not w.dead),
                        None)
        if survivor is not None:
            self._prefiller = survivor
        else:
            # Degenerate local path: chunk straight into the decode
            # pool through the decode engine (built lazily ONCE — its
            # jit cache is bounded by the same bucket count).
            if self.chunker is None:
                from triton_dist_tpu.serving.chunked import (
                    ChunkedPrefill)

                self.chunker = ChunkedPrefill(
                    self.engine, self._cache_shardings,
                    self._pf_buckets, attn_impl=self.chunk_attn,
                    telemetry=self.obs)
            self._prefiller = self
        self._pf_health = self._make_pf_health()
        self.obs.event("failover", requeued=len(requeue),
                       cause=str(cause),
                       target=("local" if self._prefiller is self
                               else "standby"))
        import logging

        logging.getLogger("triton_dist_tpu.resilience").warning(
            "prefill worker declared dead (%s): %d in-flight "
            "request(s) requeued, prefill role moved to %s", cause,
            len(requeue),
            "local in-place path" if self._prefiller is self
            else "standby worker")
        return True

    # -- bookkeeping overrides ---------------------------------------

    def _retire(self, h: RequestHandle, status: str, error=None):
        slot = h.slot
        super()._retire(h, status, error)
        if slot is not None:
            # Staging pages a mid-prefill/mid-migration failure leaves
            # behind (no-op once handed off). Released on EVERY
            # worker: the slot id is the key in each staging pool, and
            # after a failover the allocation may sit on a worker that
            # is no longer active.
            for w in self.prefill_workers:
                w.release(slot)

    def _drained(self) -> bool:
        return self.sched.idle and not self._pending

    def stats(self) -> dict:
        out = super().stats()
        w = self.prefill_worker
        if w is None:
            out["roles"] = "prefill+decode/failover-local"
        elif w.engine is self.engine:
            out["roles"] = "prefill+decode/colocated"
        else:
            out["roles"] = "prefill|decode/disjoint"
        out["migration_transport"] = self.migration
        out["prefill_workers"] = len(self.prefill_workers)
        out["dead_prefill_workers"] = sum(
            1 for x in self.prefill_workers if x.dead)
        out["prefill_pool"] = (w.manager.fragmentation()
                               if w is not None
                               else self.manager.fragmentation())
        return out
