"""ServingEngine: continuous batching over the layer / megakernel engines.

Reference: the megakernel ``model_server.py`` / chat demo
(``mega_triton_kernel/test/models``) serve a fixed batch; this engine
adds the missing serving layer — a PERSISTENT fixed-shape decode batch
that requests join and leave without recompilation, backed by the
:mod:`~triton_dist_tpu.serving.blocks` page pool and driven by the
:mod:`~triton_dist_tpu.serving.scheduler` policies.

Two backends behind one API:

- ``models.Engine`` (layer path): prompts prefill through the engine's
  own (token-exact) prefill dispatch; the resulting KV blits into the
  slot's pages; decode runs ONE jitted
  :func:`~triton_dist_tpu.models.dense.decode_step_paged` dispatch of
  fixed shape — per-slot lengths, block tables, and the live mask ride
  in as data, so the jit cache stays at one entry after warmup.
- ``MegaKernelEngine`` (megakernel path): no separate prefill — an
  admitted prompt streams through the SAME persistent decode kernel
  one token per tick (the prefill lane), each slot at its own cache
  position via the per-slot ``cache_len`` vector (the live-slot form
  of the megakernel decode step).

Failure containment: per-request deadlines fail one request; a hung
collective (the resilience watchdog's :class:`CommTimeoutError`) fails
the scheduler's chosen victim(s) and the server keeps serving — the
step's device results are dropped, host length mirrors do not advance,
and the next dispatch deterministically rewrites the same cache
positions, so survivors stay token-exact. (Exception: the hybrid-GDN
megakernel's recurrent state is not position-addressed, so a retried
step cannot be made exact — there a timeout fails every in-flight
request and only the server survives.)
"""

from __future__ import annotations

import math
import time
from typing import List, Optional, Sequence

import numpy as np

from triton_dist_tpu.serving.blocks import (
    BlockManager, BlockTableOverflowError, OutOfPagesError, PagedKVCache,
)
from triton_dist_tpu.serving.scheduler import (
    Request, RequestHandle, Scheduler,
)

__all__ = ["ServingEngine", "save_checkpoint", "load_checkpoint"]


# On-disk checkpoint FILE format (distinct from the in-memory snapshot
# format ``CHECKPOINT_FORMAT``): a versioned envelope around the
# pickled snapshot bytes plus their digest, so a truncated, bit-flipped
# or half-written file is DETECTED at load instead of surfacing as a
# raw pickle traceback (or worse, restoring silently wrong state).
CKPT_FILE_FORMAT = "tdt-serving-ckpt-file-v2"


def _samples(handles) -> bool:
    """Whether a request among ``handles`` samples (temperature > 0):
    its step's logits then go to the host; an all-greedy batch's stay
    on the device and only the picked tokens are fetched."""
    return any(h.request.temperature > 0.0 for h in handles)


class _Row:
    """One logits row of a step program with ``token``, the program's
    own greedy pick from it. The floats stay on the device unless the
    tick fetched them (``host``: a row of its batch samples); read as an
    array (``np.asarray``: a sampled request, a tap standing in for
    :meth:`ServingEngine._pick`) the row is copied then. ``exit_pass``:
    the pass the row's logits were read from (a model with
    ``ROW_STATS``), else None."""

    __slots__ = ("token", "_logits", "_index", "_host", "exit_pass")

    def __init__(self, token, logits, index=None, host=None,
                 exit_pass=None):
        self.token, self._logits = int(token), logits
        self._index, self._host = index, host
        self.exit_pass = exit_pass

    def __len__(self) -> int:
        return self._logits.shape[-1]

    def __array__(self, dtype=None, copy=None):
        if self._host is None:
            self._host = np.asarray(
                self._logits if self._index is None
                else self._logits[self._index])
        return self._host if dtype is None else self._host.astype(dtype)


class _Rows:
    """A step's rows by slot, as :class:`_Row`: ``picked`` the
    program's tokens on the host, ``logits`` its rows on the device,
    ``host`` those rows on the host where the tick fetched them,
    ``exits`` the pass each row took (a model with ``ROW_STATS``)."""

    def __init__(self, picked, logits, host=None, exits=None):
        self.picked, self.logits, self.host = picked, logits, host
        self.exits = exits

    def __getitem__(self, slot) -> _Row:
        return _Row(self.picked[slot], self.logits, slot,
                    None if self.host is None else self.host[slot],
                    None if self.exits is None else int(self.exits[slot]))


class _Flight:
    """A launched tick: what :meth:`ServingEngine._land` needs of what
    :meth:`ServingEngine._launch` handed the device.

    ``rows``: the ``(handle, slot)`` pairs whose decode step a program
    of the tick carries, ``picked`` that program's tokens (on the
    device; row ``base + slot`` is slot's), ``logits`` its decode rows'
    logits (the megakernel lane's only output), ``sampled`` whether a
    row's floats are wanted on the host, ``ecounts`` an EP program's
    expert counts, ``stat_rows`` the rows its ``STEP_STATS`` counted,
    ``fused`` 1 where it is a chunk program, ``first`` that chunk's
    request. ``finished``: ``(handle, last)`` for every prompt that
    became resident in the tick and is owed its first token from
    ``last``, its final chunk's ``(picked, logits)``. ``token_at``:
    ``id(handle) -> (picked array, index)``, where each request's newest
    token lies on the device, for the next launch to feed from.
    ``programs`` counts the tick's dispatches, ``decoded`` what a tick
    that picks on the host (speculation) already served; ``tick`` is
    the index of the tick that launched it."""

    __slots__ = ("t0", "tick", "rows", "picked", "base", "logits", "sampled",
                 "ecounts", "stat_rows", "fused", "first", "finished",
                 "token_at", "programs", "decoded")

    def __init__(self, tick: int):
        self.t0, self.tick = time.perf_counter(), tick
        self.rows, self.finished, self.token_at = [], [], {}
        self.picked = self.logits = self.ecounts = self.first = None
        self.base = self.stat_rows = self.fused = 0
        self.sampled = False
        self.programs = self.decoded = 0

    def board(self, active, picked, base, logits, ecounts=None, *,
              sampled, stat_rows, fused=0, first=None):
        """Book the program that carries ``active``'s decode step."""
        self.rows = [(h, h.slot) for h in active]
        self.picked, self.base, self.logits = picked, base, logits
        self.sampled, self.stat_rows, self.fused = sampled, stat_rows, fused
        self.ecounts, self.first = ecounts, first
        for h, slot in self.rows:
            h.in_flight += 1
            if picked is not None and h.request.temperature <= 0.0:
                self.token_at[id(h)] = (picked, base + slot)

    def owe_first(self, h, last):
        """Book ``h``'s first token, owed from ``last``."""
        self.finished.append((h, last))
        h.in_flight += 1
        if last[0] is not None and h.request.temperature <= 0.0:
            self.token_at[id(h)] = (last[0], 0)


def save_checkpoint(snap: dict, path: str) -> str:
    """Persist a :meth:`ServingEngine.checkpoint` snapshot to ``path``
    (pickle; numpy pools incl. ml_dtypes fp8 round-trip bit-exact).
    The snapshot bytes ride a versioned envelope with their payload
    digest (:data:`CKPT_FILE_FORMAT`) — :func:`load_checkpoint`
    verifies it. Atomic: written to a temp file and renamed, so a
    SIGKILL mid-write leaves the previous checkpoint intact. Returns
    ``path``."""
    import os
    import pickle

    from triton_dist_tpu.resilience.integrity import digest_bytes

    payload = pickle.dumps(snap, protocol=pickle.HIGHEST_PROTOCOL)
    env = {"format": CKPT_FILE_FORMAT,
           "digest": digest_bytes(payload),
           "payload": payload}
    tmp = f"{path}.tmp{os.getpid()}"
    with open(tmp, "wb") as f:
        pickle.dump(env, f, protocol=pickle.HIGHEST_PROTOCOL)
    os.replace(tmp, path)
    return path


def load_checkpoint(path: str) -> dict:
    """Read a snapshot :func:`save_checkpoint` wrote (feed it to
    :meth:`ServingEngine.restore` on a freshly-built engine).

    Raises :class:`~triton_dist_tpu.resilience.integrity.
    CheckpointCorruptError` when the file is truncated, unpicklable,
    or its payload digest mismatches the envelope — the supervisor's
    ring catches it and falls back to the previous snapshot. A
    pre-envelope file (a raw snapshot dict) still loads; a missing
    file raises ``FileNotFoundError`` (absence is not corruption)."""
    import pickle

    from triton_dist_tpu.resilience.integrity import (
        CheckpointCorruptError, digest_bytes)

    with open(path, "rb") as f:
        try:
            obj = pickle.load(f)
        except Exception as e:       # noqa: BLE001 — truncation, junk
            raise CheckpointCorruptError(
                path, f"unreadable envelope: {e!r}") from e
    if isinstance(obj, dict) and obj.get("format") == CKPT_FILE_FORMAT:
        payload = obj.get("payload")
        if not isinstance(payload, (bytes, bytearray)):
            raise CheckpointCorruptError(path, "envelope has no payload")
        got = digest_bytes(bytes(payload))
        if got != obj.get("digest"):
            raise CheckpointCorruptError(
                path, "payload digest mismatch",
                want=obj.get("digest"), got=got)
        try:
            return pickle.loads(bytes(payload))
        except Exception as e:       # noqa: BLE001
            raise CheckpointCorruptError(
                path, f"unpicklable payload: {e!r}") from e
    if isinstance(obj, dict) and "meta" in obj:
        return obj                   # legacy pre-envelope snapshot
    raise CheckpointCorruptError(
        path, f"not a checkpoint envelope (top-level "
              f"{type(obj).__name__})")


class ServingEngine:
    """Continuous-batching server over a layer ``Engine`` or a
    ``MegaKernelEngine`` (see module docstring).

    ``num_slots``: decode-batch width (layer path; the megakernel path
    is pinned to the engine's ``batch``). ``page``: tokens per KV page
    (layer path; must divide the engine's ``max_len`` so the paged
    view is position-exact with the dense baseline). ``num_pages``:
    pool size incl. the reserved scratch page (default: full residency
    for every slot). ``policy``: ``"continuous"`` | ``"static"`` (gang
    batching — the bench ablation). ``attn_impl``: ``"ref"`` |
    ``"kernel"`` | ``"flash"`` (layer path; default ref — token-exact
    and interpret-friendly; ``"kernel"`` streams decode through the
    paged flash kernel; ``"flash"`` does that AND routes chunked
    prefill + speculative verification through the paged Q-block
    kernel — Pallas paged attention on every serving attention).
    ``chunk_attn`` overrides the chunk/verify half independently
    (``"ref"`` | ``"flash"``; default derived from ``attn_impl``).
    ``timeout_s`` arms a watchdog on every decode dispatch; ``clock``
    is injectable for deadline tests.
    """

    def __init__(self, engine, *, num_slots: Optional[int] = None,
                 page: Optional[int] = None,
                 num_pages: Optional[int] = None, max_queue: int = 64,
                 policy: str = "continuous", attn_impl: str = "ref",
                 chunk_attn: Optional[str] = None,
                 prefix_reuse: bool = False, timeout_s=None,
                 clock=time.monotonic, transport: Optional[str] = None,
                 replica_slots: int = 0, rebalance_every: int = 8,
                 hot_expert_factor: float = 2.0,
                 load_alpha: float = 0.25,
                 prefill_buckets: Optional[Sequence[int]] = None,
                 kv_dtype: str = "bf16", spec_k: int = 0,
                 spec_ngram: int = 3, retry=None,
                 telemetry: str = "counters",
                 telemetry_capacity: int = 4096,
                 kv_tiers=None, park_quant: Optional[str] = None,
                 slo=None):
        """EP-MoE decode knobs (no-ops for dense models):

        - ``transport``: EP decode dispatch path ("ar" | "ragged" |
          "ll" | "ll2d" | "auto"); default = the engine's
          ``ep_transport``. "auto" is resolved ONCE here against the
          tune cache for the actual (mesh, num_slots, hidden, dtype)
          decode shape, so the jitted decode dispatch never
          re-specializes. A hierarchical (EP2DContext) engine resolves
          to the 2-hop "ll2d" path unless tuned otherwise. The megakernel
          path serves experts in-kernel (TP regime); the knob is
          recorded but dispatch stays in-kernel.
        - ``replica_slots``: hot-expert replica weight slots per MoE
          layer (layer path, ``"ll"`` transport). When an expert's
          load EWMA crosses ``hot_expert_factor``× the mean, its
          weights are copied onto the least-loaded rank and alternate
          assignments reroute there — replica choice is data, never a
          recompile.
        - ``rebalance_every``: decode dispatches between replication /
          scheduler-priority refreshes (0 = telemetry only).
        - ``load_alpha``: EWMA smoothing for per-expert load.

        ``prefill_buckets`` (layer path): switch prefill from the
        monolithic per-length dispatch to FIXED-SHAPE chunked prefill —
        prompts stream into the page pool in bucketed chunks (padded to
        bucket), a few chunks per serving tick (:meth:`_launch`),
        interleaved with decode.
        The prefill jit cache is then bounded by the bucket count
        (:meth:`prefill_cache_size`) instead of growing per distinct
        prompt/resume length, and a long prompt no longer monopolizes
        the dispatch. ``None`` keeps the monolithic path. (The
        megakernel path has its own prefill lane — pass ``None``.)

        ``kv_dtype`` (layer path): ``"bf16"`` keeps the pool at the
        engine's native dtype (bit-identical to ``Engine.serve``);
        ``"int8"``/``"fp8"`` store the K/V pools per-page QUANTIZED
        with fp32 scales alongside — 2–4x more resident tokens per
        HBM byte at a bounded logit divergence (see docs/serving.md).

        ``spec_k`` (layer path): 0/1 = plain one-token decode; K ≥ 2
        enables SPECULATIVE decoding — an n-gram self-draft proposes
        K-1 continuations and one fixed-shape K-token verification
        dispatch scores them; accepted tokens (greedy requests) commit
        several tokens per dispatch, token-exact with the non-spec
        greedy run by construction. ``spec_ngram`` bounds the draft's
        n-gram length. The verification dispatch attends via
        ``chunk_attn``: ``"flash"`` streams pages through the K-query
        :func:`~triton_dist_tpu.ops.paged_flash_qblock.
        paged_flash_qblock` kernel (no dense-row materialization);
        ``"ref"`` is the dense-row gather path (docs/serving.md,
        "Attention implementations").

        ``retry``: a :class:`~triton_dist_tpu.resilience.policy.
        RetryPolicy` (applied to every retryable serving op), or a
        ``{op: RetryPolicy}`` dict, or ``None`` (no retries — the
        pre-existing fail-one behaviour). Retryable ops today:
        ``"page_migration"`` (the disaggregated KV handoff),
        ``"chunked_prefill"`` (the bucketed chunk dispatch),
        ``"tier_transfer"`` (the tier hop), and the shared
        ``"serving_decode"`` / ``"spec_verify"`` dispatches — all are
        replay-idempotent (staging pages, two-phase prefix
        publication, position-keyed appends; the decode/verify length
        mirrors only advance on success), so a dropped transfer or a
        TRANSIENT dropped dispatch is retried with deterministic
        exponential backoff before the request is failed. A WEDGED
        dispatch (``CommTimeoutError``) is never retried on the
        decode/verify ops — a wedge blocks its own replay — and goes
        straight to the fail-one containment (docs/resilience.md).
        Each absorbed transient increments ``stats()["retries"]``.

        ``kv_tiers`` (layer path): the tier BELOW the paged HBM pool —
        a :class:`~triton_dist_tpu.serving.tiers.KVTierStore` (or
        ``True`` for the defaults, or a kwargs dict). With it on,
        scored prefix-cache eviction DEMOTES cold committed prefix
        pages into host RAM (then disk) instead of dropping them, a
        later same-prefix admission prefetches them back
        (``tier_hits``), and :meth:`park`/:meth:`resume` become
        first-class serving verbs — a parked session's KV offloads
        wholesale, its slot and pages free for other traffic, and the
        resume prefetch overlaps in-flight decode ticks
        (docs/serving.md, "KV memory hierarchy").

        ``park_quant``: ``None`` (default — parked payloads keep
        their pool bytes verbatim, resume is BIT-exact) or
        ``"int8"``/``"fp8"`` to requantize an unquantized pool's
        parked payload host-side ("quantize harder": 2–4x smaller
        host bytes at a bounded divergence after resume; quantized
        pools always park their stored bytes + scales, bit-exact).

        ``telemetry``: ``"off"`` | ``"counters"`` (default) |
        ``"spans"`` — the :mod:`~triton_dist_tpu.obs` recording level.
        Counters mode folds TTFT / inter-token / per-op latency
        histograms (surfaced in ``stats()["latency"]``); spans mode
        additionally records the full typed-span timeline into a
        bounded ring of ``telemetry_capacity`` entries (JSONL export,
        Perfetto merge via :meth:`trace`). All stamping is host-side
        on the injectable ``clock`` — token outputs and every jit
        no-growth gate are identical across modes
        (docs/observability.md).
        """
        from triton_dist_tpu.megakernel.engine import MegaKernelEngine
        from triton_dist_tpu.resilience.policy import RetryPolicy
        from triton_dist_tpu.serving.blocks import kv_quant_spec
        from triton_dist_tpu.serving.spec import NgramDraft

        if retry is None:
            self.retry_policies = {}
        elif isinstance(retry, RetryPolicy):
            self.retry_policies = {op: retry for op in
                                   ("page_migration",
                                    "chunked_prefill",
                                    "tier_transfer",
                                    "serving_decode",
                                    "spec_verify")}
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

        from triton_dist_tpu.obs import Telemetry

        # The telemetry sink rides the SAME injectable clock as the
        # scheduler, so fake-clock tests see deterministic timelines;
        # built first — the draft, chunker, and layer-path plumbing
        # below all hold a reference. Passing a Telemetry INSTANCE
        # shares one timeline across engines (the fleet router's
        # merged-fleet view — docs/serving.md, "Fleet serving").
        if isinstance(telemetry, Telemetry):
            self.obs = telemetry
        else:
            self.obs = Telemetry(telemetry, clock=clock,
                                 capacity=telemetry_capacity)
        self._trace_session = None

        kv_quant_spec(kv_dtype)        # validate the knob early
        self.kv_dtype = kv_dtype
        if attn_impl not in ("ref", "kernel", "flash"):
            raise ValueError(
                f"attn_impl must be 'ref' | 'kernel' | 'flash', got "
                f"{attn_impl!r}")
        self.attn_impl = attn_impl
        # chunk_attn covers the Q-BLOCK dispatches (chunked prefill +
        # speculative verification); attn_impl="flash" flips it too
        # unless overridden — one knob value = Pallas paged attention
        # on every serving attention.
        if chunk_attn is None:
            chunk_attn = "flash" if attn_impl == "flash" else "ref"
        if chunk_attn not in ("ref", "flash"):
            raise ValueError(
                f"chunk_attn must be 'ref' | 'flash', got "
                f"{chunk_attn!r}")
        self.chunk_attn = chunk_attn
        self.spec_k = int(spec_k)
        if self.spec_k < 0:
            raise ValueError(f"spec_k must be >= 0, got {spec_k}")
        self._draft = NgramDraft(spec_ngram, telemetry=self.obs)

        # KV memory hierarchy: the host/disk tier below the HBM pool
        # (docs/serving.md, "KV memory hierarchy").
        from triton_dist_tpu.serving.tiers import KVTierStore

        if kv_tiers is None or kv_tiers is False:
            self.tiers = None
        elif isinstance(kv_tiers, KVTierStore):
            self.tiers = kv_tiers
        elif kv_tiers is True:
            self.tiers = KVTierStore()
        elif isinstance(kv_tiers, dict):
            self.tiers = KVTierStore(**kv_tiers)
        else:
            raise TypeError(
                "kv_tiers must be a KVTierStore, a kwargs dict, True, "
                f"or None — got {type(kv_tiers).__name__}")
        if park_quant is not None:
            if kv_quant_spec(park_quant)[0] is None:
                park_quant = None          # "bf16" = keep verbatim
            elif kv_quant_spec(kv_dtype)[0] is not None:
                raise ValueError(
                    f"park_quant={park_quant!r} applies to an "
                    "UNQUANTIZED pool (a quantized pool parks its "
                    "stored bytes + scales verbatim, already small "
                    "and bit-exact)")
        self.park_quant = park_quant
        if self.park_quant is not None and self.tiers is None:
            raise ValueError("park_quant needs kv_tiers (parking "
                             "offloads into the tier store)")
        # Parked sessions: request_id -> handle (token-preserving; no
        # slot, no queue position). _resuming holds last tick's
        # prefetch dispatches, activated at the next tick boundary so
        # the scatter overlaps the decode dispatches in between.
        self._parked: dict = {}
        self._resuming: List = []
        # Multi-tenant SLO arbitration (docs/serving.md, "Multi-tenant
        # SLO scheduling"): when armed, submissions land in per-tenant
        # bounded queues and the SLOScheduler releases them into the
        # continuous-batching queue each tick — quotas, deadline
        # classes, DRR fair share, and priority preemption.
        from triton_dist_tpu.serving.slo import SLOScheduler

        if slo is None or slo is False:
            self.slo = None
        elif isinstance(slo, SLOScheduler):
            self.slo = slo
        elif slo is True:
            self.slo = SLOScheduler()
        elif isinstance(slo, dict):
            self.slo = SLOScheduler(**slo)
        else:
            raise TypeError(
                "slo must be an SLOScheduler, a kwargs dict, True, or "
                f"None — got {type(slo).__name__}")
        # Router-time predictive prefetch (docs/serving.md, "Fleet
        # serving"): prefix payloads whose tier_transfer already ran
        # at ROUTE time — the admission-time fetch consumes them
        # without a second transfer, so the hop overlaps queue wait.
        # Bounded drop-oldest; entries are popped on use and whenever
        # the same key re-publishes in HBM (on_commit below).
        from collections import OrderedDict as _OD

        self._tier_warm: "_OD" = _OD()
        self._tier_warm_cap = 32

        self.engine = engine
        self.mega = isinstance(engine, MegaKernelEngine)
        self.replica_slots = int(replica_slots)
        self.rebalance_every = int(rebalance_every)
        self.hot_expert_factor = float(hot_expert_factor)
        self.load_alpha = float(load_alpha)
        if transport is not None:
            from triton_dist_tpu.layers.ep_moe import DECODE_TRANSPORTS

            if transport not in DECODE_TRANSPORTS:
                raise ValueError(f"transport={transport!r} not in "
                                 f"{DECODE_TRANSPORTS}")
        self.transport = transport
        self.ep = False                  # layer-path EP-MoE decode
        self.ep2d = False                # hierarchical (ICI×DCN) EP
        self.replicas = None
        self.expert_hist: List[np.ndarray] = []
        self._hist_active = False
        self._replicated = {}            # expert id -> replica rank
        self._replica_free = list(range(self.replica_slots))
        self._mk_counts_base = None
        self._mk_load_sig = None
        ne = getattr(engine.cfg, "num_experts", 0) or 0
        self.expert_totals = np.zeros((ne,), np.int64)
        self.expert_ewma = np.zeros((ne,), np.float64)
        self.timeout_s = (timeout_s if timeout_s is not None
                          else getattr(engine, "timeout_s", None))
        if isinstance(engine, MegaKernelEngine) and timeout_s is not None:
            # The megakernel path bounds its own step dispatch; arm it.
            engine.timeout_s = timeout_s
        self.cfg = engine.cfg
        self.max_len = engine.max_len
        self.stats_counters = {
            "decode_dispatches": 0, "decode_dispatches_fused": 0,
            "tokens_generated": 0, "tokens_picked_on_device": 0,
            "prefill_tokens": 0, "prefill_calls": 0, "admit_stalls": 0,
            "preemptions": 0, "comm_timeouts": 0, "decode_time_s": 0.0,
            "decode_tokens": 0, "prefill_chunks": 0,
            "chunk_dispatches_parked": 0,
            "chunk_dispatches_padded_up": 0, "prefill_rows_padded": 0,
            "chunk_dispatches_kernel_walk": 0,
            "chunk_dispatches_kernel_scan": 0,
            "chunk_dispatches_kernel_experts": 0, "migrated_pages": 0,
            "spec_drafted": 0, "spec_accepted": 0,
            "spec_sampled_fallbacks": 0,
            "greedy_agree_tokens": 0, "greedy_ref_tokens": 0,
            "retries": 0, "failovers": 0, "restored_requests": 0,
            "tier_hits": 0, "tier_misses": 0, "offloaded_pages": 0,
            "prefetched_pages": 0, "parks": 0, "resumes": 0,
            "router_prefetched_pages": 0, "worker_prefetched_pages": 0,
            "integrity_failures": 0, "slo_preemptions": 0,
            "ticks": 0,
            # The tick in two halves: dispatching ticks left in flight
            # (the next is launched before their tokens are fetched),
            # those landed in the step() that launched them (by the
            # first reason that held: ``stats()["in_order_by"]``), and
            # decode rows launched for a request that had ended.
            "ticks_launched_ahead": 0, "ticks_in_order": 0,
            "rows_discarded": 0,
            # Held experts (a model with STEP_STATS): token-expert
            # pairs the read programs routed, the ones that fell to
            # held experts, the fullest expert's rows a layer, and
            # the passes the held experts ran.
            "expert_pairs_routed": 0, "expert_pairs_held": 0,
            "expert_rows_max": 0, "expert_passes": 0, "expert_steps": 0,
            # First chunks that zeroed a slot's sequence state (a model
            # whose pool states one).
            "seq_state_resets": 0,
        }
        # The tick launched and not yet landed (a :class:`_Flight`), why
        # ticks were landed in order, and when the last one landed.
        self._flight = None
        self._in_order: dict = {}
        self._landed_at = 0.0
        self._step_stats = ()
        self._row_stats = ()
        # Bytes of what the sequences keep beside their pages (a pool
        # that states such arrays; else 0).
        self._seq_state_bytes = 0
        # The two pools of a model with window layers (else empty).
        self._pool_bytes = {}
        self.prefill_buckets = (tuple(sorted(set(int(b) for b in
                                                 prefill_buckets)))
                                if prefill_buckets else None)
        # The chunk driver this engine streams prefills through:
        # ``self`` for in-place chunked prefill (chunks write straight
        # into the serving pool), the disaggregated subclass points it
        # at its PrefillWorker, None = monolithic prefill.
        self._prefiller = None
        self.chunker = None
        # Whether this engine's own chunker carries the decode batch
        # (set where the layer path builds it; never by the caller).
        self._rides = False
        self._kernels_of = {}     # bucket -> the model's step_kernels

        if self.mega:
            # kv_dtype / spec_k are ENGINE knobs on the megakernel lane
            # (the arena schema, scale tables, and verification builder
            # are all construction-time): the engine must have been
            # built with the matching values — this layer validates,
            # plans capacity, and drives the verification tick.
            eng_kvd = getattr(engine, "kv_dtype", "bf16")
            if kv_quant_spec(kv_dtype)[0] != kv_quant_spec(eng_kvd)[0] \
                    or (kv_quant_spec(kv_dtype)[0] is not None
                        and kv_dtype != eng_kvd):
                raise ValueError(
                    f"megakernel kv_dtype mismatch: the engine stores "
                    f"{eng_kvd!r} pools but the serving layer was "
                    f"asked for {kv_dtype!r} — construct "
                    f"MegaKernelEngine(kv_dtype={kv_dtype!r}, "
                    "paged=True) and pass the same value here")
            self.kv_dtype = eng_kvd
            # spec_k=1 degenerates to plain decode on BOTH sides (the
            # engine coerces it at construction) — normalize before
            # comparing so matching ctor arguments never "mismatch".
            if self.spec_k == 1:
                self.spec_k = 0
            # Both directions: an engine built WITH spec_k but served
            # without it would drive decode_step while expert_counts
            # reads the verify builder's (never-written) counter
            # region — fail loudly like the kv_dtype mismatch does.
            if (self.spec_k or 0) != (getattr(engine, "spec_k", 0)
                                      or 0):
                raise ValueError(
                    f"megakernel spec_k mismatch: the engine was built "
                    f"with spec_k={getattr(engine, 'spec_k', 0)} but "
                    f"the serving layer was asked for {self.spec_k} — "
                    "construct MegaKernelEngine(spec_k=K, paged=True) "
                    "and pass the same K here")
            # prefill_buckets is an ENGINE knob here too (the chunk
            # task pair is compiled at engine construction): validate
            # both directions like kv_dtype/spec_k above.
            eng_buckets = getattr(engine, "prefill_buckets", None)
            if (self.prefill_buckets or None) != (eng_buckets or None):
                raise ValueError(
                    f"megakernel prefill_buckets mismatch: the engine "
                    f"was built with prefill_buckets={eng_buckets} "
                    f"but the serving layer was asked for "
                    f"{self.prefill_buckets} — construct "
                    "MegaKernelEngine(prefill_buckets=..., paged=True) "
                    "and pass the same buckets here")
            if self.replica_slots:
                raise ValueError(
                    "replica_slots is a layer-path EP knob; the "
                    "megakernel serves every expert in-kernel (TP "
                    "regime) and rebalances via the dynamic "
                    "scoreboard's expert-load claim priority instead")
            if self.attn_impl != "ref" or self.chunk_attn != "ref":
                raise ValueError(
                    "attn_impl/chunk_attn are layer-path knobs; the "
                    "megakernel's attention rides its own in-arena "
                    "task lane (docs/serving.md)")
            if self.tiers is not None:
                raise NotImplementedError(
                    "kv_tiers on the megakernel lane: the tier "
                    "gather/scatter path addresses layer-shaped pool "
                    "leaves, but the megakernel's KV lives in its "
                    "in-kernel arena (the arena-tier limitation) — "
                    "tracked by ROADMAP Open item 3, 'Megakernel "
                    "serving parity — remainder'")
            num_slots = engine.batch
            if engine.paged:
                page = engine.builder.page
                p_max = engine.builder.p_max
                if engine.num_pages < num_slots * p_max + 1:
                    raise ValueError(
                        "paged megakernel serving reserves page 0 as "
                        f"scratch: construct the engine with num_pages "
                        f">= batch*p_max+1 (= {num_slots * p_max + 1}, "
                        f"got {engine.num_pages})")
                self.page, self.p_max = page, p_max
                # Capacity plan off the model geometry (mk pools are
                # fp32-native): surfaces bytes_per_token and the
                # quantization capacity ratio in stats, exactly like
                # the layer path.
                self.plan = self.cfg.kv_cache_plan(
                    max_len=self.max_len, page=page,
                    num_slots=num_slots,
                    tp=engine.mesh.shape[engine.axis],
                    dtype_bytes=4, kv_dtype=self.kv_dtype)
                self.manager = BlockManager(
                    engine.num_pages, page, p_max,
                    prefix_reuse=prefix_reuse,
                    page_bytes=self.plan["page_bytes_per_rank"],
                    native_page_bytes=self.plan[
                        "native_page_bytes_per_rank"])
                if self.prefill_buckets:
                    # Chunked admission over the megakernel chunk task
                    # pair: the SAME _admit_chunked/_advance_chunk
                    # stream as the layer path, driving
                    # MegaChunkedPrefill instead of ChunkedPrefill.
                    from triton_dist_tpu.serving.chunked import (
                        MegaChunkedPrefill)
                    self.chunker = MegaChunkedPrefill(
                        engine, telemetry=self.obs)
                    self._prefiller = self
                    # _advance_chunk threads p.cache through the
                    # chunker; the mk pool lives inside the engine's
                    # aliased step operands, so the serving-layer
                    # handle is a placeholder the adapter returns
                    # untouched.
                    self.cache = None
            else:
                # Dense megakernel cache: each slot owns a (max_len,)
                # row — no pages to manage, only the live-slot mask.
                self.page = self.max_len
                self.p_max = 1
                self.manager = None
        else:
            num_slots = num_slots or 4
            page = page or math.gcd(self.max_len, 32)
            if self.max_len % page:
                raise ValueError(
                    f"page={page} must divide max_len={self.max_len} "
                    "(keeps the paged view position-exact with the "
                    "dense baseline)")
            self.page = page
            self.p_max = self.max_len // page
            # Pool sized off the MODEL CONFIG (full residency for every
            # slot by default; undersize num_pages to exercise
            # backpressure). The plan carries the quantization's
            # bytes-per-token / capacity-ratio surface into stats.
            import numpy as _np

            import jax as _jax

            dtype_bytes = _np.dtype(
                _jax.tree.leaves(engine.params)[0].dtype).itemsize
            self.plan = self.cfg.kv_cache_plan(
                max_len=self.max_len, page=page, num_slots=num_slots,
                tp=engine.mesh.shape[engine.axis],
                dtype_bytes=dtype_bytes, kv_dtype=self.kv_dtype)
            num_pages = num_pages or self.plan["num_pages"]
            self.manager = BlockManager(
                num_pages, page, self.p_max, prefix_reuse=prefix_reuse,
                page_bytes=self.plan["page_bytes_per_rank"],
                native_page_bytes=self.plan[
                    "native_page_bytes_per_rank"],
                window=self._window_layers(num_slots, prefix_reuse))
            self._build_layer_path(num_slots, num_pages)

        self.sched = Scheduler(num_slots, max_queue=max_queue,
                               policy=policy, clock=clock)
        self.num_slots = num_slots
        # Host mirrors (numpy) of the per-slot device state — the
        # scheduler never syncs the device to make a decision.
        self._lens = np.zeros((num_slots,), np.int32)
        self._live = np.zeros((num_slots,), np.int32)
        self._toks = np.zeros((num_slots,), np.int32)

    # -- layer-path construction ------------------------------------

    def _window_layers(self, num_slots: int, prefix_reuse: bool):
        """The window layers the served model's pool states, sized for
        this server (``WindowLayers.sized``: the ring holds the window
        and the largest chunk program's rows), or None. A ring's pages
        are written over while their request runs and are found through
        a table of their own, so whatever shares, moves or rolls back
        PAGES by the one table is refused here, by name."""
        states = getattr(self.engine.model, "paged_pool", None)
        keeps = states(self.cfg)[2:] if states is not None else ()
        window = dict(keeps[0]).get("window") if keeps else None
        if window is None:
            return None
        refused = [
            (bool(self.spec_k), "spec_k: a refused candidate's entry "
             "would have written over a key the ring still needs"),
            (prefix_reuse, "prefix_reuse: a ring's pages are written "
             "over while their request runs, and none can be another "
             "request's prefix"),
            (self.tiers is not None, "kv_tiers (and with them park and "
             "resume): a tier moves pages by the one table, and the "
             "rings would stay behind"),
            (not self.prefill_buckets, "the monolithic prompt blit: "
             "only a chunk program writes a ring "
             "(prefill_buckets=...)"),
            (self.kv_dtype not in (None, "bf16", "native"),
             f"kv_dtype={self.kv_dtype!r}: the window layers' pool is "
             "not quantized")]
        for on, why in refused:
            if on:
                raise NotImplementedError(
                    "a model with window layers is served without "
                    f"{why}")
        return window.sized(self.page, max(self.prefill_buckets),
                            num_slots)

    def _build_layer_path(self, num_slots: int, num_pages: int):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        eng = self.engine
        model = eng.model
        if not hasattr(model, "decode_step_paged"):
            raise NotImplementedError(
                f"model {getattr(model, '__name__', model)!r} has no "
                "decode_step_paged — serve it through the megakernel "
                "engine instead")
        cfg, mesh, axis = eng.cfg, eng.mesh, eng.axis
        n = mesh.shape[axis]
        # The model states the pool it keeps (``paged_pool``: the class
        # and what a token takes in a layer: K and V of every KV head,
        # or one latent; and, third, where it differs from that: how
        # many of its layers keep pages at all, ``layers``, and what a
        # SEQUENCE keeps beside them, ``seq_state``, name -> SeqArray);
        # the server allocates that. GLOBAL sizes here — the sharding
        # carves the per-shard part the step sees; the pool is
        # allocated under that sharding, never whole on one device.
        pool_cls, per_token, *keeps = model.paged_pool(cfg)
        keeps = dict(keeps[0]) if keeps else {}
        pool_layers = keeps.pop("layers", cfg.num_hidden_layers)
        # Window layers, where the pool states any: as the manager
        # holds them, sized; the cache's specs are then asked for with
        # the ring, which is static in its tree.
        window = self.manager.window
        cache_specs = model.paged_cache_specs
        if window is not None:
            import functools as _ft

            keeps["window"] = window
            cache_specs = _ft.partial(cache_specs, ring=window.ring)
        if pool_cls is not PagedKVCache and (
                self.tiers is not None or not self.prefill_buckets):
            raise NotImplementedError(
                f"a {pool_cls.__name__} is filled by chunked prefill "
                "(prefill_buckets=...) and neither tiered nor parked: "
                "tier transfers and the monolithic prompt blit move "
                "K and V pages")
        if keeps.get("seq_state"):
            # A state a sequence keeps is not position-addressed.
            refused = [
                (bool(self.spec_k), "spec_k: lengths cannot roll a "
                 "state back past a refused candidate"),
                (self.manager.prefix_reuse, "prefix_reuse: a shared "
                 "page holds no state to start the rest of a prompt "
                 "from"),
                (self.tiers is not None, "kv_tiers (and with them "
                 "park and resume): a tier moves pages, and the state "
                 "would stay behind"),
                (not self.prefill_buckets, "the monolithic prompt "
                 "blit: only a chunk program resets and carries the "
                 "state (prefill_buckets=...)")]
            for on, why in refused:
                if on:
                    raise NotImplementedError(
                        "a model whose sequences keep state beside "
                        f"their pages is served without {why}")
        cache, shardings = pool_cls.empty_sharded(
            mesh, cache_specs, axis,
            pool_layers, num_pages, self.page,
            *per_token, num_slots=num_slots, p_max=self.p_max,
            dtype=jax.tree.leaves(eng.params)[0].dtype,
            kv_dtype=self.kv_dtype, **keeps)
        # What the pool stated, for ``stats()``. Every decode slot owns
        # its sequence state, so admission's second budget (beside
        # pages) IS the slots and ``Scheduler.admit`` counts one thing;
        # fewer states than slots would need a count of its own there.
        self._pool_layers = pool_layers
        self._seq_layers = max((a.shape[0] for a in (
            keeps.get("seq_state") or {}).values()), default=0)
        self._seq_state_bytes = sum(int(v.nbytes)
                                    for v in cache.seq.values())
        if window is not None:
            # The two pools' bytes over the mesh, and what ONE table
            # for all paged layers would take at the same slots and
            # max_len (every layer a page for every position).
            a_page = 2 * cache.k_pages.dtype.itemsize * int(
                np.prod(cache.k_pages.shape[2:]))
            self._pool_bytes = {
                "window_layers": window.layers,
                "global_layers": pool_layers,
                "pool_bytes_global": a_page * pool_layers * num_pages,
                "pool_bytes_window": (a_page * window.layers
                                      * window.num_pages),
                "pool_bytes_one_table": a_page * (
                    pool_layers + window.layers) * (
                        1 + num_slots * self.p_max)}
        kv_spec = cache_specs(axis, quantized=cache.quantized)
        self.cache = cache
        # The pool's pinned shardings — every writer into it (prompt
        # blit, chunk steps, page-migration scatter) must return leaves
        # with EXACTLY these, or the decode dispatch re-specializes.
        self._cache_shardings = shardings

        # EP-MoE decode: resolve the transport knob ONCE (host-side,
        # against the tune cache, with the true decode batch shape) so
        # the jitted dispatch below never re-specializes; thread it and
        # the replica state through decode_step_paged alongside the
        # on-device expert-counts output.
        from triton_dist_tpu.layers import ep_moe as _ep_moe
        from triton_dist_tpu.ops.ep_a2a import (EPContext as _EPCtx,
                                                EP2DContext as _EP2D)

        mk = dict(eng.model_kwargs)
        ep_ctx = mk.get("ep_ctx")
        self.ep = (mk.get("moe_impl") == "ep"
                   and isinstance(ep_ctx, _EPCtx))
        self.ep2d = (mk.get("moe_impl") == "ep"
                     and isinstance(ep_ctx, _EP2D))
        if self.ep:
            # Key the tune lookup on the EXPERT weight dtype — the
            # same key tune_transport persists under (a mixed-dtype
            # checkpoint's first param leaf may be the fp32 router).
            dtype = eng.params["layers"][0]["moe"]["w_gate"].dtype
            tr = self.transport or getattr(eng, "ep_transport",
                                           None) or "ar"
            tr = _ep_moe.resolve_transport(
                tr, ctx=ep_ctx, batch=num_slots,
                hidden=cfg.hidden_size, dtype=dtype,
                topk=cfg.num_experts_per_tok)
            self.transport = tr
            mk["transport"] = tr
            mk["with_expert_counts"] = True
            if self.replica_slots and tr != "ll":
                raise ValueError(
                    "replica_slots needs transport='ll' (replica "
                    f"rerouting rides the count-free dispatch), "
                    f"resolved transport is {tr!r}")
            if self.replica_slots:
                from jax.sharding import NamedSharding

                # Pin the replica state's (replicated) shardings once:
                # a refresh must hand the decode dispatch arrays with
                # IDENTICAL shardings or the jit cache would grow on
                # the first post-replication step.
                self._replica_shardings = jax.tree.map(
                    lambda s: NamedSharding(mesh, s),
                    _ep_moe.replica_specs())
                self.replicas = jax.tree.map(
                    jax.device_put,
                    _ep_moe.init_replicas(
                        cfg, slots=self.replica_slots,
                        num_layers=cfg.num_hidden_layers, dtype=dtype),
                    self._replica_shardings)
        elif self.ep2d:
            if self.replica_slots:
                raise ValueError(
                    "replica_slots needs a flat EPContext and "
                    "transport='ll' (hierarchical EP2D decode does "
                    "not consult replicas)")
            # Same ONCE-here host-side resolution as the flat branch,
            # at the true decode shape — an untuned hierarchical mesh
            # resolves "auto" to the 2-hop 'll2d' path, never a silent
            # 'ar' fallback.
            dtype = eng.params["layers"][0]["moe"]["w_gate"].dtype
            tr = self.transport or getattr(eng, "ep_transport",
                                           None) or "auto"
            tr = _ep_moe.resolve_transport(
                tr, ctx=ep_ctx, batch=num_slots,
                hidden=cfg.hidden_size, dtype=dtype,
                topk=cfg.num_experts_per_tok)
            if tr not in ("ar", "ll2d"):
                raise ValueError(
                    f"transport={tr!r}: hierarchical (EP2D) decode "
                    "rides 'ar' or the 2-hop 'll2d' (ragged/ll need a "
                    "flat EPContext)")
            self.transport = tr
            mk["transport"] = tr
        elif self.replica_slots or self.transport:
            raise ValueError(
                "transport/replica_slots are EP-MoE decode knobs; "
                "this engine serves a non-EP model")

        if self.prefill_buckets:
            from triton_dist_tpu.serving.chunked import ChunkedPrefill

            # In-place chunked prefill writes the pool the decoders
            # read, so each bucket's one program carries the decode
            # batch too (docs/serving.md, "The decode batch rides the
            # chunk's program"). Not where the decode dispatch is
            # another program than the plain step: speculation verifies
            # K tokens a slot, EP decode has its transport, replicas
            # and expert counts.
            self._rides = not (self.spec_k or self.ep or self.ep2d)
            self.chunker = ChunkedPrefill(
                eng, shardings, self.prefill_buckets,
                attn_impl=self.chunk_attn, telemetry=self.obs,
                decode_rows=num_slots if self._rides else 0,
                decode_attn=self.attn_impl)
            self._prefiller = self

        # Pinned cache out_shardings on the decode dispatch too: every
        # producer of the pool (init device_put, prompt writer, chunk
        # steps, decode itself, migration scatter) must emit ONE
        # sharding spelling, or each producer pair costs a jit entry in
        # every consumer (PartitionSpec() and PartitionSpec(None, None)
        # place identically but key differently).
        # The step's first output is each row's greedy token, picked
        # behind the head (at TP > 1 every shard holds the gathered
        # row, so the pick is replicated): a tick of greedy rows
        # fetches those integers and leaves the logits on the device.
        from triton_dist_tpu.serving.chunked import (greedy_tokens,
                                                     picked_with_stats)

        # A model with ``STEP_STATS`` returns them last from every step;
        # they leave the chip behind the picked tokens, in their array.
        self._step_stats = tuple(getattr(model, "STEP_STATS", ()))
        # ``ROW_STATS``: one int32 a head row instead, the pass the row
        # took (``models.looped``), behind the tokens the same way; the
        # served tokens are counted by it.
        self._row_stats = tuple(getattr(model, "ROW_STATS", ()))
        if self._row_stats:
            self._picked_by_pass = np.zeros((cfg.num_passes,), np.int64)
        row_sh = NamedSharding(mesh, P(None))
        logits_sh = NamedSharding(mesh, P(None, None))
        # The decode rows' input tokens go up under the sharding the
        # programs' picked tokens come back with, and where a row's
        # token is still on the device (its program not landed) this
        # one small program takes it from there: ``idx[slot]`` is the
        # row's index in ``picked``, or -1 to keep what the host sent.
        # Either way a step program sees one committed (num_slots,)
        # int32 array, so a bucket keeps its ONE compiled program.
        self._row_sh = row_sh
        self._feed = jax.jit(
            lambda toks, idx, picked: jnp.where(
                idx >= 0, picked[jnp.maximum(idx, 0)], toks),
            out_shardings=row_sh)
        out_specs = (P(None), P(None, None), kv_spec)
        out_sh = (row_sh, logits_sh, shardings)
        if self.ep:
            out_specs += (P(None),)
            out_sh += (row_sh,)            # the expert counts, last
        if self.ep and self.replicas is not None:
            def _decode(params, toks, c, reps):
                out = model.decode_step_paged(
                    params, toks, c, cfg, mode=eng.mode, axis=axis,
                    ctxs=eng.ctxs, attn_impl=self.attn_impl,
                    replicas=reps, **mk)
                return (greedy_tokens(out[0]), *out)

            self._decode = jax.jit(jax.shard_map(
                _decode, mesh=mesh,
                in_specs=(eng._specs, P(None), kv_spec,
                          _ep_moe.replica_specs()),
                out_specs=out_specs, check_vma=False),
                donate_argnums=(2,), out_shardings=out_sh)
        else:
            def _decode(params, toks, c):
                out = model.decode_step_paged(
                    params, toks, c, cfg, mode=eng.mode, axis=axis,
                    ctxs=eng.ctxs, attn_impl=self.attn_impl, **mk)
                if self._step_stats or self._row_stats:
                    return (picked_with_stats(greedy_tokens(out[0]),
                                              out[-1]), *out[:-1])
                return (greedy_tokens(out[0]), *out)

            self._decode = jax.jit(jax.shard_map(
                _decode, mesh=mesh,
                in_specs=(eng._specs, P(None), kv_spec),
                out_specs=out_specs, check_vma=False),
                donate_argnums=(2,), out_shardings=out_sh)
        # Pinned out_shardings: the writer's output must land with the
        # exact shardings the decode dispatch was compiled for, or the
        # first post-admit step would re-specialize the jit cache.
        self._writer = jax.jit(
            lambda c, k0, v0, pids: c.write_prompt(k0, v0, pids),
            donate_argnums=(0,), out_shardings=shardings)
        self._axis_n = n

        if self.tiers is not None:
            # Tier transfer dispatches, both FIXED-SHAPE so the jit
            # cache stays bounded: the gather replicates whole-page
            # payloads off the sharded pool (ids are (1,) for a
            # single-page prefix demote or (p_max,) scratch-padded for
            # a session park — two entries, never more), the scatter
            # blits a scratch-padded (p_max,)-payload back in, donated
            # and PINNED to the pool's one sharding spelling so the
            # decode dispatch never re-specializes on a prefetch.
            rep = NamedSharding(mesh, P())
            self._tier_gather = jax.jit(
                lambda c, ids: c.gather_pages(ids),
                out_shardings=((rep,) * 4 if cache.quantized
                               else (rep, rep)))
            if cache.quantized:
                self._tier_scatter = jax.jit(
                    lambda c, k, v, ks, vs, ids: c.scatter_pages(
                        k, v, ids, ks, vs),
                    donate_argnums=(0,),
                    out_shardings=shardings)
            else:
                self._tier_scatter = jax.jit(
                    lambda c, k, v, ids: c.scatter_pages(k, v, ids),
                    donate_argnums=(0,),
                    out_shardings=shardings)
            # Scored eviction demotes instead of dropping: the hook
            # offloads the victim page's bytes (+ scales) into the
            # tier store while the page is still HBM-resident — the
            # two-phase tier transition (stage, commit, THEN free).
            self.manager.on_demote = self._demote_prefix_page
            # And the dual direction: a key committing into the HBM
            # cache (first publication OR a recompute after a faulted
            # prefetch) drops any stale tier copy -- exactly one
            # authoritative tier per page, always. The router-time
            # warm buffer is a copy of the tier payload, so it goes
            # with it.
            def _on_commit(key):
                self.tiers.pop(("prefix", key), None)
                self._tier_warm.pop(key, None)

            self.manager.on_commit = _on_commit

        self._verify = None
        if self.spec_k:
            if not hasattr(model, "verify_step_paged"):
                raise NotImplementedError(
                    f"model {getattr(model, '__name__', model)!r} has "
                    "no verify_step_paged — speculative decoding needs "
                    "the K-token verification contract (models.dense / "
                    "models.qwen_moe)")
            # The verification dispatch REPLACES the one-token decode
            # dispatch wholesale (K is static, acceptance is data), so
            # the serving jit cache still holds exactly one decode-side
            # entry after warmup. MoE models verify in the AR expert
            # regime (like prefill chunks) — transport stays a
            # plain-decode knob.
            vk = {k: v for k, v in mk.items()
                  if k in ("moe_impl", "ep_ctx")}

            def _vrf(params, toks, budget, c):
                return model.verify_step_paged(
                    params, toks, c, cfg, budget=budget, mode=eng.mode,
                    axis=axis, ctxs=eng.ctxs,
                    attn_impl=self.chunk_attn, **vk)

            self._verify = jax.jit(jax.shard_map(
                _vrf, mesh=mesh,
                in_specs=(eng._specs, P(None, None), P(None), kv_spec),
                out_specs=(P(None, None, None), kv_spec),
                check_vma=False), donate_argnums=(3,),
                out_shardings=(NamedSharding(mesh, P()), shardings))

    # -- public API --------------------------------------------------

    def submit(self, request, **kw) -> RequestHandle:
        """Admit a request (a :class:`Request`, or a prompt sequence
        plus :class:`Request` kwargs). Raises
        :class:`~triton_dist_tpu.serving.scheduler.QueueFullError` on
        backpressure and ``ValueError`` for requests that could never
        fit (fail fast, mirroring ``Engine.serve``'s bound check)."""
        # The span is submit()'s own work, so that the time between two
        # ticks splits into the program's part and the caller's; the
        # scheduler names the request, so its keys are filled in last.
        with self.obs.span("submit") as span:
            if isinstance(request, Request):
                if kw:
                    raise TypeError(
                        f"keyword args {sorted(kw)} ignored when "
                        "passing a Request — set them on the Request "
                        "itself")
            else:
                request = Request(prompt=list(request), **kw)
            if len(request.prompt) == 0:
                raise ValueError("empty prompt")
            if request.max_new_tokens < 1:
                raise ValueError("max_new_tokens must be >= 1")
            total = len(request.prompt) + request.max_new_tokens
            cap = self.p_max * self.page
            if total > cap or total > self.max_len:
                raise ValueError(
                    f"prompt {len(request.prompt)} + gen "
                    f"{request.max_new_tokens} exceeds capacity "
                    f"{min(cap, self.max_len)}")
            if self.slo is not None:
                h = self.slo.submit(self, request)
            else:
                h = self.sched.submit(request)
            if self.obs.enabled:
                span.fields.update(
                    request_id=h.request.request_id,
                    tenant=h.request.tenant,
                    prompt_tokens=len(h.request.prompt),
                    max_new_tokens=h.request.max_new_tokens)
        return h

    def step(self) -> int:
        """One serving tick: deadlines → admission → the tick's step
        programs LAUNCHED (:meth:`_launch`: prefill chunks, and one
        joint decode step, aboard the first chunk's program where this
        engine's chunker carries the decode batch) → a launched tick's
        results LANDED (:meth:`_land`: tokens fetched, sampled, emitted,
        requests retired). Which tick lands follows
        :meth:`_in_order_by`: where it names a reason, the one just
        launched (``launch(T); land(T)``); where it names none, the one
        the LAST call launched, and this call's stays in flight, its
        programs queued on the device behind the last one's
        (``launch(T+1); land(T)``), so the host's turn runs under a
        program (docs/serving.md, "The tick in two halves"). A call
        with nothing to launch lands what is in flight. Returns how many
        sequences the landed tick decoded (0 = none landed)."""
        tick = self.stats_counters["ticks"]
        self.stats_counters["ticks"] = tick + 1
        # The root span: every span and event below carries ``tick``,
        # and the leaves (schedule, decode_prep, decode_enqueue,
        # prefill_chunk of the launch; decode_wait/fetch, prefill_fetch,
        # sample, emit of the landing) tile it.
        with self.obs.span("tick", tick=tick) as span:
            why = self._in_order_by()
            if why is not None:
                self._land()
            with self.obs.span("schedule"):
                self._release_ended()
                self._schedule()
            flight = self._launch()
            decoded = self._land()
            if flight is None:
                return decoded
            # Admission may have brought a reason (a request that
            # samples).
            why = why or self._in_order_by()
            if why is None:
                self._flight = flight
                self.stats_counters["ticks_launched_ahead"] += 1
            else:
                decoded = self._land(flight)
                self.stats_counters["ticks_in_order"] += 1
                self._in_order[why] = self._in_order.get(why, 0) + 1
            if self.obs.enabled:
                span.fields["ahead"] = int(why is None)
            return decoded

    def _in_order_by(self) -> Optional[str]:
        """Why a launched tick is landed before the next is launched:
        the first that holds of the reasons below, or None, and the
        next tick is then launched ahead. Each is something this engine
        can observe of itself, and each names what reads a token VALUE
        (or rests on the length mirrors) between two launches."""
        from triton_dist_tpu.resilience import faults

        if self.mega:
            return "megakernel"       # its step returns host rows
        if self.spec_k:
            return "spec_k"           # drafts start from the last token
        if self.ep or self.ep2d:
            return "expert_parallel"  # a step's counts steer the next
        if not self._rides:
            # No buckets: admission itself prefills, picks and emits.
            # Or chunk programs that carry no decode rows.
            return "not_riding"
        # Containment rests on "the length mirrors never advanced".
        if self.timeout_s is not None:
            return "watchdog"
        if self.retry_policies:
            return "retry_policy"
        if faults.active_plan() is not None:
            return "fault_plan"
        if self.slo is not None:
            return "slo"              # arbitration preempts and parks
        if _samples(self.sched.running()):
            return "sampled"          # its next input is made on the host
        return None

    def _release_ended(self):
        """What the host knows by count it does not wait for: a request
        whose token in flight is its ``max_new_tokens``-th gives its
        slot and pages back to admission now (the device's stream order
        makes that safe: whatever a later program writes there runs
        after the one in flight). Its status stays non-terminal, its
        ``slot`` readable and the scheduler busy until the landing has
        emitted the token and retired it."""
        if self._flight is None:
            return
        for h in self.sched.running():
            if (h.status == "running" and h.in_flight
                    and len(h.tokens) + h.in_flight
                    >= h.request.max_new_tokens):
                self.sched.release(h)
                self._vacate(h.slot)

    def _schedule(self):
        """The tick's head: resumes, deadlines, SLO arbitration and
        admission into free slots."""
        if self._resuming:
            self._collect_resumes()
        now = self.sched.now()
        for h in self.sched.expired(now):
            self._fail(h, "timeout", TimeoutError(
                f"request {h.request.request_id} missed deadline "
                f"{h.request.deadline} (now {now})"))
        if self.slo is not None:
            for h in self.slo.expired(now):
                self._fail(h, "timeout", TimeoutError(
                    f"request {h.request.request_id} missed deadline "
                    f"{h.request.deadline} (now {now})"))
            # Arbitration before admission: preempt if an interactive
            # deadline is in danger, then release up to the free slot
            # capacity (class rank -> DRR -> EDF) into sched.queue.
            self.slo.pump(self)
        stalled: List[RequestHandle] = []
        for h in self.sched.admit():
            # Queue-wait closes at slot assignment, measured from the
            # handle's LAST entry into the queue (a stalled/preempted
            # handle requeues and logs another wait — the timeline
            # records each wait, never the time it already spent
            # running). It cannot be back-dated into a profiler
            # capture, so the admit event carries its length.
            self.obs.complete_span(
                "queue_wait", h.queued_at, now,
                request_id=h.request.request_id, slot=h.slot,
                tenant=h.request.tenant)
            self.obs.event("admit", request_id=h.request.request_id,
                           slot=h.slot, tenant=h.request.tenant,
                           waited_ms=(now - h.queued_at) * 1e3)
            self._admit(h, stalled)
        # Pool-starved admissions go back to the queue HEAD in their
        # original submission order (reversed appendleft — two stalls
        # in one tick must not leapfrog each other).
        for h in reversed(stalled):
            self.sched.queue.appendleft(h)

    def _drained(self) -> bool:
        """Nothing left to serve (subclasses add their in-flight
        state — e.g. pending migrations)."""
        return self.sched.idle and (self.slo is None or self.slo.idle)

    def run(self, *, max_steps: int = 100000, on_tick=None) -> None:
        """Drive :meth:`step` until queue and slots drain. ``on_tick``
        (no-arg) fires after every step at a consistent state boundary
        — the hook checkpoint-on-signal callers need without
        re-implementing the drain loop."""
        for _ in range(max_steps):
            if self._drained():
                return
            self.step()
            if on_tick is not None:
                on_tick()
        raise RuntimeError(f"serving loop did not drain in {max_steps} "
                           "steps")

    def generate(self, prompts: Sequence[Sequence[int]],
                 max_new_tokens: int = 32, **kw) -> List[List[int]]:
        """Batch convenience: submit every prompt, run to idle, return
        per-prompt token lists (order preserved)."""
        handles = [self.submit(p, max_new_tokens=max_new_tokens, **kw)
                   for p in prompts]
        self.run()
        for h in handles:
            if h.status != "done":
                raise RuntimeError(
                    f"request {h.request.request_id} ended "
                    f"{h.status}: {h.error!r}") from h.error
        return [h.tokens for h in handles]

    def stats(self) -> dict:
        """Serving counters + scheduler counters + pool fragmentation
        (the request/latency/throughput surface the profiler hooks and
        bench read)."""
        out = dict(self.stats_counters)
        out.update(self.sched.counters)
        # Why ticks were landed in the step() that launched them: the
        # first reason of ``_in_order_by`` that held, counted.
        out["in_order_by"] = dict(self._in_order)
        out["queue_depth"] = len(self.sched.queue) + (
            len(self.slo.queued_handles()) if self.slo is not None
            else 0)
        out["live_slots"] = int(self._live.sum())
        out["prefill_cache_size"] = self.prefill_cache_size()
        out["prefill_buckets"] = (list(self._prefiller.chunker.buckets)
                                  if self._prefiller is not None
                                  else None)
        # EP-MoE decode surface: which dispatch transport the decode
        # rides, and where the routed tokens actually went.
        if self.mega:
            out["dispatch_transport"] = (
                "in-kernel-tp" if getattr(self.cfg, "is_moe", False)
                else None)
        else:
            # self.transport is also resolved for EP2D engines
            # ("ll2d" unless tuned otherwise — the one-line signal
            # that the hierarchical mesh is NOT falling back to 'ar';
            # self.ep covers flat-EPContext telemetry).
            out["dispatch_transport"] = self.transport
        if self._telemetry_active or self.expert_totals.any():
            out["expert_load"] = self.expert_ewma.tolist()
            out["expert_totals"] = self.expert_totals.tolist()
            out["replicated_experts"] = dict(self._replicated)
        if self._step_stats and out["expert_pairs_routed"]:
            # Of the pairs the read programs routed, the share a held
            # expert computed, and how full the fullest expert ran
            # against the even share of them (1 = perfectly even).
            out["expert_held_share"] = (out["expert_pairs_held"]
                                        / out["expert_pairs_routed"])
            out["expert_load_imbalance"] = (
                out["expert_rows_max"] * self.cfg.held_experts
                / max(out["expert_pairs_held"], 1))
            # Rows a held expert was given, a layer of a read program.
            out["expert_rows_mean"] = out["expert_pairs_held"] / (
                self.cfg.held_experts * self.cfg.num_moe_layers
                * out["expert_steps"])
            # Passes the held experts ran, a layer of a read program:
            # 1.0 = one pass had room for the held pairs every time.
            out["expert_passes_a_layer"] = out["expert_passes"] / (
                self.cfg.num_moe_layers * out["expert_steps"])
        if self._seq_state_bytes:
            # What the sequences keep beside their pages, as the pool
            # stated it: its bytes over all slots, the slots that own
            # one (every decode slot), and how many layers keep such
            # state and how many keep pages.
            out["seq_state_bytes"] = self._seq_state_bytes
            out["seq_state_slots"] = self.num_slots
            out["seq_state_layers"] = self._seq_layers
            out["paged_layers"] = self._pool_layers
        if self._pool_bytes:
            # Window layers: how many layers keep a window and how many
            # every position, the pages of a slot's ring, ring entries
            # written over while their request ran (counted as a slot
            # is freed), and the pools' bytes beside what one table for
            # all layers would take.
            out.update(self._pool_bytes)
            out["window_pages_a_slot"] = self.manager.ring
            out["window_pages_recycled"] = self.manager.stats[
                "window_pages_recycled"]
        if self._row_stats:
            # Layers applied several times: how often, the layers the
            # pool keeps and the layer applications of one step program
            # (both passes x layers), and the served tokens by the pass
            # their logits were read from (index 0 = pass 1).
            out["passes"] = self.cfg.num_passes
            out["paged_layers"] = self._pool_layers
            out["layer_applications_a_step"] = (
                self.cfg.num_passes * self.cfg.num_hidden_layers)
            out["picked_by_pass"] = self._picked_by_pass.tolist()
        if self.manager is not None:
            out["pool"] = self.manager.fragmentation()
        if hasattr(self, "plan"):
            out["plan"] = self.plan
        # Attention-impl surface: which implementation each serving
        # attention shape rides (decode vs the chunk/verify Q-block).
        out["attn_impl"] = None if self.mega else self.attn_impl
        out["chunk_attn"] = None if self.mega else self.chunk_attn
        # KV quantization surface: which storage the pools ride and
        # what a resident token costs (capacity math in the pool dict).
        out["kv_dtype"] = self.kv_dtype
        if hasattr(self, "plan"):
            out["kv_bytes_per_token"] = self.plan["bytes_per_token"]
        # Megakernel lane capabilities (nulled, not omitted, on the
        # layer path) — smoke scripts gate on these instead of
        # grepping tracebacks for the old NotImplementedError rejects.
        out["mk_kv_dtype"] = self.kv_dtype if self.mega else None
        out["mk_spec"] = (self.spec_k or 0) if self.mega else None
        out["mk_checkpointable"] = True if self.mega else None
        out["mk_chunked_prefill"] = (
            list(self.prefill_buckets or ()) if self.mega else None)
        # Speculative-decode surface: draft volume vs accepted volume
        # (tokens beyond the per-dispatch guaranteed one).
        if self.spec_k:
            drafted = self.stats_counters["spec_drafted"]
            out["spec"] = {
                "k": self.spec_k,
                "drafted": drafted,
                "accepted": self.stats_counters["spec_accepted"],
                # Dispatches where a sampled (temperature > 0) request
                # rode the degenerate repeat-draft — it commits at most
                # one token, so a high count here means the speculative
                # lane is paying K-row verification for one-token
                # progress (ROADMAP item 5b visibility).
                "sampled_fallbacks": self.stats_counters[
                    "spec_sampled_fallbacks"],
                "accept_rate": (
                    self.stats_counters["spec_accepted"] / drafted
                    if drafted else None),
                "tokens_per_dispatch": (
                    self.stats_counters["decode_tokens"]
                    / max(self.stats_counters["decode_dispatches"], 1)),
            }
        # Greedy-token agreement vs a reference run (folded in via
        # compare_greedy) — the quantized path's divergence surface.
        if self.stats_counters["greedy_ref_tokens"]:
            out["greedy_agreement"] = (
                self.stats_counters["greedy_agree_tokens"]
                / self.stats_counters["greedy_ref_tokens"])
        if self.stats_counters["decode_time_s"] > 0:
            # Decode-emitted tokens over decode-dispatch time only —
            # the first token of each request comes from prefill and
            # must not inflate the decode throughput number.
            out["tokens_per_s"] = (
                self.stats_counters["decode_tokens"]
                / self.stats_counters["decode_time_s"])
        # KV memory hierarchy surface: tier occupancy + the hot-set
        # HBM hit rate (prefix allocations served from HBM over all
        # prefix lookups — tier hits and recomputes are the misses).
        # Nulled, not omitted, when tiering is off; tier_hits /
        # tier_misses / offloaded_pages / parks / resumes ride the
        # plain counters above.
        out["parked_sessions"] = len(self._parked)
        if self.tiers is not None:
            ts = self.tiers.stats()
            out["tiers"] = ts
            out["tier_pages"] = (ts["host_pages_used"]
                                 + ts["disk_pages_used"])
            s = self.manager.stats if self.manager is not None else {}
            denom = (s.get("prefix_hits", 0)
                     + s.get("prefix_misses", 0))
            out["kv_hot_hit_rate"] = (
                round(s["prefix_hits"] / denom, 4) if denom else None)
        else:
            out["tiers"] = None
            out["tier_pages"] = None
            out["kv_hot_hit_rate"] = None
        # Multi-tenant SLO surface: per-tenant quota/attainment view +
        # the aggregate attainment fraction — nulled, not omitted,
        # when the layer is off (slo_preemptions rides the plain
        # counters above either way).
        out["slo"] = self.slo.stats() if self.slo is not None else None
        out["slo_attainment"] = (out["slo"]["attainment"]
                                 if self.slo is not None else None)
        # Telemetry surface: histogram summaries (TTFT / inter-token /
        # per-op, per-tenant groups) — None in telemetry="off", keeping
        # the key present either way (nulled, not omitted).
        out["telemetry"] = self.obs.mode
        out["latency"] = self.obs.latency_summary()
        return out

    def decode_cache_size(self) -> int:
        """Jit-cache entries of the shared decode dispatch — the
        no-recompilation-after-warmup gate (1 after warmup: the decode
        batch shape is fixed). With speculation on, the K-token
        verification dispatch IS the decode dispatch (K is static,
        acceptance is data), so the same gate covers it."""
        if self.mega:
            fn = (self.engine._verify_step if self.spec_k
                  else self.engine._step)
        else:
            fn = self._verify if self.spec_k else self._decode
        return fn._cache_size()

    def compare_greedy(self, pairs) -> float:
        """Fold greedy-token agreement against a REFERENCE run into
        the stats counters (surfaced as ``stats()["greedy_agreement"]``)
        — the quantized path's accuracy telemetry: serve the same
        prompts through a bf16 pool (or ``Engine.serve``) and hand the
        (got_tokens, reference_tokens) pairs here. Returns the running
        agreement fraction."""
        for got, want in pairs:
            n = min(len(got), len(want))
            self.stats_counters["greedy_ref_tokens"] += n
            self.stats_counters["greedy_agree_tokens"] += sum(
                1 for a, b in zip(got[:n], want[:n]) if a == b)
        ref = self.stats_counters["greedy_ref_tokens"]
        return (self.stats_counters["greedy_agree_tokens"] / ref
                if ref else 1.0)

    # -- checkpoint / restore ----------------------------------------

    CHECKPOINT_FORMAT = "tdt-serving-ckpt-v1"

    def _ckpt_meta(self) -> dict:
        return {
            "format": self.CHECKPOINT_FORMAT,
            "engine_kind": "mega" if self.mega else "layer",
            "kv_dtype": self.kv_dtype, "page": self.page,
            "p_max": self.p_max, "num_slots": self.num_slots,
            "max_len": self.max_len, "spec_k": self.spec_k,
            "vocab_size": self.cfg.vocab_size,
            "num_pages": (None if self.manager is None
                          else self.manager.num_pages),
            "kv_tiers": self.tiers is not None,
        }

    @staticmethod
    def _ser_handle(h: RequestHandle, *, keep_slot: bool,
                    status: Optional[str] = None) -> dict:
        r = h.request
        return {
            "request": {
                "prompt": [int(t) for t in r.prompt],
                "max_new_tokens": r.max_new_tokens,
                "request_id": r.request_id, "eos_id": r.eos_id,
                "deadline": r.deadline, "temperature": r.temperature,
                "top_k": r.top_k, "seed": r.seed, "tenant": r.tenant,
                "slo_class": r.slo_class,
            },
            "status": status or ("running" if keep_slot else "queued"),
            "tokens": [int(t) for t in h.tokens],
            "slot": h.slot if keep_slot else None,
            "decode_steps": h.decode_steps,
            # SLO-preempted park victims are owed an auto-resume — the
            # restoring process re-adopts the debt.
            "slo_parked": bool(getattr(h, "_slo_parked", False)),
        }

    def checkpoint(self) -> dict:
        """Host-side snapshot of the FULL serving state at a tick
        boundary: the paged KV pools (+ quantization scales,
        bit-exact), the block manager's free-list/refcounts/prefix
        index, the scheduler queue and slot assignments, the host
        length mirrors, and every counter. ``restore()`` on a FRESH
        engine (same model config, weights, and pool plan — weights
        are NOT in the snapshot) resumes decode token-exact
        mid-stream — the substrate for preemptible-VM restarts.

        Semantics per in-flight state: ``running`` slots restore
        exactly (their KV is in the snapshot pools); mid-``prefill``
        and mid-``migrating`` requests snapshot as QUEUED with their
        generated-so-far tokens — restore re-prefills them through the
        deterministic re-prefill contract (token-exact; their partial
        staging work is dropped, never trusted). ``stream_cb``
        callbacks cannot cross a process boundary and are dropped:
        reattach via the handles ``restore()`` returns. Pure
        observation — the live engine is not mutated.

        Megakernel engines snapshot by ARENA SCHEMA (KV pools +
        quantization scales + in-arena counters + GDN state, by
        region name — ``MegaKernelEngine.snapshot_state``), bit-exact
        at any kv_dtype, so the persistent lane resumes decode
        token-exact too (mid-prefill-LANE requests snapshot as
        queued, exactly like mid-chunk-stream ones).
        """
        t_ck = self.obs.now()
        self._land()        # a snapshot holds nothing in flight
        running = [h for h in self.sched.running()
                   if h.status == "running"]
        inflight = [h for h in self.sched.running()
                    if h.status != "running"]
        # Release in-flight (non-running) slots on a COPY of the
        # allocator state, so the snapshot is self-consistent with
        # their queued status — reusing free_slot keeps the refcount /
        # staged-prefix algebra identical to the live path. (A dense
        # megakernel engine has no allocator: the mirrors alone carry
        # the slot state.)
        m2 = None
        if self.manager is not None:
            m2 = BlockManager(self.manager.num_pages, self.page,
                              self.p_max,
                              prefix_reuse=self.manager.prefix_reuse)
            m2.load_snapshot(self.manager.snapshot())
        lens, live, toks = (self._lens.copy(), self._live.copy(),
                            self._toks.copy())
        for h in inflight:
            if h.slot is not None:
                if m2 is not None:
                    m2.free_slot(h.slot)
                lens[h.slot] = live[h.slot] = toks[h.slot] = 0
        if self.mega:
            cache_np = self.engine.snapshot_state()
        else:
            c = self.cache
            cache_np = {
                "k_pages": np.asarray(c.k_pages),
                "v_pages": np.asarray(c.v_pages),
                "k_scale": (None if c.k_scale is None
                            else np.asarray(c.k_scale)),
                "v_scale": (None if c.v_scale is None
                            else np.asarray(c.v_scale)),
            }
        handles = ([self._ser_handle(h, keep_slot=True)
                    for h in running]
                   + [self._ser_handle(h, keep_slot=False)
                      for h in inflight]
                   + [self._ser_handle(h, keep_slot=False)
                      for h in self.sched.queue]
                   + [self._ser_handle(h, keep_slot=False)
                      for h in (self.slo.queued_handles()
                                if self.slo is not None else ())]
                   + [self._ser_handle(h, keep_slot=False,
                                       status="parked")
                      for h in self._parked.values()])
        snap = {
            "meta": self._ckpt_meta(),
            "cache": cache_np,
            "manager": (None if m2 is None else m2.snapshot()),
            "handles": handles,
            "lens": lens, "live": live, "toks": toks,
            "counters": dict(self.stats_counters),
            "sched_counters": dict(self.sched.counters),
            # Tier contents ride the snapshot wholesale (offloaded
            # prefix pages + parked-session payloads, disk entries
            # materialized) — a restored process resumes parked
            # sessions without the original spill directory.
            "tiers": (None if self.tiers is None
                      else self.tiers.snapshot()),
        }
        self.obs.complete_span("checkpoint", t_ck,
                               requests=len(handles))
        return snap

    def restore(self, snap: dict) -> List[RequestHandle]:
        """Adopt a :meth:`checkpoint` snapshot into this (idle,
        identically-planned) engine and return the revived handles —
        running requests resume decode token-exact at the next
        :meth:`step`; queued ones re-prefill deterministically.
        Counters continue from the snapshot, and every revived
        request counts into ``stats()["restored_requests"]``.
        Deadlines are restored verbatim (they are absolute times on
        the scheduler clock — after a real process restart, expired
        ones fail on the first tick, which is the correct reading of
        a missed SLO)."""
        import dataclasses as _dc
        import itertools
        import re

        import jax
        import jax.numpy as jnp

        t_rs = self.obs.now()
        self._land()
        meta = snap.get("meta", {})
        if meta.get("format") != self.CHECKPOINT_FORMAT:
            raise ValueError(
                f"not a serving checkpoint (format={meta.get('format')!r},"
                f" want {self.CHECKPOINT_FORMAT!r})")
        mine = self._ckpt_meta()
        bad = {k: (meta.get(k), v) for k, v in mine.items()
               if meta.get(k) != v}
        if bad:
            raise ValueError(
                "checkpoint/engine plan mismatch (snapshot vs this "
                f"engine): {bad} — restore needs an identically-"
                "configured engine over the same weights")
        if self.sched.slots or self.sched.queue or self._parked \
                or (self.slo is not None
                    and self.slo.queued_handles()):
            raise RuntimeError(
                "restore() needs an idle engine (fresh process / "
                "drained loop); this one has live slots, a queue, or "
                "parked sessions")
        # Tier-capacity validation UP FRONT, before any mutation: a
        # snapshot whose tier contents cannot fit this store must not
        # leave a half-restored engine behind.
        t_snap = snap.get("tiers")
        if t_snap is not None:
            if self.tiers is None:
                raise ValueError(
                    "snapshot carries tier contents (offloaded pages "
                    "/ parked sessions); construct the restoring "
                    "engine with kv_tiers")
            reason = self.tiers.fits_snapshot(t_snap)
            if reason is not None:
                raise ValueError(
                    f"snapshot tier contents do not fit this "
                    f"engine's tier store ({reason}) — restore needs "
                    "an equally-provisioned tier store")
        if self.mega:
            # Schema-driven adoption: pools + scales + counters + GDN
            # state land back in the engine, re-pinned to their
            # construction shardings (the persistent step never
            # re-specializes); counters telemetry restarts from the
            # restored baseline.
            self.engine.restore_state(snap["cache"])
            self._mk_counts_base = None
            self._mk_load_sig = None
        else:
            c = snap["cache"]
            if np.dtype(c["k_pages"].dtype) != np.dtype(
                    self.cache.k_pages.dtype):
                raise ValueError(
                    f"pool dtype mismatch: snapshot "
                    f"{c['k_pages'].dtype} vs engine "
                    f"{self.cache.k_pages.dtype}")
            cache = _dc.replace(
                self.cache,
                k_pages=jnp.asarray(c["k_pages"]),
                v_pages=jnp.asarray(c["v_pages"]),
                k_scale=(None if c["k_scale"] is None
                         else jnp.asarray(c["k_scale"])),
                v_scale=(None if c["v_scale"] is None
                         else jnp.asarray(c["v_scale"])))
            # Re-pin to the pool's one sharding spelling — the decode
            # dispatch must not re-specialize on the first
            # post-restore tick.
            self.cache = jax.tree.map(
                jax.device_put, cache, self._cache_shardings,
                is_leaf=lambda x: isinstance(x, jax.Array))
        if self.manager is not None and snap["manager"] is not None:
            self.manager.load_snapshot(snap["manager"])
        self._lens = np.asarray(snap["lens"], np.int32).copy()
        self._live = np.asarray(snap["live"], np.int32).copy()
        self._toks = np.asarray(snap["toks"], np.int32).copy()
        self.stats_counters.update(snap["counters"])
        self.sched.counters.update(snap["sched_counters"])
        handles: List[RequestHandle] = []
        max_seq = -1
        now = self.sched.now()
        for hs in snap["handles"]:
            req = Request(**hs["request"])
            if req.request_id:
                m = re.fullmatch(r"req-(\d+)", req.request_id)
                if m:
                    max_seq = max(max_seq, int(m.group(1)))
            h = RequestHandle(request=req, status=hs["status"],
                              tokens=list(hs["tokens"]),
                              slot=hs["slot"],
                              decode_steps=hs["decode_steps"],
                              submitted_at=now)
            h.queued_at = now
            if h.tokens:
                # Mid-stream revival: its TTFT already happened in the
                # previous process — the next emission must not record
                # a second one, and the ITL chain restarts at the
                # first post-restore gap (last_token_at stays None).
                h.first_token_at = now
            if h.status == "running":
                h.started_at = now
                self.sched.slots[h.slot] = h
            elif h.status == "parked":
                # Token-preserving parked registry — its KV payload
                # arrives with the tier snapshot below; resume() works
                # exactly as in the original process.
                self._parked[req.request_id] = h
                if hs.get("slo_parked") and self.slo is not None:
                    # Re-adopt the auto-resume debt: an SLO-preempted
                    # park victim must still reach a terminal status.
                    h._slo_parked = True
                    self.slo._parked_by_slo.append(h)
            elif self.slo is not None:
                self.slo.adopt(self, h)
            else:
                self.sched.queue.append(h)
            handles.append(h)
        if t_snap is not None:
            self.tiers.load_snapshot(t_snap)
            # Sessions that were mid-"resuming" at snapshot time were
            # serialized as QUEUED (they re-prefill deterministically)
            # — their orphaned pinned payloads are dead weight.
            keep = {("session", h.request.request_id)
                    for h in self._parked.values()}
            for k in list(self.tiers.keys()):
                if tuple(k)[0] == "session" and tuple(k) not in keep:
                    self.tiers.pop(tuple(k))
        # Auto request-ids must not collide with restored ones.
        self.sched._ids = itertools.count(max_seq + 1)
        self.stats_counters["restored_requests"] += len(handles)
        self.obs.complete_span("restore", t_rs, requests=len(handles))
        return handles

    def prefill_cache_size(self) -> Optional[int]:
        """Jit-cache entries of the PREFILL path — the other half of
        the no-recompilation gate. Chunked: the chunk dispatch's
        entries, bounded by the bucket count (asserted inline after
        every chunk). Monolithic layer path: the engine's prefill
        entries — grows per distinct prompt/resume length (the PR-4
        known limit this surfaces). Megakernel: chunked (the engine's
        per-bucket chunk steps) when built with ``prefill_buckets``,
        else ``None`` (the one-token prefill lane IS the decode
        dispatch)."""
        if self._prefiller is not None:
            return self._prefiller.chunker.cache_size()
        if self.mega:
            return None
        return self.engine._prefill._cache_size()

    def trace(self, name: str = "serving", *,
              expert_histograms: bool = True,
              log_dir: str = "/tmp/tdt_traces", out_dir=None,
              xprof="auto", mk_keep: int = 4,
              create_perfetto_link: bool = False):
        """One tracing context over the serving loop: the xprof
        capture, the per-step expert histograms, and the host span
        timeline all share ONE session directory and ONE context
        manager (yields a :class:`~triton_dist_tpu.obs.TraceSession`).

        While active: each decode step's per-expert routed-token
        histogram is appended to :attr:`expert_hist` (when the model
        exposes expert telemetry — the per-step routing record the
        load EWMA in :meth:`stats` smooths over), and a megakernel
        engine built with ``profile=True`` contributes its last
        ``mk_keep`` steps' slot records. On exit the session holds
        everything :meth:`TraceSession.export` needs to write ONE
        merged Perfetto file — host request spans (``telemetry=
        "spans"``) and megakernel slot records. The xprof capture
        itself already holds every host span beside the device's
        operations, as ``tdt.<kind>`` annotations on its own clock
        (any enabled telemetry mode; docs/observability.md).

        The old signature still works: ``with srv.trace("x"):`` starts
        an xprof capture under ``{log_dir}/{name}`` exactly as before
        (``os.fspath`` of the yielded session is that directory);
        ``out_dir`` overrides the session directory wholesale.
        """
        import contextlib

        from triton_dist_tpu.obs.trace import TraceSession

        path = out_dir or f"{log_dir}/{name}"

        @contextlib.contextmanager
        def _traced():
            sess = TraceSession(
                path, self.obs, xprof=xprof, mk_keep=mk_keep,
                create_perfetto_link=create_perfetto_link)
            self._hist_active = expert_histograms
            self._trace_session = sess
            try:
                with sess:
                    yield sess
            finally:
                self._hist_active = False
                self._trace_session = None

        return _traced()

    # -- admission / prefill ----------------------------------------

    def _unadmit(self, h: RequestHandle, error: OutOfPagesError,
                 stalled: List[RequestHandle]):
        """Roll an admitted request back (pool dry — backpressure); the
        caller requeues ``stalled`` at the head in submission order. If
        NOTHING else holds a slot, no future retirement can free pages,
        so waiting would spin forever: fail it instead."""
        self.sched.slots.pop(h.slot, None)
        h.slot = None
        if not self.sched.slots:
            self._fail(h, "failed", error)
            return
        h.status, h.started_at = "queued", None
        h.queued_at = self.sched.now()
        stalled.append(h)
        self.stats_counters["admit_stalls"] += 1

    def _admit(self, h: RequestHandle,
               stalled: List[RequestHandle]):
        import jax.numpy as jnp

        slot = h.slot
        # Parked-session resume: prefetch the tier payload instead of
        # recomputing (falls through to the re-prefill below only when
        # the payload is gone — equally token-exact).
        if (getattr(h, "resume_key", None) is not None
                and self.tiers is not None):
            if self._admit_resume(h, stalled):
                return
        # Resume form (preempted requests): the cache must be rebuilt
        # from the prompt PLUS every already-fed generated token; the
        # last generated token was never fed and re-enters via decode.
        seq = list(h.request.prompt) + [int(t) for t in h.tokens[:-1]]
        if self.mega and self._prefiller is None:
            # Prefill lane: ``seq`` streams through the shared decode
            # kernel one token per tick. Fresh slot state now.
            # (With prefill_buckets the megakernel admits through
            # _admit_chunked below instead — bucketed chunk tasks,
            # not one token per tick.)
            if self.manager is not None:
                try:
                    self.manager.alloc_prefill(slot, seq)
                except OutOfPagesError as e:
                    self._unadmit(h, e, stalled)
                    return
            if hasattr(self.engine, "reset_slot"):
                self.engine.reset_slot(slot)
            h.lane = seq
            h.prompt_pos = 0
            h.status = "prefill"
            self._lens[slot] = 0
            self._live[slot] = 1
            self._toks[slot] = seq[0]
            return
        if self._prefiller is not None:
            self._admit_chunked(h, seq, stalled)
            return
        try:
            pages = self.manager.alloc_prefill(slot, seq)
        except OutOfPagesError as e:
            self._unadmit(h, e, stalled)
            return
        # Tier hits extend the resident run (pages scattered back from
        # the host/disk tier — the blit below skips them like any
        # prefix hit).
        self._tier_prefill_fetch(h, slot)
        # Token-exact prefill through the engine's own dispatch: B=tp
        # identical rows satisfies the token-sharding divisibility for
        # ANY prompt length; row 0 is the answer (chat_server pattern).
        # A wedged prefill (CommTimeoutError) fails THIS request only —
        # slot and pages must not leak, and the loop must survive.
        eng = self.engine
        ids = np.tile(np.asarray([seq], np.int32), (self._axis_n, 1))
        with self.obs.span("prefill", request_id=h.request.request_id,
                           slot=slot, tenant=h.request.tenant,
                           tokens=len(seq)):
            try:
                logits, kv = eng.prefill(jnp.asarray(ids))
            except Exception as e:  # noqa: BLE001 — route via policy
                from triton_dist_tpu.resilience.watchdog import (
                    CommTimeoutError)

                if isinstance(e, CommTimeoutError):
                    self.stats_counters["comm_timeouts"] += 1
                    self.obs.event(
                        "timeout", op="serving.prefill",
                        request_id=h.request.request_id, slot=slot)
                    self._fail(h, "timeout", e)
                    return
                # Unexpected failure: still release the slot and pages
                # (no leaked half-admitted state), then propagate.
                self._fail(h, "failed", e)
                raise
            self.stats_counters["prefill_calls"] += 1
            self.stats_counters["prefill_tokens"] += len(seq)
            # Blit only the NON-shared suffix pages: prefix-hit pages
            # hold KV already computed by the first sharer, and
            # rewriting them with this (differently-shaped) prefill's
            # floats could perturb a live request attending to them —
            # XLA guarantees no bit-exactness across shapes. (Also
            # skips the redundant writes.)
            hits = self.manager.prefix_hits(slot)
            if hits < len(pages):
                s_pad = len(pages) * self.page
                k0 = kv.k[:, 0, hits * self.page:s_pad]
                v0 = kv.v[:, 0, hits * self.page:s_pad]
                self.cache = self._writer(
                    self.cache, k0, v0,
                    jnp.asarray(pages[hits:], jnp.int32))
            # Pages written — NOW they may be shared with later
            # requests.
            self.manager.commit_prefix(slot)
        self._lens[slot] = len(seq)
        self._live[slot] = 1
        h.status = "running"
        self._close_resume_span(h, path="reprefill")
        if not h.tokens:
            first = self._pick(np.asarray(logits)[0], h.request, 0)
            self._emit(h, first)
        # resumed: the next decode tick feeds h.tokens[-1] at len(seq)

    # -- chunked prefill (layer path) -------------------------------

    def _admit_chunked(self, h: RequestHandle, seq,
                       stalled: List[RequestHandle]):
        """Admit into the chunk stream: allocate the slot's pages in
        the prefiller's pool now (backpressure = the same requeue as
        monolithic admission), then leave the handle in ``"prefill"``
        status — :meth:`_launch` streams its bucketed chunks
        tick by tick, interleaved with decode, until the prompt is
        resident. Prefix hits skip straight past already-resident
        pages: the compute cursor starts at the first non-shared page
        (clamped so the last prompt token always runs — its logits
        seed the first generated token), and those pages are never
        re-blitted (``wfrom``)."""
        p = self._prefiller
        slot = h.slot
        try:
            p.manager.alloc_prefill(slot, seq)
        except OutOfPagesError as e:
            self._unadmit(h, e, stalled)
            return
        if p is self:
            # In-place chunked prefill: tier-resident prefix pages
            # prefetch straight into the serving pool and the chunk
            # stream starts PAST them — the compute skip that turns a
            # demoted cold prefix back into a (slower) cache hit.
            self._tier_prefill_fetch(h, slot)
        else:
            # Disaggregated prefill WORKER: tier-resident leading
            # pages scatter into the staging pool so the chunk stream
            # skips their compute too (the decode-side handoff fetch
            # is unchanged — it pops the tier entry when the decode
            # pool becomes authoritative).
            self._tier_worker_fetch(h, slot)
        h.resident = p.manager.prefix_hits(slot) * self.page
        h.lane = seq
        h.prompt_pos = min(h.resident, len(seq) - 1)
        h.chunks = []
        h.status = "prefill"
        # Parked until the prompt is resident: the decode dispatch
        # sees live=0 and a scratch table row for this slot.
        self._lens[slot] = 0
        self._live[slot] = 0
        self._toks[slot] = 0

    def _launch(self):
        """The LAUNCH half of a tick, which needs no token's value: the
        tick's prefill chunks and its joint decode step are handed to
        the device, and everything the host can book without their
        results is booked (page growth, the chunk cursors, the lengths,
        a resident prompt's slot going live). Returns the
        :class:`_Flight` that :meth:`_land` takes, None where no
        program was dispatched.

        Where this engine's chunker carries the decode batch and a
        decoder is live, the tick is one program's worth of prefill
        rows, oldest prompt first, with the batch aboard the first of
        them (:meth:`_launch_fused`). Otherwise one bucketed chunk per
        prefilling slot (nothing rides, so waiting saves no weight
        read) and then the decode step's own program, which a slot
        whose prompt just became resident joins."""
        flight = _Flight(self.stats_counters["ticks"] - 1)
        if self._prefiller is not None:
            prefilling = [h for h in self.sched.running()
                          if h.status == "prefill"]
            if self._rides and prefilling:
                active, tbl = self._prepared()
                if active:
                    self._launch_fused(flight, prefilling, active, tbl)
                    return flight
            for h in prefilling:
                self._launch_chunk(flight, h)
        if self.spec_k or any(id(h) not in flight.token_at
                              for h, _ in flight.finished):
            # The next input of these rows is made on the host (a
            # draft's history, a sampled token, a lane whose chunks
            # pick none): their first tokens land before the step.
            self._first_tokens(flight)
        if self.spec_k:
            flight.decoded = self._spec_tick()
            flight.programs += bool(flight.decoded)
        else:
            active, tbl = self._prepared()
            if active:
                self._launch_decode(flight, active, tbl)
        return flight if flight.programs else None

    def _prepared(self):
        """The tick's decoders with their pages grown and the table
        built (:meth:`_decode_prep`), or ``([], None)``."""
        active = self._decoders()
        if not active:
            return active, None
        with self.obs.span("decode_prep"):
            return self._decode_prep(active)

    def _decoders(self) -> List[RequestHandle]:
        """The handles whose decode step the tick runs: running, and by
        count not yet at their last token (``in_flight``: tokens a
        launched program owes them). Layer-path slots still
        mid-chunk-stream (or mid-migration in the disaggregated
        subclass) are parked: they join the decode batch only once
        their prompt is resident. The megakernel's prefill lane rides
        the decode dispatch itself."""
        return [h for h in self.sched.running()
                if (h.status == "running"
                    and len(h.tokens) + h.in_flight
                    < h.request.max_new_tokens)
                or (self.mega and self._prefiller is None
                    and h.status == "prefill")]

    def _launch_chunk(self, flight, h: RequestHandle):
        """``h``'s next chunk, in a program with the decode rows parked;
        a prompt that it makes resident goes live."""
        last = self._advance_chunk(h)
        flight.programs += 1
        if last is not None and self._finish_prefill(h, last):
            flight.owe_first(h, last)

    def _riding_chunks(self, prefilling):
        """The handles whose next chunk a riding tick runs, one at a
        time as each is dispatched (so a request's cursor is where its
        last chunk left it): prompts in admission order, a prompt's
        consecutive chunks included, while the tick's bucket rows stay
        within the largest bucket. It stops at the first chunk that
        does not fit; the oldest prompt's chunk always does, so no
        prompt starves, and a decoder never waits longer than one
        chunk program's worth of rows for its next token."""
        chunker = self._prefiller.chunker
        room = max(chunker.buckets)
        for h in sorted(prefilling, key=lambda h: (h.started_at, h.slot)):
            while h.status == "prefill" and h.prompt_pos < len(h.lane):
                bucket, _ = chunker.next_chunk(len(h.lane) - h.prompt_pos)
                if bucket > room:
                    return
                room -= bucket
                yield h

    def _launch_fused(self, flight, prefilling, active, tbl):
        """A tick whose decode batch rides its first chunk's program:
        the weights are read once for the chunk and the step. The
        tick's other chunks (:meth:`_riding_chunks`; decode rows
        parked) are enqueued behind that program; slots whose last
        chunk ran go live now and decode from the next tick, their
        first tokens landing after the decode rows'.

        A fault raised here is contained where its scope is: one at
        ``chunked_prefill`` fails the chunk's request alone, as
        :meth:`_advance_chunk` does, and the decoders (whose lengths
        never advanced) redo their step next tick; anything else — a
        drop at ``serving_decode`` — is the decode tick's containment,
        and the first chunk's request goes with it. A watchdog miss on
        the joint program is the landing's (:meth:`_land_failed`)."""
        import jax.numpy as jnp
        from triton_dist_tpu.resilience import faults
        from triton_dist_tpu.resilience.watchdog import CommTimeoutError

        chunks = self._riding_chunks(prefilling)
        first = next(chunks)
        sampled = _samples(active)
        try:
            with self.obs.span("decode_enqueue"):
                batch = (self._input_tokens(flight, active),
                         jnp.asarray(tbl), *self._mirrors())
            picked, logits, dec, plan = self._enqueue_chunk(
                first, batch, rows=sampled)
        except (CommTimeoutError, faults.InjectedFault) as e:
            if getattr(e, "op", None) == "chunked_prefill":
                self._chunk_failed(first, e)
            else:
                self._decode_contain(e)
                if first.status == "prefill":
                    self._fail(first, "timeout" if isinstance(
                        e, CommTimeoutError) else "failed", e)
        else:
            flight.programs += 1
            self._board(flight, active, picked, 1, dec, sampled=sampled,
                        stat_rows=plan[1] + self.num_slots, fused=1,
                        first=first)
            # Booked at enqueue: the prompt's next chunk may run in
            # this tick.
            if (self._chunk_done(first, plan)
                    and self._finish_prefill(first, (picked, logits))):
                flight.owe_first(first, (picked, logits))
        for h in chunks:
            self._launch_chunk(flight, h)

    def _board(self, flight, active, *program, **booked):
        """``active``'s decode step is aboard ``program``: booked on
        the flight, and the length mirrors advance (the next launch
        writes one position further, whether or not this one landed)."""
        flight.board(active, *program, **booked)
        for _, slot in flight.rows:
            self._lens[slot] += 1

    def _mirrors(self):
        """The length and live mirrors as a step program takes them.
        Copies go up: the launch moves the mirrors on right behind the
        enqueue, while an upload may still read the host's buffer."""
        import jax.numpy as jnp

        return jnp.asarray(self._lens.copy()), jnp.asarray(self._live.copy())

    def _input_tokens(self, flight, active):
        """The decode rows' input tokens as a step program takes them,
        ``(num_slots,)`` int32: each row's newest token. The host sends
        the ones it knows (``h.tokens[-1]``; a lane's next token); one
        that a program not yet landed picked (the tick in flight, or
        this tick's own chunk for a slot that just went live) is taken
        from that program's ``picked`` on the device (``self._feed``),
        so the token never crosses to the host between two programs."""
        import jax
        import jax.numpy as jnp

        prev = self._flight
        feeds = {}        # id(picked) -> (picked, index by slot or -1)
        for h in active:
            at = flight.token_at.get(id(h))
            if at is None and prev is not None:
                at = prev.token_at.get(id(h))
            if at is None:
                self._toks[h.slot] = (
                    h.lane[h.prompt_pos]
                    if self.mega and h.status == "prefill"
                    else h.tokens[-1])
                continue
            picked, index = at
            feeds.setdefault(id(picked), (picked, np.full(
                (self.num_slots,), -1, np.int32)))[1][h.slot] = index
        if self.mega:
            return jnp.asarray(self._toks.copy())
        toks = jax.device_put(self._toks.copy(), self._row_sh)
        for picked, idx in feeds.values():
            toks = self._feed(toks, idx, picked)
        return toks

    def _run_op_with_retry(self, op: str, fn, retry_on=None):
        """Run one retryable serving op under its configured
        :class:`~triton_dist_tpu.resilience.policy.RetryPolicy` (none
        configured = one attempt). Retries only the transient fault
        types — by default a watchdog miss or an injected fault;
        ``retry_on`` narrows that per call site (the decode/verify
        dispatches pass ``(InjectedFault,)`` because a WEDGE blocks
        its own replay). Every attempt re-enters the op's fault
        scope, so a ``fail_kth_call`` plan's call index advances per
        attempt and a transient at k=0 is absorbed. Each retry
        increments the ``retries`` counter."""
        from triton_dist_tpu.resilience import faults
        from triton_dist_tpu.resilience.watchdog import CommTimeoutError

        pol = self.retry_policies.get(op)
        if pol is None:
            return fn()
        if retry_on is None:
            retry_on = (CommTimeoutError, faults.InjectedFault)

        def _note(attempt, exc):
            self.stats_counters["retries"] += 1
            self.obs.event("retry", op=op, attempt=attempt,
                           error=type(exc).__name__)
            if isinstance(exc, CommTimeoutError):
                # An absorbed wedge is still an observed watchdog
                # miss — the telemetry keeps counting them even when
                # the retry hides them from the request.
                self.stats_counters["comm_timeouts"] += 1

        return pol.run(fn, op=f"serving.{op}",
                       retry_on=retry_on,
                       on_retry=_note,
                       event_cb=(self.obs.event if self.obs.spans_on
                                 else None))

    def _note_integrity_failure(self, boundary: str, exc, *,
                                request_id=None) -> None:
        """Account one detected payload-digest violation (the
        ``integrity_check`` span row in docs/observability.md) — the
        caller then routes into the boundary's recovery path."""
        self.stats_counters["integrity_failures"] += 1
        self.obs.complete_span(
            "integrity_check", self.obs.now(), boundary=boundary,
            ok=False, request_id=request_id,
            key=str(getattr(exc, "key", None)))

    def _tier_worker_fetch(self, h: RequestHandle, slot: int) -> int:
        """Staging-pool tier fetch hook — a no-op on the in-place
        chunk path (the disaggregated subclass scatters tier-resident
        leading pages into its prefill WORKER's staging pool so the
        chunk stream skips their compute; docs/serving.md, 'KV memory
        hierarchy')."""
        return 0

    # Role-health hooks (no-ops here): the disaggregated subclass
    # tracks per-role heartbeats/failures and fails over a dead
    # prefill worker. ``_note_role_failure`` returns True when it
    # handled the failure by failing over (the victim was REQUEUED
    # with the rest of the in-flight work — do not also fail it).

    def _note_role_ok(self, role: str) -> None:
        pass

    def _note_role_failure(self, role: str, exc) -> bool:
        return False

    def _advance_chunk(self, h: RequestHandle):
        """Dispatch ``h``'s next chunk and book it; a chunk that fails
        past its retries fails ``h`` alone. Returns the chunk's result
        ``(picked tokens, logits)``, still on the device, if it was the
        prompt's last — the caller owes ``h`` its
        :meth:`_finish_prefill` — else None. A program that carries
        decode rows runs here with all of them parked."""
        from triton_dist_tpu.resilience import faults
        from triton_dist_tpu.resilience.watchdog import CommTimeoutError

        if h.status != "prefill":
            # An earlier chunk of this tick took it out of the stream
            # (a failover requeues every in-flight prefill).
            return None
        try:
            picked, logits, _, plan = self._enqueue_chunk(h)
        except (CommTimeoutError, faults.InjectedFault) as e:
            self._chunk_failed(h, e)
            return None
        if self._rides:
            self.stats_counters["chunk_dispatches_parked"] += 1
        return (picked, logits) if self._chunk_done(h, plan) else None

    def _enqueue_chunk(self, h: RequestHandle, batch=None, rows=False):
        """Dispatch ``h``'s next chunk under the ``chunked_prefill``
        fault scope and retry policy; raises what outlives the retries.
        With ``batch`` (the decode batch's uploaded tokens, table,
        lengths and live mask) the decode step rides the same program:
        the attempt opens the ``serving_decode`` scope too, either op's
        policy absorbs a TRANSIENT drop (raised at a scope's entry,
        before anything is dispatched), and a wedge is not retried, as
        on the decode dispatch; ``rows`` says that a row of the batch
        samples, so the decode logits are copied to the host too.
        Returns ``(picked tokens, chunk logits, decode logits or None,
        (start, bucket, valid))``, all still on the device; the picked
        tokens (None from the megakernel's chunker) are row 0 the
        chunk's and rows 1.. the decode rows'."""
        import dataclasses as _dc

        from triton_dist_tpu.models.paged_step import STEP_KERNELS
        from triton_dist_tpu.resilience import faults
        from triton_dist_tpu.resilience.watchdog import (
            CommTimeoutError, block_until_ready)

        p = self._prefiller
        slot, seq, start = h.slot, h.lane, h.prompt_pos
        bucket, valid = p.chunker.next_chunk(len(seq) - start)
        ran = self._step_kernels(bucket)
        kernels = {f"{block}_kernel": int(block in ran)
                   for block in STEP_KERNELS}
        passes = ({"passes": self.cfg.num_passes} if self._row_stats
                  else {})
        if p.manager.ring:
            # Pages the chunk's slot holds in a window layer: its ring.
            passes["window_pages"] = p.manager.window_pages(slot)
        toks = np.zeros((bucket,), np.int32)
        toks[:valid] = seq[start:start + valid]
        row = np.asarray(p.manager.table_row(slot), np.int32)

        def _attempt():
            # Replay-idempotent: a retried chunk rewrites the same
            # positions of the same pages with the same bytes
            # (quantized pools re-merge to the identical amax), and
            # prefix pages stay scratch-routed below ``wfrom``; the
            # decode rows aboard append at lengths that advance only
            # on success. One span per ATTEMPT — retries show as
            # repeated chunk spans interleaved with their retry events.
            with self.obs.span("prefill_chunk",
                               request_id=h.request.request_id,
                               slot=slot, tenant=h.request.tenant,
                               start=int(start), bucket=int(bucket),
                               valid=int(valid),
                               padded_up=self._padded_up(bucket, valid),
                               **kernels, **passes), \
                    faults.on_op_call("chunked_prefill"):
                if batch is not None:
                    dec_toks, tbl, lens, live = batch
                    with faults.on_op_call("serving_decode"):
                        picked, logits, dec, p.cache = (
                            p.chunker.step_decode(
                                p.engine.params, toks,
                                _dc.replace(p.cache, block_table=tbl,
                                            lens=lens, live=live),
                                row, start, h.resident, valid, dec_toks,
                                slot=slot))
                    # The copies are asked for now, behind the program.
                    picked.copy_to_host_async()
                    if rows:
                        dec.copy_to_host_async()
                    return picked, logits, dec
                picked, logits, p.cache = p.chunker.step(
                    p.engine.params, toks, p.cache, row, start,
                    h.resident, valid, slot=slot)
                if picked is not None and start + valid >= len(seq):
                    picked.copy_to_host_async()   # the prompt's token
                if self.timeout_s is not None:
                    logits = block_until_ready(
                        logits, timeout_s=self.timeout_s,
                        op="serving.chunked_prefill",
                        progress_fn=lambda: {
                            "slot": slot, "chunk_start": start,
                            "chunks": list(h.chunks)})
            return picked, logits, None

        try:
            if batch is None:
                picked, logits, dec = self._run_op_with_retry(
                    "chunked_prefill", _attempt)
            else:
                drops = (faults.InjectedFault,)
                picked, logits, dec = self._run_op_with_retry(
                    "serving_decode",
                    lambda: self._run_op_with_retry(
                        "chunked_prefill", _attempt, retry_on=drops),
                    retry_on=drops)
        except (CommTimeoutError, faults.InjectedFault):
            raise
        except Exception as e:  # noqa: BLE001 — release, then surface
            self._fail(h, "failed", e)
            raise
        return picked, logits, dec, (start, bucket, valid)

    def _padded_up(self, bucket: int, valid: int) -> int:
        """1 where the chunk ``(bucket, valid)`` is a prompt's tail in
        one padded program of a larger bucket than the greedy step
        takes (:func:`ops.chunked_prefill.plan_chunks`), else 0."""
        from triton_dist_tpu.ops.chunked_prefill import padded_up

        return int(padded_up(bucket, valid,
                             self._prefiller.chunker.buckets))

    def _step_kernels(self, bucket: int) -> tuple:
        """The blocks (of ``models.paged_step.STEP_KERNELS``) that a
        chunk program of ``bucket`` rows, with the decode rows it
        carries, runs in a Pallas kernel: what the served model's module
        states (``step_kernels``, by the rules on sizes its program
        decides by), nothing for a model that states no such choice.
        Host arithmetic, once a bucket."""
        import jax

        known = self._kernels_of
        if bucket not in known:
            p = self._prefiller
            states = getattr(getattr(p.engine, "model", None),
                             "step_kernels", None)
            known[bucket] = () if states is None else states(
                p.engine.cfg, int(bucket),
                decode_rows=p.chunker.decode_rows, page=p.cache.page,
                dtype=jax.tree.leaves(p.engine.params)[0].dtype)
        return known[bucket]

    def _chunk_failed(self, h: RequestHandle, e):
        """A chunk was wedged or dropped past its retries. A dying
        prefill worker fails over (``h`` requeues with the rest of its
        in-flight work); otherwise it fails THIS request only (slot and
        pages released) and the loop keeps serving."""
        from triton_dist_tpu.resilience.watchdog import CommTimeoutError

        if isinstance(e, CommTimeoutError):
            self.stats_counters["comm_timeouts"] += 1
        if self._note_role_failure("prefill", e):
            return
        self._fail(h, "timeout" if isinstance(e, CommTimeoutError)
                   else "failed", e)

    def _chunk_done(self, h: RequestHandle, plan) -> bool:
        """Book a dispatched chunk: counters and the compute cursor.
        True once the whole prompt is resident."""
        start, bucket, valid = plan
        self._note_role_ok("prefill")
        self.stats_counters["prefill_chunks"] += 1
        for block in self._step_kernels(bucket):
            self.stats_counters[f"chunk_dispatches_kernel_{block}"] += 1
        self.stats_counters["chunk_dispatches_padded_up"] += (
            self._padded_up(bucket, valid))
        self.stats_counters["prefill_rows_padded"] += bucket - valid
        self.stats_counters["prefill_tokens"] += valid
        if start == 0 and self._seq_state_bytes:
            # The program zeroed the slot's state before its first row.
            self.stats_counters["seq_state_resets"] += 1
        h.chunks.append((start, bucket, valid))
        h.prompt_pos = start + valid
        if h.prompt_pos < len(h.lane):
            return False
        self.stats_counters["prefill_calls"] += 1
        return True

    def _finish_prefill(self, h: RequestHandle, last) -> bool:
        """Prompt fully resident, at the launch of its last chunk:
        the slot goes live (in-place chunked mode — the disaggregated
        subclass migrates pages first, and activates whole, later).
        True where the landing owes ``h`` its first token from
        ``last``."""
        self._go_live(h)
        return not h.tokens

    def _activate(self, h: RequestHandle, last):
        """Both halves of an activation in order, for a caller that
        holds the last chunk's result ``last`` already landed (the
        disaggregated handoff): the slot goes live, and a fresh request
        is given its first token."""
        self._go_live(h)
        if not h.tokens:
            self._first_token(h, last)

    def _go_live(self, h: RequestHandle):
        """Flip a fully-prefilled slot live: structure only, no token's
        value. Resumed requests already know their next token."""
        slot = h.slot
        # Every page's content is resident in THIS engine's pool, or
        # will be before any later program reads it (the last chunk is
        # enqueued — or, disaggregated, the migration scatter landed):
        # publish the slot's staged prefix pages.
        self.manager.commit_prefix(slot)
        self._lens[slot] = len(h.lane)
        self._live[slot] = 1
        self._toks[slot] = h.lane[-1]
        h.status = "running"
        self._close_resume_span(h, path="reprefill")

    def _first_token(self, h: RequestHandle, last):
        """Seed the first generated token from the final chunk's result
        ``last``, ``(picked tokens, last-valid-token logits)``: a
        greedy request's from row 0 of the picked tokens, a sampled
        one's (and the megakernel lane's, whose chunks pick none) from
        the logits row."""
        picked, logits = last
        with self.obs.span("prefill_fetch", slot=h.slot,
                           request_id=h.request.request_id,
                           chunks=len(h.chunks)) as span:
            if picked is None or h.request.temperature > 0.0:
                row = self._read(logits)
            else:
                picked = self._read(picked)
                exits = self._exit_passes(picked)
                row = _Row(picked[0], logits, exit_pass=(
                    None if exits is None else int(exits[0])))
                if exits is not None and self.obs.enabled:
                    span.fields.update(passes=self.cfg.num_passes,
                                       exit_pass=row.exit_pass)
        self._sample_emit(h, row)

    def _first_tokens(self, flight):
        """Land the first tokens ``flight`` owes (a request that ended
        meanwhile is owed none)."""
        owed, flight.finished = flight.finished, []
        for h, last in owed:
            h.in_flight -= 1
            if not h.done:
                self._first_token(h, last)

    def _read(self, out) -> np.ndarray:
        """A step program's output on the host: the one place the layer
        path's tick copies one, so what a tick fetches is counted here
        (a greedy tick: the picked tokens, ``(1 + num_slots)`` int32)."""
        return np.asarray(out)

    def _sample_emit(self, h: RequestHandle, row):
        """One slot's token, picked from its logits ``row`` (a
        :class:`_Row`: a greedy request takes the token its step
        program picked on the device, stat ``device`` 1; or the floats
        on the host), then its emission (stream callback, retirement),
        each under its own span."""
        rid = h.request.request_id
        device = int(isinstance(row, _Row)
                     and h.request.temperature <= 0.0)
        with self.obs.span("sample", slot=h.slot, request_id=rid,
                           device=device):
            tok = self._pick(row, h.request, len(h.tokens))
            self.stats_counters["tokens_picked_on_device"] += device
            if getattr(row, "exit_pass", None) is not None:
                self._picked_by_pass[row.exit_pass - 1] += 1
        with self.obs.span("emit", slot=h.slot, request_id=rid):
            self._emit(h, tok)

    # -- KV memory hierarchy: demote / prefetch / park / resume ------

    def _gather_tier_pages(self, page_ids) -> tuple:
        """Whole-page tier payload (replicated numpy) for ``page_ids``
        — ``(k, v)`` plus the scale planes on a quantized pool. Two
        call shapes only ((1,) demote, (p_max,) park), so the gather's
        jit cache is bounded at two entries."""
        import jax.numpy as jnp

        payload = self._tier_gather(
            self.cache, jnp.asarray(np.asarray(page_ids, np.int32)))
        return tuple(np.asarray(a) for a in payload)

    def _scatter_tier_payload(self, arrays, dst_ids) -> None:
        """Blit a tier payload back into HBM pages: ``arrays`` hold
        ``n`` pages along axis 1, ``dst_ids`` the ``n`` target pool
        slots. Scratch-padded to ``p_max`` — one fixed-shape dispatch
        whatever the payload size (padding rows land in the scratch
        page, benign garbage by contract)."""
        import jax.numpy as jnp
        from triton_dist_tpu.serving.blocks import SCRATCH_PAGE

        n = int(arrays[0].shape[1])
        ids = np.full((self.p_max,), SCRATCH_PAGE, np.int32)
        ids[:n] = np.asarray(dst_ids, np.int32)
        padded = []
        for a in arrays:
            a = np.asarray(a)
            pad = np.zeros(a.shape[:1] + (self.p_max - n,)
                           + a.shape[2:], a.dtype)
            padded.append(jnp.asarray(np.concatenate([a, pad], axis=1)))
        self.cache = self._tier_scatter(self.cache, *padded,
                                        jnp.asarray(ids))

    def _tier_fetch_prefix(self, key):
        """One prefix payload off the tier: the router-time warm
        buffer when present (its transfer already ran at route time),
        else a live ``tier_transfer`` hop. Raises the transfer's fault
        past retries; returns None on a genuine miss."""
        warm = self._tier_warm.pop(key, None)
        if warm is not None:
            return warm
        return self._run_op_with_retry(
            "tier_transfer", lambda: self.tiers.get(("prefix", key)))

    def _tier_resident_prefix(self, key) -> bool:
        """Is ``key``'s payload reachable below HBM (tier entry or the
        router-time warm buffer)? The affinity/prefetch membership
        test — never transfers."""
        return (key in self._tier_warm
                or ("prefix", key) in self.tiers)

    def tier_prefetch(self, tokens) -> int:
        """Router-time predictive prefetch (ROADMAP item 4 remainder):
        run the ``tier_transfer`` hop for the prompt's tier-resident
        leading prefix run NOW — at routing time — into a host-side
        warm buffer the admission-time fetch consumes without a second
        transfer, so the (disk unspill / bridge hop) latency overlaps
        queue wait instead of starting at admission. Walks
        ``BlockManager.iter_prefix_keys`` — the same chain
        ``alloc_prefill`` consumes: HBM-resident keys extend the run,
        the first genuinely cold key ends it. Safe no-op without tiers/prefix-reuse (the
        admission-time fetch is unchanged when routing is off).
        Returns the page count warmed."""
        from triton_dist_tpu.resilience import faults
        from triton_dist_tpu.resilience.integrity import IntegrityError
        from triton_dist_tpu.resilience.watchdog import CommTimeoutError

        if (self.tiers is None or self.manager is None
                or not self.manager.prefix_reuse):
            return 0
        t0 = self.obs.now()
        fetched = 0
        for key in self.manager.iter_prefix_keys(tokens):
            if key in self.manager._prefix:
                continue              # HBM-resident: the run goes on
            if key in self._tier_warm:
                continue              # already warmed
            if ("prefix", key) not in self.tiers:
                break                 # genuinely cold: run ends
            try:
                arrays = self._run_op_with_retry(
                    "tier_transfer",
                    lambda k=key: self.tiers.get(("prefix", k)))
            except IntegrityError as e:
                # Corrupt payload: quarantined by the store — a miss
                # (the content recomputes); never served.
                self._note_integrity_failure("tier_get", e)
                break
            except (CommTimeoutError, faults.InjectedFault):
                break                 # faulted past retries: a miss
            if arrays is None:
                break
            self._tier_warm[key] = arrays
            while len(self._tier_warm) > self._tier_warm_cap:
                self._tier_warm.popitem(last=False)
            fetched += 1
        if fetched:
            self.stats_counters["router_prefetched_pages"] += fetched
            self.obs.complete_span("kv_prefetch", t0, pages=fetched,
                                   payload="router")
        return fetched

    def _demote_prefix_page(self, key, pid) -> bool:
        """BlockManager eviction hook: offload one cold committed
        prefix page into the tier store BEFORE its HBM page frees
        (stage → transfer → commit; the manager frees only after this
        returns). A dropped/wedged transfer past retries — or a tier
        full of pinned parked sessions — returns False: the content
        drops instead (recomputable by contract), eviction proceeds,
        the server never stalls on its own cache."""
        from triton_dist_tpu.resilience import faults
        from triton_dist_tpu.resilience.watchdog import CommTimeoutError
        from triton_dist_tpu.serving.tiers import TierFullError

        try:
            with self.obs.span("kv_offload", pages=1, payload="prefix"):
                arrays = self._gather_tier_pages([pid])
                self._run_op_with_retry(
                    "tier_transfer",
                    lambda: self.tiers.put(("prefix", key), arrays,
                                           pages=1))
        except (CommTimeoutError, faults.InjectedFault, TierFullError):
            return False
        self.stats_counters["offloaded_pages"] += 1
        return True

    def _tier_prefill_fetch(self, h: RequestHandle, slot: int) -> int:
        """Extend ``slot``'s resident leading-page run with prefix
        pages prefetched FROM THE TIER: for each staged (missed) page
        whose chained content key is tier-resident, scatter the
        payload into the already-allocated page, publish it
        (``commit_pages``) and pop the tier entry — the promote half
        of the two-phase transition. Stops at the first genuinely
        cold page (neither HBM-shared nor tier-resident): hits must
        stay a leading run, the contract every blit/chunk skip is
        built on. Returns the page count fetched."""
        if self.tiers is None or self.manager is None:
            return 0
        pend = self.manager._pending_prefix.get(slot)
        if not pend:
            return 0
        from triton_dist_tpu.resilience import faults
        from triton_dist_tpu.resilience.integrity import IntegrityError
        from triton_dist_tpu.resilience.watchdog import CommTimeoutError

        pend_by_pid = {pid: key for key, pid in pend}
        pages = self.manager._slot_pages[slot]
        pos = self.manager.prefix_hits(slot)
        fetch = []                          # (pid, payload arrays)
        while pos < len(pages):
            pid = pages[pos]
            key = pend_by_pid.get(pid)
            if key is None:
                # Not a staged miss: resident only if it is a SHARED
                # page (slot ref + cache/another holder); a private
                # page (the ragged tail, or anything past the prefix-
                # eligible region) ends the run.
                if self.manager._refs.get(pid, 0) > 1:
                    pos += 1
                    continue
                break
            try:
                arrays = self._tier_fetch_prefix(key)
            except IntegrityError as e:
                # Quarantined by the store: a miss — the prefix
                # content recomputes through the normal chunk stream.
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
                           payload="prefix"):
            stacked = tuple(
                np.concatenate([arr[i] for _, arr in fetch], axis=1)
                for i in range(len(fetch[0][1])))
            self._scatter_tier_payload(stacked,
                                       [pid for pid, _ in fetch])
        # Bytes resident: publish the pages (shareable NOW) — the
        # manager's on_commit hook pops each tier entry as its key
        # publishes, so HBM is the one authoritative tier again.
        self.manager.commit_pages(slot, [pid for pid, _ in fetch])
        self.manager.note_tier_hits(slot, pos)
        self.stats_counters["tier_hits"] += len(fetch)
        self.stats_counters["prefetched_pages"] += len(fetch)
        return len(fetch)

    def park(self, h: RequestHandle) -> RequestHandle:
        """Park a RUNNING request: offload its KV pages wholesale into
        the tier store (requantized under ``park_quant``), release its
        slot and HBM pages for other traffic, and keep the
        token-preserving handle in the parked registry
        (``stats()["parked_sessions"]``). :meth:`resume` continues it
        token-exact — BIT-exact when the payload was not requantized.
        The offload is two-phase: slot and pages free only after the
        tier transfer commits, so a failed park (dropped transfer
        past retries, or :class:`~triton_dist_tpu.serving.tiers.
        TierFullError`) leaves the request RUNNING, untouched."""
        if self.mega:
            raise NotImplementedError(
                "park/resume on the megakernel lane: the park payload "
                "is gathered from layer-shaped pool leaves, but the "
                "megakernel's KV lives in its in-kernel arena (the "
                "arena-tier limitation) — tracked by ROADMAP Open "
                "item 3, 'Megakernel serving parity — remainder'")
        if self.tiers is None:
            raise RuntimeError(
                "park() needs kv_tiers — the tier store holds the "
                "parked payload (docs/serving.md, 'KV memory "
                "hierarchy')")
        self._land()        # the payload and the tokens it parks with
        if h.status != "running" or h.slot is None or not h.tokens:
            raise ValueError(
                f"park() needs a running slot-holder; request "
                f"{h.request.request_id} is {h.status!r}")
        from triton_dist_tpu.serving.blocks import SCRATCH_PAGE
        from triton_dist_tpu.serving.tiers import quantize_park_payload

        slot, rid = h.slot, h.request.request_id
        n_tok = int(self._lens[slot])
        # Page list derived from the LENGTH MIRROR, not the allocator:
        # a failed dispatch's idempotent pre-append can leave the
        # allocator one page ahead of _lens, and resume's
        # alloc_resume(n_tok) must re-derive the identical page count
        # (the extra page held only the never-committed position,
        # rewritten by the post-resume decode anyway).
        n_pages = max((n_tok + self.page - 1) // self.page, 1)
        pages = list(self.manager._slot_pages[slot])[:n_pages]
        key = ("session", rid)
        with self.obs.span("park", request_id=rid, slot=slot,
                           tenant=h.request.tenant, pages=len(pages),
                           tokens=n_tok):
            ids = np.full((self.p_max,), SCRATCH_PAGE, np.int32)
            ids[:len(pages)] = pages
            with self.obs.span("kv_offload", request_id=rid, slot=slot,
                               tenant=h.request.tenant,
                               pages=len(pages), payload="session"):
                # Materialized copy, not a slice VIEW: the tier would
                # otherwise retain the whole p_max-wide gather buffer
                # behind every parked page — defeating the host_pages
                # budget by up to p_max/n_pages.
                arrays = tuple(np.ascontiguousarray(a[:, :len(pages)])
                               for a in self._gather_tier_pages(ids))
                meta = {"n_tok": n_tok, "park_quant": None}
                if self.park_quant is not None:
                    arrays = quantize_park_payload(arrays,
                                                   self.park_quant)
                    meta["park_quant"] = self.park_quant
                self._run_op_with_retry(
                    "tier_transfer",
                    lambda: self.tiers.put(key, arrays,
                                           pages=len(pages),
                                           pinned=True, meta=meta))
            # Transfer committed — only NOW does the HBM side release
            # (the two-phase demotion: a fault above left everything
            # running).
            self.sched.slots.pop(slot, None)
            h.slot = None
            self._vacate(slot)
            h.status = "parked"
            self._parked[rid] = h
            self.stats_counters["parks"] += 1
            self.stats_counters["offloaded_pages"] += len(pages)
        return h

    def resume(self, h: RequestHandle) -> RequestHandle:
        """Resume a parked session: requeue it at the HEAD with its
        tier payload marked for prefetch. Admission allocates fresh
        pages and dispatches the scatter WITHOUT blocking — the handle
        parks one tick as ``"resuming"`` while in-flight decode
        dispatches run over the transfer, then reactivates
        token-exact at its parked position (the ``resume`` span /
        ``session_resume_ms`` measure requeue → reactivation)."""
        if h.status != "parked":
            raise ValueError(
                f"resume() needs a parked handle; request "
                f"{h.request.request_id} is {h.status!r}")
        rid = h.request.request_id
        self._parked.pop(rid, None)
        h.status = "queued"
        h.queued_at = self.sched.now()
        h.resume_key = ("session", rid)
        h.resume_t0 = h.queued_at
        self.sched.queue.appendleft(h)
        self.stats_counters["resumes"] += 1
        return h

    def _admit_resume(self, h: RequestHandle,
                      stalled: List[RequestHandle]) -> bool:
        """Slot assigned to a resuming session: prefetch its tier
        payload into fresh pages (async dispatch — activation happens
        at the NEXT tick boundary, so the scatter overlaps this tick's
        decode). Returns False when the payload is unavailable
        (dropped transfer past retries): the caller falls through to
        the deterministic re-prefill contract, which is equally
        token-exact, just slower."""
        from triton_dist_tpu.resilience import faults
        from triton_dist_tpu.resilience.integrity import IntegrityError
        from triton_dist_tpu.resilience.watchdog import CommTimeoutError
        from triton_dist_tpu.serving.tiers import (
            dequantize_park_payload)

        slot, key = h.slot, h.resume_key
        entry = self.tiers.entry(key)
        if entry is None:
            self.stats_counters["tier_misses"] += 1
            h.resume_key = None
            return False
        # Allocate BEFORE fetching: a pool-dry tick must not pay the
        # payload transfer (disk unspill / bridge hop) just to throw
        # it away and repeat it on every stalled retry.
        n_tok = int(entry.meta.get(
            "n_tok", len(h.request.prompt) + len(h.tokens) - 1))
        try:
            pages = self.manager.alloc_resume(slot, n_tok)
        except OutOfPagesError as e:
            # Pool dry: the payload stays tier-resident and the
            # resume_key survives the requeue — retried next tick.
            self._unadmit(h, e, stalled)
            return True
        try:
            arrays = self._run_op_with_retry(
                "tier_transfer", lambda: self.tiers.get(key))
        except IntegrityError as e:
            # Corrupt parked payload: quarantined — fall through to
            # the deterministic re-prefill (token-exact, never serves
            # the corrupted bytes).
            self._note_integrity_failure(
                "tier_get", e, request_id=h.request.request_id)
            arrays = None
        except (CommTimeoutError, faults.InjectedFault):
            arrays = None
        if arrays is None:
            # Transfer faulted past retries (or the payload vanished):
            # release the fresh pages and fall back to the
            # deterministic re-prefill — equally token-exact.
            self.manager.free_slot(slot)
            self.stats_counters["tier_misses"] += 1
            self.tiers.pop(key, None)
            h.resume_key = None
            return False
        if entry.meta.get("park_quant") and not self.cache.quantized:
            arrays = dequantize_park_payload(
                arrays, np.dtype(self.cache.k_pages.dtype))
        with self.obs.span("kv_prefetch",
                           request_id=h.request.request_id, slot=slot,
                           tenant=h.request.tenant, pages=len(pages),
                           payload="session"):
            self._scatter_tier_payload(arrays, pages)
        h.status = "resuming"
        self._lens[slot] = self._live[slot] = self._toks[slot] = 0
        self._resuming.append((h, key))
        self.stats_counters["tier_hits"] += 1
        self.stats_counters["prefetched_pages"] += len(pages)
        return True

    def _close_resume_span(self, h: RequestHandle, *,
                           path: str) -> None:
        """Close the resume span at REACTIVATION whichever route got
        there — the overlapped prefetch or the re-prefill fallback
        after a faulted/missing payload. ``session_resume_ms`` must
        include the slow path, or it reads optimistic exactly when
        tier transfers are failing. No-op for handles that are not
        mid-resume."""
        if h.resume_t0 is None:
            return
        self.obs.complete_span(
            "resume", h.resume_t0, request_id=h.request.request_id,
            slot=h.slot, tenant=h.request.tenant,
            tokens=len(h.tokens), path=path)
        h.resume_t0 = None

    def _collect_resumes(self) -> None:
        """Activate LAST tick's resume prefetches — their scatters have
        been in flight across the gap, overlapped with every dispatch
        issued since (resume latency hides behind decode, not ahead
        of it)."""
        pend, self._resuming = self._resuming, []
        for h, key in pend:
            if h.status != "resuming":
                continue      # expired/failed meanwhile; _retire
                              # already cleaned the tier entry up
            slot = h.slot
            self._lens[slot] = (len(h.request.prompt)
                                + len(h.tokens) - 1)
            self._live[slot] = 1
            self._toks[slot] = h.tokens[-1]
            h.status = "running"
            # Promotion commit: HBM is the authoritative tier again.
            self.tiers.pop(key, None)
            h.resume_key = None
            self._close_resume_span(h, path="prefetch")

    # -- the decode tick --------------------------------------------

    def _launch_decode(self, flight, active, tbl):
        """The joint decode step as a program of its own."""
        from triton_dist_tpu.resilience import faults
        from triton_dist_tpu.resilience.watchdog import CommTimeoutError

        sampled = _samples(active)

        # The joint decode rides its own fault-op scope: chaos /
        # fault plans can drop or wedge the k-th decode dispatch
        # and the containment below fails the victim, not the
        # server (survivors redo the identical dispatch — length
        # mirrors never advanced).
        # A TRANSIENT drop (InjectedFault — raised at the fault
        # scope's entry, before the dispatch mutates anything) is
        # absorbed by one retry pass when a serving_decode
        # RetryPolicy is armed: the length mirrors only advance on
        # success, so the replayed joint dispatch is byte-
        # identical. A WEDGE (CommTimeoutError) is deliberately
        # NOT in retry_on — a wedged joint dispatch blocks its own
        # replay (docs/resilience.md) — and goes straight to the
        # fail-one containment below.
        def _attempt():
            with faults.on_op_call("serving_decode"):
                return self._enqueue_decode(flight, active, tbl, sampled)

        try:
            program = self._run_op_with_retry(
                "serving_decode", _attempt,
                retry_on=(faults.InjectedFault,))
        except (CommTimeoutError, faults.InjectedFault) as e:
            self._decode_contain(e)
            return
        flight.programs += 1
        self._board(flight, active, *program, sampled=sampled,
                    stat_rows=self.num_slots)

    def _land(self, flight=None) -> int:
        """The LAND half of a tick, which needs the values: ``flight``
        (default: the one in flight) has its decode rows' tokens
        waited for, fetched, sampled and emitted, the stats that ride
        behind them booked, and the first tokens of the prompts that
        became resident in it emitted: after the decode rows' where
        those rode a chunk's program (the slots went live for the NEXT
        tick), before them where the step was a program of its own
        (which the slots joined). Returns how many sequences it
        decoded."""
        from triton_dist_tpu.resilience.watchdog import CommTimeoutError

        if flight is None:
            flight, self._flight = self._flight, None
            if flight is None:
                return 0
        if not flight.fused:
            self._first_tokens(flight)
        decoded = flight.decoded
        if flight.rows:
            try:
                with self.obs.span(
                        "decode",
                        step=self.stats_counters["decode_dispatches"],
                        batch=len(flight.rows), fused=flight.fused,
                        launched=flight.tick) as span:
                    rows = self._fetch_rows(flight, span)
            except CommTimeoutError as e:
                self._land_failed(flight, e)
            else:
                decoded = self._decode_commit(flight, rows)
        self._first_tokens(flight)
        self._landed_at = time.perf_counter()
        return decoded

    def _land_failed(self, flight, e):
        """The watchdog missed ``flight``'s joint program: nothing of
        the step is known, so the length mirrors go back to where it
        started, the victim(s) fail, and the survivors redo the
        identical step; a chunk that program carried fails with it."""
        for h, slot in flight.rows:
            h.in_flight -= 1
            if self.sched.slots.get(slot) is h:
                self._lens[slot] -= 1
        self._decode_contain(e)
        if flight.first is not None and not flight.first.done:
            self._fail(flight.first, "timeout", e)

    def _decode_contain(self, e):
        """The joint decode was wedged or dropped: fail the victim(s),
        not the server — the survivors redo the identical step."""
        from triton_dist_tpu.resilience.watchdog import CommTimeoutError

        timed_out = isinstance(e, CommTimeoutError)
        if timed_out:
            self.stats_counters["comm_timeouts"] += 1
            self.obs.event("timeout", op="serving.decode")
        if self.mega and getattr(self.engine, "states",
                                 None) is not None:
            # Hybrid GDN: the recurrent state is NOT position-
            # addressed, so a retried step would advance survivors'
            # states twice for one token — no exact recovery
            # exists. Fail every in-flight request; the server (and
            # new requests, via reset_slot) stay healthy.
            victims = list(self.sched.running())
        else:
            victims = self.sched.timeout_victims()
        for victim in victims:
            self._fail(victim, "timeout" if timed_out else "failed", e)

    def _decode_commit(self, flight, rows) -> int:
        """Book the decode step that ``flight`` carried: counters, and
        each slot's token from its row of ``rows`` (the layer path's
        :class:`_Rows`, each with its program's own pick; the
        megakernel lane's host logits). A row whose request ended
        while the step was in flight (a stop token the tick before, a
        deadline) is dropped: the position it wrote lies in pages that
        were freed with the slot. Returns how many sequences decoded."""
        now = time.perf_counter()
        self.stats_counters["decode_time_s"] += now - max(
            flight.t0, self._landed_at)
        self.stats_counters["decode_dispatches"] += 1
        self.stats_counters["decode_dispatches_fused"] += flight.fused
        self._maybe_rebalance()

        for h, slot in flight.rows:
            h.in_flight -= 1
            if h.done:
                self.stats_counters["rows_discarded"] += 1
                continue
            if self.mega and h.status == "prefill":
                h.prompt_pos += 1
                if h.prompt_pos < len(h.lane):
                    continue
                h.status = "running"   # last lane token's logits
                if self.manager is not None:
                    # The lane's final token just wrote its page —
                    # the prompt's pages are shareable from here.
                    self.manager.commit_prefix(slot)
                if h.tokens:
                    # Resumed lane: the next token to feed is already
                    # known (h.tokens[-1]); do not re-pick it.
                    continue
            h.decode_steps += 1
            self.stats_counters["decode_tokens"] += 1
            self._sample_emit(h, rows[slot])
        return len(flight.rows)

    def _decode_prep(self, active):
        """Host work before the joint dispatch: page growth for the
        position each slot writes, and the block table. Returns the
        handles that still decode (pool-dry ones are preempted) and the
        table. A preempted request requeues with the tokens it has, so
        the tick in flight lands first, and the prep starts over on
        what that leaves."""
        preempted = []
        for h in active:
            slot = h.slot
            if self.manager is not None and not (
                    self.mega and h.status == "prefill"):
                # Page-boundary growth for the (generated) token being
                # written this step; prefill-lane tokens land in pages
                # alloc_prefill already reserved. Passing the position
                # keeps the accounting idempotent across a timed-out
                # step's retry. A row overflow here is a caller bug
                # (submit validates capacity) — propagate.
                try:
                    self.manager.append(slot, int(self._lens[slot]))
                except OutOfPagesError as e:
                    if self._flight is not None:
                        self._land()
                        return self._decode_prep(self._decoders())
                    # Pool dry MID-DECODE: preempt this request —
                    # release its pages, requeue it at the head, and
                    # let it resume later via re-prefill of prompt +
                    # generated-so-far (deterministic, so still
                    # token-exact). One starving request must not
                    # crash the server.
                    self._preempt(h, e)
                    preempted.append(h)
        if preempted:
            active = [h for h in active if h not in preempted]
        tbl = np.zeros((self.num_slots, self.p_max if self.manager is None
                        else self.manager.table_width), np.int32)
        if self.manager is not None:
            for h in active:
                tbl[h.slot] = self.manager.table_row(h.slot)
        return active, tbl

    # -- the speculative tick (spec_k >= 1, layer path) --------------

    def _spec_tick(self) -> int:
        """One serving tick through the K-token VERIFICATION dispatch:
        draft → one fixed-shape dispatch → greedy acceptance → commit
        the accepted prefix, roll the rejected suffix's page growth
        back (``truncate_to``). Token-exact with the non-spec greedy
        loop by construction; non-greedy (sampled) requests ride the
        same dispatch but commit exactly one token from position 0's
        exact logits."""
        import dataclasses as _dc

        import jax.numpy as jnp
        from triton_dist_tpu.resilience import faults
        from triton_dist_tpu.resilience.watchdog import (
            CommTimeoutError, block_until_ready)
        from triton_dist_tpu.serving.spec import accept_greedy

        if self.mega:
            return self._spec_tick_mega()
        active = [h for h in self.sched.running()
                  if h.status == "running"]
        if not active:
            return 0
        kk = self.spec_k
        preempted = []
        drafts: dict = {}
        budget = np.zeros((self.num_slots,), np.int32)
        draft_span = self.obs.span("spec_draft", batch=len(active),
                                   k=kk)
        draft_span.__enter__()
        for h in active:
            slot = h.slot
            base = int(self._lens[slot])
            # Feed budget: how many candidates may commit (and write
            # real pages) — bounded by the request's remaining token
            # budget, so a fixed-K dispatch never grows pages past
            # what submit() validated.
            rem = h.request.max_new_tokens - len(h.tokens)
            n_pre = max(1, min(kk, rem))
            budget[slot] = n_pre
            try:
                for j in range(n_pre):
                    self.manager.append(slot, base + j)
            except OutOfPagesError as e:
                # Pool dry MID-DRAFT: preempt — pages freed, requeued
                # at the head, resumed via the deterministic re-prefill
                # (the draft replays from the same history).
                self._preempt(h, e)
                preempted.append(h)
                continue
            hist = list(h.request.prompt) + [int(t) for t in h.tokens]
            d = [int(h.tokens[-1])]
            if kk > 1:
                if h.request.temperature <= 0.0:
                    d += self._draft.propose(hist, kk - 1)
                    # Count only candidates that COULD commit (the
                    # budget caps acceptance near a request's tail) —
                    # accept_rate measures draft quality, not budget
                    # clipping.
                    self.stats_counters["spec_drafted"] += n_pre - 1
                else:
                    d += [d[-1]] * (kk - 1)   # sampled: 1 commit max
                    self.stats_counters["spec_sampled_fallbacks"] += 1
            drafts[slot] = d
        draft_span.__exit__(None, None, None)
        if preempted:
            active = [h for h in active if h not in preempted]
            if not active:
                return 0
        tbl = np.zeros((self.num_slots, self.p_max), np.int32)
        toks = np.zeros((self.num_slots, kk), np.int32)
        for h in active:
            tbl[h.slot] = self.manager.table_row(h.slot)
            toks[h.slot] = drafts[h.slot]

        t0 = time.perf_counter()
        try:
            # Transient drop (InjectedFault at the fault scope's
            # entry, nothing mutated) → one retry pass when a
            # spec_verify RetryPolicy is armed; a wedge is NOT
            # retried — straight to fail-one (docs/resilience.md).
            def _attempt():
                with self.obs.span(
                        "spec_verify",
                        step=self.stats_counters["decode_dispatches"],
                        batch=len(active), k=kk), \
                        faults.on_op_call("spec_verify"):
                    cache = _dc.replace(self.cache,
                                        block_table=jnp.asarray(tbl),
                                        lens=jnp.asarray(self._lens),
                                        live=jnp.asarray(self._live))
                    logits, self.cache = self._verify(
                        self.engine.params, jnp.asarray(toks),
                        jnp.asarray(budget), cache)
                    if self.timeout_s is not None:
                        logits = block_until_ready(
                            logits, timeout_s=self.timeout_s,
                            op="serving.spec_verify",
                            progress_fn=lambda: {
                                "lens": self._lens.tolist(),
                                "live": self._live.tolist(),
                                "spec_k": kk,
                                **{k: self.stats_counters[k] for k in
                                   ("decode_dispatches",
                                    "spec_accepted")}})
                return logits

            logits = np.asarray(self._run_op_with_retry(
                "spec_verify", _attempt,
                retry_on=(faults.InjectedFault,)))
        except (CommTimeoutError, faults.InjectedFault) as e:
            # A wedged collective or a dropped verification (past any
            # armed retry) fails the scheduler's victim(s), never the
            # server: no length mirror advanced, so survivors redo
            # the identical dispatch token-exactly.
            if isinstance(e, CommTimeoutError):
                self.stats_counters["comm_timeouts"] += 1
            for victim in self.sched.timeout_victims():
                self._fail(victim,
                           "timeout" if isinstance(e, CommTimeoutError)
                           else "failed", e)
            return 0
        self.stats_counters["decode_time_s"] += time.perf_counter() - t0
        self.stats_counters["decode_dispatches"] += 1

        for h in active:
            slot = h.slot
            d = drafts[slot]
            h.decode_steps += 1
            greedy = h.request.temperature <= 0.0
            if greedy:
                picks = [int(np.argmax(logits[slot, j]))
                         for j in range(kk)]
                m = accept_greedy(d, picks)
            else:
                m = 1
            m = min(m, int(budget[slot]))
            if kk > 1 and greedy:
                self.stats_counters["spec_accepted"] += m - 1
            # Commit the accepted prefix BEFORE emitting (an emission
            # may retire the request and free the slot's pages).
            base = int(self._lens[slot])
            self._lens[slot] = base + m
            self.manager.truncate_to(slot, base + m)
            rolled = int(budget[slot]) - m
            if rolled > 0:
                self.obs.event("spec_rollback",
                               request_id=h.request.request_id,
                               slot=slot, accepted=m, rolled=rolled)
            self.stats_counters["decode_tokens"] += m
            for j in range(m):
                if h.done:
                    break
                tok = (picks[j] if greedy else
                       self._pick(logits[slot, j], h.request,
                                  len(h.tokens)))
                self._emit(h, tok)
        return len(active)

    def _spec_tick_mega(self) -> int:
        """The megakernel speculative tick: every decode-side dispatch
        is ONE Q-block verification launch
        (:meth:`MegaKernelEngine.verify_step`) — running slots feed
        their K drafted candidates at per-row positions, PREFILL-LANE
        slots ride row (slot, 0) with the lane's next token (rows
        1..K-1 masked), so the jitted step count stays at one entry.
        Acceptance/rollback/draft logic is the layer tick's,
        token-exact with the non-spec megakernel run by construction
        (the verification rows' logits are bit-identical to the
        sequential decode body's)."""
        import jax.numpy as jnp
        from triton_dist_tpu.resilience import faults
        from triton_dist_tpu.resilience.watchdog import CommTimeoutError
        from triton_dist_tpu.serving.spec import accept_greedy

        kk = self.spec_k
        active = [h for h in self.sched.running()
                  if h.status == "running"
                  or (h.status == "prefill"
                      and self._prefiller is None)]
        if not active:
            return 0
        preempted = []
        drafts: dict = {}
        budget = np.zeros((self.num_slots,), np.int32)
        pos = np.full((self.num_slots * kk,), -1, np.int32)
        toks = np.zeros((self.num_slots, kk), np.int32)
        draft_span = self.obs.span("spec_draft", batch=len(active),
                                   k=kk)
        draft_span.__enter__()
        for h in active:
            slot = h.slot
            if h.status == "prefill":
                # Prefill lane: one lane token this tick through row
                # (slot, 0); its pages were reserved at admission.
                toks[slot, 0] = h.lane[h.prompt_pos]
                pos[slot * kk] = int(self._lens[slot])
                continue
            base = int(self._lens[slot])
            rem = h.request.max_new_tokens - len(h.tokens)
            n_pre = max(1, min(kk, rem))
            try:
                for j in range(n_pre):
                    self.manager.append(slot, base + j)
            except OutOfPagesError as e:
                self._preempt(h, e)
                preempted.append(h)
                continue
            hist = list(h.request.prompt) + [int(t) for t in h.tokens]
            d = [int(h.tokens[-1])]
            if kk > 1:
                if h.request.temperature <= 0.0:
                    d += self._draft.propose(hist, kk - 1)
                    self.stats_counters["spec_drafted"] += n_pre - 1
                else:
                    d += [d[-1]] * (kk - 1)   # sampled: 1 commit max
                    self.stats_counters["spec_sampled_fallbacks"] += 1
            drafts[slot] = d
            budget[slot] = n_pre
            toks[slot] = d
            # Over-budget rows stay at -1: the kernel MASKS them, so
            # they never touch real pages (or, quantized, scales).
            for j in range(n_pre):
                pos[slot * kk + j] = base + j
        draft_span.__exit__(None, None, None)
        if preempted:
            active = [h for h in active if h not in preempted]
            if not active:
                return 0
        tbl = np.zeros((self.num_slots, self.p_max), np.int32)
        for h in active:
            tbl[h.slot] = self.manager.table_row(h.slot)
        self.engine.block_table = jnp.asarray(tbl.reshape(-1),
                                              jnp.int32)
        if (self._mk_counts_base is None
                and hasattr(self.engine, "expert_counts")
                and getattr(self.cfg, "is_moe", False)):
            # The verification dispatch carries the in-arena router
            # counters exactly like the decode dispatch — same
            # pre-serving-warmup baseline discipline as _dispatch.
            self._mk_counts_base = self.engine.expert_counts()

        t0 = time.perf_counter()
        try:
            # Same transient-retry contract as the layer spec tick:
            # the fault raises at scope entry (the in-arena verify
            # never launched — positions unchanged), so one replay is
            # byte-identical; wedges stay fail-one.
            def _attempt():
                with self.obs.span(
                        "spec_verify",
                        step=self.stats_counters["decode_dispatches"],
                        batch=len(active), k=kk), \
                        faults.on_op_call("spec_verify"):
                    return np.asarray(self.engine.verify_step(
                        jnp.asarray(toks.reshape(-1)),
                        jnp.asarray(pos)))

            logits = self._run_op_with_retry(
                "spec_verify", _attempt,
                retry_on=(faults.InjectedFault,))
        except (CommTimeoutError, faults.InjectedFault) as e:
            if isinstance(e, CommTimeoutError):
                self.stats_counters["comm_timeouts"] += 1
            for victim in self.sched.timeout_victims():
                self._fail(victim,
                           "timeout" if isinstance(e, CommTimeoutError)
                           else "failed", e)
            return 0
        self.stats_counters["decode_time_s"] += time.perf_counter() - t0
        self.stats_counters["decode_dispatches"] += 1
        if self._mk_counts_base is not None:
            total = self.engine.expert_counts()
            self._note_expert_counts(total - self._mk_counts_base)
            self._mk_counts_base = total
        self._maybe_rebalance()

        for h in active:
            slot = h.slot
            if h.status == "prefill":
                self._lens[slot] += 1
                h.prompt_pos += 1
                if h.prompt_pos < len(h.lane):
                    continue
                h.status = "running"   # last lane token's logits
                if self.manager is not None:
                    self.manager.commit_prefix(slot)
                if h.tokens:
                    continue           # resumed lane: next token known
                h.decode_steps += 1
                self.stats_counters["decode_tokens"] += 1
                first = self._pick(logits[slot, 0], h.request, 0)
                self._emit(h, first)
                continue
            d = drafts[slot]
            h.decode_steps += 1
            greedy = h.request.temperature <= 0.0
            if greedy:
                picks = [int(np.argmax(logits[slot, j]))
                         for j in range(kk)]
                m = accept_greedy(d, picks)
            else:
                m = 1
            m = min(m, int(budget[slot]))
            if kk > 1 and greedy:
                self.stats_counters["spec_accepted"] += m - 1
            base = int(self._lens[slot])
            self._lens[slot] = base + m
            self.manager.truncate_to(slot, base + m)
            rolled = int(budget[slot]) - m
            if rolled > 0:
                self.obs.event("spec_rollback",
                               request_id=h.request.request_id,
                               slot=slot, accepted=m, rolled=rolled)
            self.stats_counters["decode_tokens"] += m
            for j in range(m):
                if h.done:
                    break
                tok = (picks[j] if greedy else
                       self._pick(logits[slot, j], h.request,
                                  len(h.tokens)))
                self._emit(h, tok)
        return len(active)

    def _enqueue_decode(self, flight, active, tbl: np.ndarray,
                        sampled: bool):
        """Hand the device the joint decode step; returns the program
        as :meth:`_Flight.board` takes it, ``(picked tokens, index of
        slot 0 in them, logits, an EP program's expert counts or
        None)``: all still on the device. The
        megakernel lane's step picks no token and its rows come back
        whole, ``(None, 0, logits (num_slots, vocab))``."""
        import dataclasses as _dc

        import jax.numpy as jnp

        if self.mega:
            return self._enqueue_mega(flight, active, tbl)
        # Uploads and the jitted call returning: the part of the
        # dispatch a device idle gap can fall under at this end.
        with self.obs.span("decode_enqueue"):
            toks = self._input_tokens(flight, active)
            lens, live = self._mirrors()
            cache = _dc.replace(self.cache, block_table=jnp.asarray(tbl),
                                lens=lens, live=live)
            if self.ep and self.replicas is not None:
                picked, logits, self.cache, ecounts = self._decode(
                    self.engine.params, toks, cache, self.replicas)
            elif self.ep:
                picked, logits, self.cache, ecounts = self._decode(
                    self.engine.params, toks, cache)
            else:
                ecounts = None
                picked, logits, self.cache = self._decode(
                    self.engine.params, toks, cache)
            # Ask for the copies now, behind the program: the landing's
            # wait then puts no host round trip between the program's
            # end and a copy's start.
            picked.copy_to_host_async()
            if sampled:
                logits.copy_to_host_async()
        return picked, 0, logits, ecounts

    def _enqueue_mega(self, flight, active, tbl: np.ndarray):
        """The megakernel lane's joint step, under the engine's own
        watchdog."""
        import jax.numpy as jnp

        toks = self._input_tokens(flight, active)
        lens, _ = self._mirrors()
        if self.manager is not None:
            # Paged megakernel: install THIS tick's allocator table
            # (flat (batch·p_max,), the builder's prefetch layout) —
            # the engine's identity table is only its standalone
            # default, and parked rows must hit the scratch page.
            self.engine.block_table = jnp.asarray(
                tbl.reshape(-1), jnp.int32)
        if (self._mk_counts_base is None
                and hasattr(self.engine, "expert_counts")
                and getattr(self.cfg, "is_moe", False)):
            # In-kernel counters accumulate monotonically in the
            # arena; snapshot BEFORE the first serving dispatch so
            # pre-serving warmup traffic never pollutes the load.
            self._mk_counts_base = self.engine.expert_counts()
        out = self.engine.decode_step(toks, lens)
        if (self._trace_session is not None
                and getattr(self.engine, "last_prof",
                            None) is not None):
            # Megakernel slot records for the merged trace: only
            # while a trace session is open (prof_tracks syncs the
            # step), keyed by this dispatch's step index.
            self._trace_session.add_slot_record(
                self.stats_counters["decode_dispatches"],
                self.engine.builder.prof_tracks(
                    self.engine.last_prof))
        if self._mk_counts_base is not None:
            total = self.engine.expert_counts()
            self._note_expert_counts(total - self._mk_counts_base)
            self._mk_counts_base = total
        return None, 0, out

    def _fetch_rows(self, flight, span):
        """A landing's two parts a device idle gap can fall under: the
        host blocked until the picked tokens exist (``decode_wait``),
        then their copy to the host, and the logits' where a row
        samples (``decode_fetch``), with what rides behind the tokens
        booked (``STEP_STATS``, ``ROW_STATS`` onto the open ``decode``
        span, an EP program's counts). Returns the step's rows by slot,
        as :meth:`_decode_commit` takes them: the layer path's
        :class:`_Rows`, the megakernel lane's host logits (num_slots,
        vocab)."""
        if flight.picked is None:
            return np.asarray(flight.logits)
        picked, ecounts = flight.picked, flight.ecounts
        with self.obs.span("decode_wait"):
            # The counts output rides the SAME dispatch: it must sit
            # inside the watchdog-bounded wait, or a wedged collective
            # would hang the host in the counts conversion below
            # before the deadline ever fires.
            self._wait_decode(picked if ecounts is None
                              else (picked, ecounts))
        with self.obs.span("decode_fetch"):
            if ecounts is not None:
                self._note_expert_counts(
                    self._read(ecounts).astype(np.int64))
            host = self._read(picked)
            # A prompt that rode this program reads row 0 of the same
            # array: it is on the host now.
            flight.finished = [
                (h, (host, last[1]) if last[0] is picked else last)
                for h, last in flight.finished]
            exits = self._exit_passes(host)
            rows = _Rows(host[flight.base:], flight.logits,
                         self._read(flight.logits) if flight.sampled
                         else None,
                         None if exits is None else exits[flight.base:])
            self._note_step_stats(host, flight.stat_rows)
            if self.obs.enabled and exits is not None:
                span.fields.update(
                    passes=self.cfg.num_passes,
                    exit_pass=float(np.mean(
                        [rows.exits[slot] for _, slot in flight.rows])))
        return rows

    def _exit_passes(self, picked: np.ndarray):
        """The pass each head row of a step program took, 1-based: what
        a model with ``ROW_STATS`` appends to its picked tokens, row for
        row; None for any other model."""
        return picked[len(picked) // 2:] if self._row_stats else None

    def _note_step_stats(self, picked: np.ndarray, rows: int):
        """Book the ``STEP_STATS`` a step program of ``rows`` rows
        appended to its picked tokens (a model that has none: nothing):
        the counters behind ``stats()["expert_pairs_held"]`` and its
        neighbours, and one ``expert_load`` event, which a profiler
        capture holds as ``tdt.expert_load``. Read only where the tick
        fetched the picked tokens anyway: a chunk program that ran with
        its decode rows parked, mid-prompt, is not counted."""
        if not self._step_stats:
            return
        cfg = self.cfg
        held, rows_max, passes = (int(v) for v in
                                  picked[-len(self._step_stats):])
        routed = rows * cfg.num_experts_per_tok * cfg.num_moe_layers
        c = self.stats_counters
        c["expert_pairs_routed"] += routed
        c["expert_pairs_held"] += held
        c["expert_rows_max"] += rows_max
        c["expert_passes"] += passes
        c["expert_steps"] += 1
        self.obs.event(
            "expert_load", step=c["decode_dispatches"], rows=rows,
            held_pairs=held, routed_pairs=routed,
            expert_rows_max=rows_max, passes=passes,
            expert_imbalance=(rows_max * cfg.held_experts / held
                              if held else None))

    def _wait_decode(self, outputs):
        """Block until a decode step's ``outputs`` exist, under the
        watchdog where ``timeout_s`` arms one; returns them."""
        import jax
        from triton_dist_tpu.resilience.watchdog import block_until_ready

        if self.timeout_s is None:
            return jax.block_until_ready(outputs)
        return block_until_ready(
            outputs, timeout_s=self.timeout_s, op="serving.decode",
            progress_fn=lambda: {
                "lens": self._lens.tolist(),
                "live": self._live.tolist(),
                **{k: self.stats_counters[k] for k in
                   ("decode_dispatches", "tokens_generated")}})

    # -- expert-load telemetry + hot-expert rebalancing --------------

    def _note_expert_counts(self, counts: np.ndarray):
        """Fold one decode step's per-expert routed-token counts into
        the running totals + load EWMA (and the active trace's
        histogram log). Counts come from the decode dispatch itself —
        the layer path's on-device counts output, or the megakernel's
        in-arena router counters."""
        counts = np.asarray(counts, np.int64).reshape(-1)
        if counts.size != self.expert_totals.size or counts.sum() <= 0:
            return
        self.expert_totals += counts
        a = self.load_alpha
        self.expert_ewma = ((1.0 - a) * self.expert_ewma
                            + a * (counts / counts.sum()))
        if self._hist_active:
            self.expert_hist.append(counts.copy())

    @property
    def _telemetry_active(self) -> bool:
        return bool(self.ep or (self.mega and self._mk_counts_base
                                is not None))

    def _maybe_rebalance(self):
        """Between-steps reaction to the load EWMA: replicate hot
        experts (layer path, ``"ll"`` transport) and refresh the
        megakernel's expert-load claim priorities. Pure host work on
        DATA (replica buffers, claim tables) — the decode dispatch is
        never re-specialized."""
        if (self.rebalance_every <= 0
                or self.stats_counters["decode_dispatches"]
                % self.rebalance_every):
            return
        ewma = self.expert_ewma
        if ewma.size == 0 or ewma.sum() <= 0:
            return
        if self.mega:
            self._rebalance_megakernel(ewma)
            return
        if self.replicas is None:
            return
        self._rebalance_replicas(ewma)

    def _rank_loads(self, ewma: np.ndarray):
        """Per-ep-rank load: owned experts' EWMA mass plus hosted
        replicas' (half of a replicated expert's traffic reroutes)."""
        ep_ctx = self.engine.model_kwargs["ep_ctx"]
        n = ep_ctx.mesh.size(ep_ctx.axis)
        e_loc = ep_ctx.num_experts // n
        loads = np.zeros((n,), np.float64)
        for e in range(ep_ctx.num_experts):
            share = 0.5 if e in self._replicated else 1.0
            loads[e // e_loc] += ewma[e] * share
            if e in self._replicated:
                loads[self._replicated[e]] += ewma[e] * 0.5
        return loads, n, e_loc

    def _rebalance_replicas(self, ewma: np.ndarray):
        from triton_dist_tpu.layers import ep_moe as _ep_moe

        loads, n, e_loc = self._rank_loads(ewma)
        if n < 2:
            return
        mean = ewma.mean()
        for e in np.argsort(ewma)[::-1]:
            e = int(e)
            if ewma[e] <= self.hot_expert_factor * mean:
                break
            if e in self._replicated:
                continue
            if not self._replica_free:
                # Evict the coldest replica iff this expert is hotter.
                coldest = min(self._replicated, key=lambda x: ewma[x])
                if ewma[coldest] >= ewma[e]:
                    break
                slot = self._evict_replica(coldest)
            else:
                slot = self._replica_free.pop(0)
            owner = e // e_loc
            cand = [r for r in range(n) if r != owner]
            target = int(min(cand, key=lambda r: loads[r]))
            import jax.numpy as jnp

            layers = self.engine.params["layers"]
            stack = {k: jnp.stack([lp["moe"][k][e] for lp in layers])
                     for k in ("w_gate", "w_up", "w_down")}
            self.replicas = _ep_moe.install_replica_layers(
                self.replicas, slot, e, target, stack["w_gate"],
                stack["w_up"], stack["w_down"])
            self._replicated[e] = target
            loads[owner] -= ewma[e] * 0.5
            loads[target] += ewma[e] * 0.5
        self._commit_replicas()

    def _commit_replicas(self):
        """Re-pin the refreshed replica pytree to the shardings the
        decode dispatch was compiled for (jit keys on shardings, so an
        uncommitted update would re-specialize the cache)."""
        import jax

        self.replicas = jax.tree.map(jax.device_put, self.replicas,
                                     self._replica_shardings)

    def _evict_replica(self, expert: int) -> int:
        """Clear one expert's replica routing; returns its freed slot.
        The routing entry flips to -1 (data), so the very next dispatch
        stops rerouting — weights in the slot are dead until reused."""
        import jax.numpy as jnp

        slot = int(np.asarray(
            self.replicas["slot_expert"][0] == expert).argmax())
        self.replicas = dict(
            self.replicas,
            slot_expert=self.replicas["slot_expert"].at[:, slot].set(-1),
            replica_rank=self.replicas["replica_rank"]
            .at[:, expert].set(-1))
        self._commit_replicas()
        del self._replicated[expert]
        return slot

    def _rebalance_megakernel(self, ewma: np.ndarray):
        """Feed the load EWMA into the dynamic scoreboard: hot-expert
        group-GEMM/combine chains get claimed first. Hysteresis on the
        SET of genuinely hot experts (EWMA > factor × mean) — a
        re-prioritize rebuilds claim tables and re-jits the step, so
        neither near-tied ranking churn under uniform load nor an
        unchanged hot set may trigger it. An emptied hot set restores
        the uniform claim order once."""
        eng = self.engine
        if (getattr(eng, "schedule", None) != "dynamic"
                or not hasattr(eng, "set_expert_load")):
            return
        hot = frozenset(
            int(e) for e in
            np.nonzero(ewma > self.hot_expert_factor * ewma.mean())[0])
        if hot == self._mk_load_sig or (not hot
                                        and self._mk_load_sig is None):
            return
        eng.set_expert_load(ewma.tolist() if hot else None)
        self._mk_load_sig = hot or None

    # -- per-request token handling ---------------------------------

    def _pick(self, logits_row, req: Request, step: int) -> int:
        """One slot's token from its logits row. Greedy: the row's
        arg-max, which a :class:`_Row` brings with it from its step
        program (the same float32 row, the same first-index rule), so
        no float leaves the device for it."""
        if req.temperature <= 0.0:
            if isinstance(logits_row, _Row):
                return logits_row.token
            return int(np.argmax(logits_row))
        import jax
        import jax.numpy as jnp

        lg = jnp.asarray(np.asarray(logits_row), jnp.float32) \
            / req.temperature
        if req.top_k > 0:
            kth = jax.lax.top_k(lg, req.top_k)[0][-1]
            lg = jnp.where(lg < kth, -jnp.inf, lg)
        key = jax.random.fold_in(jax.random.PRNGKey(req.seed), step)
        return int(jax.random.categorical(key, lg))

    def _emit(self, h: RequestHandle, tok: int):
        h.tokens.append(int(tok))
        self.stats_counters["tokens_generated"] += 1
        if self.slo is not None:
            self.slo.on_token(h)
        if self.obs.enabled:
            # TTFT / inter-token latency edges, on the engine clock.
            # Host-side stamping only — one clock read per token.
            now = self.obs.now()
            if h.first_token_at is None:
                h.first_token_at = now
                self.obs.observe("ttft", now - h.submitted_at,
                                 h.request.tenant)
                self.obs.event("first_token",
                               request_id=h.request.request_id,
                               slot=h.slot, tenant=h.request.tenant)
            elif h.last_token_at is not None:
                self.obs.observe("itl", now - h.last_token_at,
                                 h.request.tenant)
            h.last_token_at = now
        if h.request.stream_cb is not None:
            h.request.stream_cb(int(tok), h)
        hit_eos = (h.request.eos_id is not None
                   and tok == h.request.eos_id)
        if hit_eos or len(h.tokens) >= h.request.max_new_tokens:
            self._retire(h, "done")

    def _preempt(self, h: RequestHandle, error: OutOfPagesError):
        """Evict a starving request mid-decode: free its pages, park
        its slot, requeue it at the HEAD for a resume re-prefill. If it
        was the only slot-holder, nothing can ever free pages for it —
        fail it instead of spinning."""
        slot = h.slot
        self.sched.slots.pop(slot, None)
        h.slot = None
        self._vacate(slot)
        if not self.sched.slots:
            h.slot = slot            # _fail/retire bookkeeping no-op path
            self._fail(h, "failed", error)
            return
        h.status = "queued"
        h.queued_at = self.sched.now()
        self.sched.queue.appendleft(h)
        self.stats_counters["preemptions"] += 1
        self.obs.event("preempt", request_id=h.request.request_id,
                       slot=slot, tenant=h.request.tenant)

    def _retire(self, h: RequestHandle, status: str, error=None):
        slot = h.slot
        # A request released by count (``_release_ended``) handed its
        # slot and pages on already; they may be another's by now.
        holds = slot is not None and self.sched.slots.get(slot) is h
        if getattr(h, "resume_key", None) is not None \
                and self.tiers is not None:
            # A mid-resume failure (deadline, timeout victim) must not
            # leak its pinned session payload in the tier.
            self.tiers.pop(h.resume_key, None)
            h.resume_key = None
        self._close_resume_span(h, path=status)
        self.sched.retire(h, status, error)
        if holds:
            self._vacate(slot)
        # The whole-request span closes at the terminal transition —
        # submit -> done|failed|timeout, with the generated volume.
        self.obs.complete_span(
            "request", h.submitted_at, h.finished_at,
            request_id=h.request.request_id, slot=slot,
            tenant=h.request.tenant, status=status,
            tokens=len(h.tokens), decode_steps=h.decode_steps)
        if self.slo is not None:
            self.slo.on_retire(self, h)

    def _vacate(self, slot: int):
        """Clear ``slot``'s host mirrors and free its pages."""
        self._live[slot] = self._lens[slot] = self._toks[slot] = 0
        if self.manager is not None:
            self.manager.free_slot(slot)

    def _fail(self, h: RequestHandle, status: str, error):
        self._retire(h, status, error)
