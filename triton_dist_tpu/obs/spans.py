"""Typed spans and the bounded event ring.

The serving stack's per-request timeline is a sequence of SPANS —
(kind, t0, t1) intervals stamped by the engine's injectable monotonic
clock — and instant EVENTS (t1 is None). Everything is host-side data:
spans are never traced into a jit, so recording them cannot grow any
dispatch cache (the serving no-recompilation gates hold with spans
active).

The span taxonomy (``SPAN_KINDS``) names every stage a request can
pass through plus the resilience events that can interleave with it;
see docs/observability.md for the full table. Kinds outside the
taxonomy are allowed (callers may invent attrs-only kinds), but the
serving engine itself emits only these.

:class:`EventLog` is a bounded ring (drop-oldest) so a long-running
server's telemetry cost is O(capacity), with JSONL import/export for
offline inspection and the Perfetto merge
(:func:`~triton_dist_tpu.profiler.viewer.export_merged_trace`).
"""

from __future__ import annotations

import dataclasses
import json
from collections import deque
from typing import Any, Dict, List, Optional

__all__ = ["SPAN_KINDS", "DEVICE_SCOPES", "SCOPE_PREFIX", "scope", "Span",
           "EventLog"]

# The serving span/event taxonomy (docs/observability.md). Interval
# spans carry t0 < t1 on the engine clock; instant events have t1 None.
SPAN_KINDS = (
    # the serving tick, tiled: ``tick`` is the root, the rest leaves
    # (``decode`` below is the parent of the three ``decode_*`` after
    # ``decode_prep``); every span inside a tick carries its index
    "tick",              # span: one ServingEngine.step()
    "schedule",          # span: deadlines, SLO pump, admission, requeue
    "decode_prep",       # span: token gather, page growth, block table
    "decode_enqueue",    # span: uploads, the jitted call, copy request
    "decode_wait",       # span: host blocked until the step's picks exist
    "decode_fetch",      # span: picked tokens copied to the host (the
                         # logits too where a row of the batch samples)
    "prefill_fetch",     # span: wait for + copy of the last chunk's
                         # token (its logits row for a sampled request)
    "sample",            # span: one slot's token (attrs: device = 1 the
                         # program's own pick, 0 _pick on the host's row)
    "emit",              # span: one slot's _emit (callbacks, retire)
    # request lifecycle
    "submit",            # span: submit()'s own work (validate, enqueue)
    "queue_wait",        # span: submit -> slot admission
    "admit",             # event: slot assigned (attrs: waited_ms)
    "prefill",           # span: monolithic prefill dispatch + blit
    "prefill_chunk",     # span: one bucketed chunk dispatch (1 attempt)
    "migration",         # span: one KV page-migration attempt (disagg)
    "decode",            # span: one joint decode dispatch
    "spec_draft",        # span: host-side draft proposal (all slots)
    "spec_verify",       # span: one K-token verification dispatch
    "spec_rollback",     # event: rejected suffix rolled back
    "first_token",       # event: TTFT edge (request's first emission)
    "request",           # span: submit -> terminal status
    # KV memory hierarchy (docs/serving.md, "KV memory hierarchy")
    "kv_offload",        # span: page payload demoted into the tier
    "kv_prefetch",       # span: tier payload scattered back into HBM
    "park",              # span: session offloaded + slot released
    "resume",            # span: resume() -> token-exact reactivation
    # fleet serving (docs/serving.md, "Fleet serving")
    "route",             # span: routing decision -> fleet admission
    "fleet_failover",    # span: dead fleet's work rehomed on survivors
    "drain",             # span: fleet drained (park/finish in-flight)
    "restore_fleet",     # span: fleet state restored on new topology
    "shed",              # event: request shed by deadline class
    # resilience
    "retry",             # event: one absorbed transient (attempt n)
    "retry_backoff",     # event: backoff sleep scheduled (policy)
    "retry_giveup",      # event: retries exhausted (policy)
    "preempt",           # event: pool-dry eviction, requeued at head
    "failover",          # event: prefill role moved, handles requeued
    "role_fail",         # event: one post-retry role failure recorded
    "role_dead",         # event: health tracker declared a role dead
    "timeout",           # event: a watchdog deadline fired
    "checkpoint",        # span: full serving-state snapshot
    "restore",           # span: snapshot adopted into a fresh engine
    "chaos_fault",       # event: the chaos soak injected a fault
    "chaos_restore",     # event: the soak's mid-run kill/restore drill
)

# The blocks of a step program (docs/observability.md, "In a profiler
# capture"): what runs on the DEVICE carries ``tdt.<block>`` in its
# operations' ``op_name``, as what runs on the host carries
# ``tdt.<kind>`` above. One vocabulary for every model family, so that
# one reader and one set of metric names serve them all.
SCOPE_PREFIX = "tdt."
DEVICE_SCOPES = (
    "embed",             # the token rows gathered from the table
    "attn_project",      # norm, q/k/v or the latent's projections, rope
    "cache_write",       # the rows' entries written into the paged pool,
                         # and a sequence's state where it keeps one
    "attn_chunk",        # what a prefill chunk's queries read
    "attn_decode",       # what decode and verification rows read
    "attn_chunk_window", # what a chunk's queries read in a layer that
                         # keeps a window of positions (the other two
                         # then hold the layers that keep all)
    "attn_decode_window",  # what decode rows read in such a layer
    "attn_out",          # the output projection and its residual
    "ssm_project",       # a state-space layer's norm and in-projection
    "ssm",               # between its two projections: convolution,
                         # step sizes, the scan or the step, gated norm
    "ssm_out",           # its output projection and residual
    "mlp",               # norm + dense FFN
    "router",            # the expert layer's norm, scores and top-k
    "experts",           # sort by expert, grouped SwiGLU, the combine
    "expert_latent",     # into and out of the latent the experts work in
    "shared_expert",     # the expert every token goes through
    "head",              # final norm, the rows kept, the vocabulary
    "pick",              # the greedy token (and the step's counts)
    "pass_norm",         # the final norm between two passes of a stack
                         # applied several times (models/looped.py)
    "exit_gate",         # its exit gate's scores and the pick among passes
)


def scope(block: str):
    """``jax.named_scope("tdt.<block>")`` for a block of
    ``DEVICE_SCOPES``: a context manager, or a decorator over a function
    that is one block. Names on the traced operations and nothing else:
    no operand, no branch, no work at run time, so nothing to switch."""
    if block not in DEVICE_SCOPES:
        raise ValueError(f"device scope {block!r} is not one of "
                         f"DEVICE_SCOPES {DEVICE_SCOPES}")
    import jax

    return jax.named_scope(SCOPE_PREFIX + block)


@dataclasses.dataclass
class Span:
    """One timeline entry. ``t1 is None`` marks an instant event.

    ``request_id`` / ``slot`` / ``step`` are the correlation keys the
    Perfetto merge threads across components (host track <-> megakernel
    step <-> xprof span); ``tenant`` is the histogram grouping key;
    everything else rides in ``attrs``.
    """

    kind: str
    t0: float
    t1: Optional[float] = None
    request_id: Optional[str] = None
    slot: Optional[int] = None
    step: Optional[int] = None
    tenant: Optional[str] = None
    attrs: Dict[str, Any] = dataclasses.field(default_factory=dict)

    @property
    def instant(self) -> bool:
        return self.t1 is None

    @property
    def duration(self) -> float:
        return 0.0 if self.t1 is None else self.t1 - self.t0

    def to_dict(self) -> dict:
        d = {"kind": self.kind, "t0": self.t0}
        for k in ("t1", "request_id", "slot", "step", "tenant"):
            v = getattr(self, k)
            if v is not None:
                d[k] = v
        if self.attrs:
            d["attrs"] = self.attrs
        return d

    @classmethod
    def from_dict(cls, d: dict) -> "Span":
        return cls(kind=d["kind"], t0=d["t0"], t1=d.get("t1"),
                   request_id=d.get("request_id"), slot=d.get("slot"),
                   step=d.get("step"), tenant=d.get("tenant"),
                   attrs=dict(d.get("attrs", {})))


class EventLog:
    """Bounded drop-oldest ring of :class:`Span` records.

    ``capacity`` bounds memory for arbitrarily long runs; ``dropped``
    counts evictions so an exported timeline is honest about what it no
    longer holds. Appends are O(1) host work — the serving loop calls
    this on its hot path only in ``telemetry="spans"`` mode.
    """

    def __init__(self, capacity: int = 4096):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = capacity
        self._ring: deque = deque(maxlen=capacity)
        self.total = 0

    def append(self, span: Span) -> None:
        self._ring.append(span)
        self.total += 1

    def spans(self) -> List[Span]:
        """Oldest-first snapshot of the retained window."""
        return list(self._ring)

    @property
    def dropped(self) -> int:
        return self.total - len(self._ring)

    def __len__(self) -> int:
        return len(self._ring)

    def clear(self) -> None:
        self._ring.clear()
        self.total = 0

    # -- JSONL round-trip --------------------------------------------

    def to_jsonl(self, path: str) -> str:
        """One span per line, oldest first. Returns ``path``."""
        with open(path, "w") as f:
            for s in self._ring:
                f.write(json.dumps(s.to_dict(), sort_keys=True) + "\n")
        return path

    @classmethod
    def from_jsonl(cls, path: str, capacity: Optional[int] = None
                   ) -> "EventLog":
        """Rebuild a log from :meth:`to_jsonl` output (``capacity``
        defaults to at least the line count, so nothing re-drops)."""
        spans = []
        with open(path) as f:
            for line in f:
                line = line.strip()
                if line:
                    spans.append(Span.from_dict(json.loads(line)))
        log = cls(capacity or max(len(spans), 1))
        for s in spans:
            log.append(s)
        return log
