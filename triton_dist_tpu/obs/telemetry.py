"""The serving telemetry facade: one object per engine.

Three modes, chosen at engine construction
(``ServingEngine(telemetry=...)``):

- ``"off"`` — every hook is a no-op (the pre-existing counters in
  ``stats()`` still work; nothing here runs on the hot path).
- ``"counters"`` — the cheap default: latency histograms (TTFT,
  inter-token latency, per-op durations) and named counters. No span
  objects are allocated; the hot-path cost is two clock reads, one
  histogram bisect and one profiler annotation per instrumented
  region.
- ``"spans"`` — everything above PLUS the full typed-span timeline in
  the bounded :class:`~triton_dist_tpu.obs.spans.EventLog` (JSONL
  export, Perfetto merge).

All stamping is host-side on the engine's injectable clock — a fake
clock makes timelines deterministic in tests, and nothing here is ever
traced into a jit, so the decode/prefill no-growth gates hold with
spans active.

In every enabled mode each span and event is ALSO a
``jax.profiler.TraceAnnotation`` named ``tdt.<kind>``: while a profiler
capture is running (started by anyone, by any means) the host spans
land in the capture's own ``.xplane.pb``, on the timebase of the
device's ``XLA Ops``, with their correlation keys as stats. Outside a
capture the annotation is TraceMe's own no-op.
"""

from __future__ import annotations

import time
from typing import Callable, Dict, Optional

from jax.profiler import TraceAnnotation

from triton_dist_tpu.obs.hist import HistogramSet
from triton_dist_tpu.obs.spans import EventLog, Span

__all__ = ["TELEMETRY_MODES", "Telemetry"]

TELEMETRY_MODES = ("off", "counters", "spans")

# Span kinds whose durations feed the per-op histogram series
# ("op:<kind>" in the latency summary).
_OP_HIST_KINDS = frozenset({
    "queue_wait", "prefill", "prefill_chunk", "migration", "decode",
    "spec_draft", "spec_verify", "checkpoint", "restore", "request",
    "kv_offload", "kv_prefetch", "park", "resume",
    "route", "fleet_failover", "drain", "restore_fleet",
    # the serving tick, tiled (docs/observability.md)
    "tick", "schedule", "decode_prep", "decode_enqueue", "decode_wait",
    "decode_fetch", "prefill_fetch", "sample", "emit", "submit",
})

# The fields an annotation carries as stats into a profiler capture:
# the correlation keys, and the counts read there (``batch`` of a
# decode and whether it rode a chunk program, ``fused`` 0/1; whether a
# chunk's rows walk their context in a Pallas kernel, ``walk_kernel``
# 0/1, whether its state-space layers scan them in one,
# ``scan_kernel`` 0/1, and whether its held experts' MLP is one,
# ``experts_kernel`` 0/1; whether it is a prompt's tail in one padded
# program of a larger bucket than the greedy step takes, ``padded_up``
# 0/1, and on ``prefill_fetch`` the programs the prompt took,
# ``chunks``; ``waited_ms`` of an admission; what an
# ``expert_load`` event counted; of a model whose layers run several
# times, ``passes`` and the pass the rows' logits were read from,
# ``exit_pass``: a decode's mean over its rows, known once its tokens
# are on the host, so set while the span is open; of a model with
# window layers, ``window_pages``: the pages a chunk's slot holds in
# such a layer, its ring; on ``tick``, ``ahead`` 0/1: whether what it
# launched stayed in flight when it closed, set as it closes).
_ANNOTATED = frozenset({"request_id", "slot", "step", "batch", "fused",
                        "bucket", "valid", "walk_kernel", "scan_kernel",
                        "experts_kernel", "padded_up", "chunks",
                        "waited_ms", "passes", "exit_pass",
                        "window_pages", "ahead",
                        # ``expert_load``: a step program's held experts
                        "rows", "held_pairs", "routed_pairs",
                        "expert_rows_max", "expert_imbalance"})


class _NullSpan:
    """Shared no-op context (``telemetry="off"`` / events disabled)."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL = _NullSpan()


class _SpanCtx:
    """One timed region: a profiler annotation around it, clock at
    enter/exit, histogram fold, and (in spans mode) an EventLog append
    — error type recorded when the region raised. A ``tick`` span makes
    its index the ambient ``Telemetry.tick`` while it is open. A field
    set while the span is open (``span.fields[...] = ...``) reaches the
    annotation as it closes."""

    __slots__ = ("tel", "kind", "fields", "ann", "t0", "entered")

    def __init__(self, tel: "Telemetry", kind: str, fields: dict):
        self.tel = tel
        self.kind = kind
        self.fields = fields

    def __enter__(self):
        tel = self.tel
        if self.kind == "tick":
            tel.tick = self.fields["tick"]
        self.ann = tel._annotation(self.kind, self.fields)
        self.entered = (None if self.ann is _NULL
                        else frozenset(self.fields))
        self.ann.__enter__()
        self.t0 = tel.clock()
        return self

    def __exit__(self, etype, exc, tb):
        tel = self.tel
        fields = self.fields
        t1 = tel.clock()
        if self.ann is not _NULL:
            late = {k: v for k, v in fields.items()
                    if k in _ANNOTATED and k not in self.entered
                    and v is not None}
            if late:
                self.ann.set_metadata(**late)
        self.ann.__exit__(etype, exc, tb)
        if etype is not None:
            fields["error"] = etype.__name__
        tel._finish_span(self.kind, self.t0, t1, fields)
        if self.kind == "tick":
            tel.tick = None
        return False


class Telemetry:
    """Per-engine telemetry sink (see module docstring).

    ``clock`` is the engine's monotonic clock (injectable);
    ``capacity`` bounds the spans-mode event ring.
    """

    def __init__(self, mode: str = "counters", *,
                 clock: Callable[[], float] = time.monotonic,
                 capacity: int = 4096, **hist_kw):
        if mode not in TELEMETRY_MODES:
            raise ValueError(
                f"telemetry must be one of {TELEMETRY_MODES}, got "
                f"{mode!r}")
        self.mode = mode
        self.clock = clock
        self.log = EventLog(capacity)
        self.hist = HistogramSet(**hist_kw)
        self.counters: Dict[str, int] = {}
        # Index of the serving tick that is open, None between ticks:
        # every span and event recorded meanwhile carries it.
        self.tick: Optional[int] = None

    # -- mode predicates ---------------------------------------------

    @property
    def enabled(self) -> bool:
        return self.mode != "off"

    @property
    def spans_on(self) -> bool:
        return self.mode == "spans"

    def now(self) -> float:
        return self.clock()

    # -- recording ----------------------------------------------------

    def span(self, kind: str, **fields):
        """Context manager timing one region. In counters mode the
        duration folds into the ``op:<kind>`` histogram; in spans mode
        a :class:`Span` is appended too. Off mode: a shared no-op."""
        if self.mode == "off":
            return _NULL
        return _SpanCtx(self, kind, fields)

    def _annotation(self, kind: str, fields: dict):
        """The span or event as the profiler sees it: ``tdt.<kind>``
        with its correlation keys as stats. Built only while a capture
        runs (TraceMe's own switch), else the shared no-op."""
        if not TraceAnnotation.is_enabled():
            return _NULL
        stats = {k: v for k, v in fields.items()
                 if k in _ANNOTATED and v is not None}
        tick = fields.get("tick", self.tick)
        if tick is not None:
            stats["tick"] = tick
        return TraceAnnotation("tdt." + kind, **stats)

    def _finish_span(self, kind: str, t0: float, t1: float,
                     fields: dict) -> None:
        tenant = fields.get("tenant")
        if kind in _OP_HIST_KINDS:
            self.hist.observe(f"op:{kind}", t1 - t0, tenant)
        if self.mode == "spans":
            self._log(kind, t0, t1, fields)

    def _log(self, kind: str, t0: float, t1: Optional[float],
             fields: dict) -> None:
        if self.tick is not None:
            fields.setdefault("tick", self.tick)
        self.log.append(Span(
            kind=kind, t0=t0, t1=t1,
            request_id=fields.pop("request_id", None),
            slot=fields.pop("slot", None),
            step=fields.pop("step", None),
            tenant=fields.pop("tenant", None),
            attrs=fields))

    def complete_span(self, kind: str, t0: float,
                      t1: Optional[float] = None, **fields) -> None:
        """Record a span whose start was stamped earlier (e.g.
        queue-wait: ``t0`` is the submit time). ``t1`` defaults to
        now. It cannot be back-dated into a profiler capture, so it is
        no annotation: the event that closes it carries its length
        (``tdt.admit``'s ``waited_ms``)."""
        if self.mode == "off":
            return
        self._finish_span(kind, t0, self.clock() if t1 is None else t1,
                          fields)

    def event(self, kind: str, **fields) -> None:
        """Instant event: a timeline entry in spans mode (events are
        not distributions), a zero-length annotation in a profiler
        capture, and a bump of the ``kind`` counter, in any enabled
        mode."""
        if self.mode == "off":
            return
        self.counters[kind] = self.counters.get(kind, 0) + 1
        with self._annotation(kind, fields):
            pass
        if self.mode == "spans":
            self._log(kind, self.clock(), None, fields)

    def observe(self, name: str, seconds: float,
                tenant: Optional[str] = None) -> None:
        """Fold one duration into the named histogram (TTFT / ITL /
        custom series)."""
        if self.mode != "off":
            self.hist.observe(name, seconds, tenant)

    def count(self, name: str, inc: int = 1) -> None:
        if self.mode != "off":
            self.counters[name] = self.counters.get(name, 0) + inc

    # -- readout ------------------------------------------------------

    def latency_summary(self) -> Optional[dict]:
        """The ``stats()["latency"]`` payload: named histogram
        summaries in ms (``ttft_ms`` / ``itl_ms`` aliased from the
        raw series names), per-op durations under ``ops``, per-tenant
        groups, counters, and the event-ring accounting. None in off
        mode."""
        if self.mode == "off":
            return None
        raw = self.hist.summary()
        out: dict = {
            "ttft_ms": raw.pop("ttft", None),
            "itl_ms": raw.pop("itl", None),
        }
        ops = {k[len("op:"):]: raw.pop(k)
               for k in sorted(raw) if k.startswith("op:")}
        if ops:
            out["ops"] = ops
        per_tenant = raw.pop("per_tenant", None)
        if per_tenant:
            out["per_tenant"] = {
                t: {("ttft_ms" if n == "ttft" else
                     "itl_ms" if n == "itl" else n): s
                    for n, s in series.items()}
                for t, series in per_tenant.items()}
        out.update(raw)          # any remaining custom series
        if self.counters:
            out["counters"] = dict(sorted(self.counters.items()))
        if self.spans_on:
            out["events"] = {"recorded": self.log.total,
                             "retained": len(self.log),
                             "dropped": self.log.dropped}
        return out
