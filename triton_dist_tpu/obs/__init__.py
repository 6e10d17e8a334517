"""Serving observability: span timelines, latency histograms, spans in
the profiler's capture, merged Perfetto traces.

The telemetry substrate ROADMAP item 5c's "at production traffic you
debug with traces, not reruns" calls for (see docs/observability.md):

- :mod:`~triton_dist_tpu.obs.spans` — the typed span taxonomy and the
  bounded :class:`EventLog` ring with JSONL round-trip; beside it the
  device's half of the vocabulary, ``DEVICE_SCOPES``, and
  :func:`scope`, the ``tdt.<block>`` name a step program's operations
  carry into the same capture;
- :mod:`~triton_dist_tpu.obs.hist` — fixed log-spaced-bucket latency
  histograms (TTFT / inter-token / per-op) with percentile summaries
  and per-tenant grouping;
- :mod:`~triton_dist_tpu.obs.telemetry` — the per-engine facade behind
  ``ServingEngine(telemetry="off"|"counters"|"spans")``; every span and
  event it records is also a ``jax.profiler.TraceAnnotation`` named
  ``tdt.<kind>``, so a profiler capture holds the host spans beside the
  device's operations, on one clock;
- :mod:`~triton_dist_tpu.obs.trace` — the one-directory trace session
  ``ServingEngine.trace()`` yields (the profiler's capture, and host
  spans + megakernel slot records -> one merged Perfetto file).

Everything here is host-side bookkeeping on the engine's injectable
clock: recording never touches a jitted dispatch, so the serving
no-recompilation gates hold with full span recording active. A device
scope is a name on the operations a step program is traced into, and
nothing at run time.
"""

from triton_dist_tpu.obs.spans import (  # noqa: F401
    DEVICE_SCOPES,
    SPAN_KINDS,
    EventLog,
    Span,
    scope,
)
from triton_dist_tpu.obs.hist import (  # noqa: F401
    HistogramSet,
    LatencyHistogram,
)
from triton_dist_tpu.obs.telemetry import (  # noqa: F401
    TELEMETRY_MODES,
    Telemetry,
)
from triton_dist_tpu.obs.trace import TraceSession  # noqa: F401
