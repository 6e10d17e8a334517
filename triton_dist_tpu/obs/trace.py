"""One trace session = one output directory, every telemetry tier.

:class:`TraceSession` is what :meth:`ServingEngine.trace` yields: it
owns the session directory, runs the xprof capture inside it (when
available — a failed profiler start records a skip reason instead of
killing the serve), collects megakernel slot records per decode step
while active, and exports ONE merged Perfetto file plus a
``metrics.json`` snapshot on demand. The capture needs no merging: it
holds the host spans itself, as ``tdt.<kind>`` annotations beside the
device's operations (:mod:`~triton_dist_tpu.obs.telemetry`).

``os.fspath(session)`` / ``str(session)`` return the session directory
— pre-existing callers that treated the old ``trace()`` yield as a
path string keep working.
"""

from __future__ import annotations

import json
import os
from typing import List, Optional, Tuple

__all__ = ["TraceSession"]


def _mk_tag_names() -> dict:
    try:
        from triton_dist_tpu.megakernel.task import TaskType

        # Slot records store task_type + 1 (0 is the unused-slot
        # sentinel) — the same mapping the standalone exporter uses.
        return {int(t) + 1: t.name for t in TaskType}
    except Exception:  # pragma: no cover — megakernel optional
        return {}


class TraceSession:
    """See module docstring. Built by ``ServingEngine.trace()``.

    ``xprof``: ``"auto"`` starts a ``jax.profiler.trace`` capture and
    degrades to a recorded reason on failure; ``True`` propagates the
    failure; ``False`` skips the capture (reason recorded).
    ``mk_keep`` bounds how many decode steps' megakernel slot records
    the session retains (newest win).
    """

    def __init__(self, path: str, telemetry, *, xprof="auto",
                 mk_keep: int = 4, create_perfetto_link: bool = False):
        self.path = path
        self.telemetry = telemetry
        self.xprof = xprof
        self.mk_keep = mk_keep
        self.create_perfetto_link = create_perfetto_link
        self.xprof_reason: Optional[str] = None
        self._xprof_cm = None
        self._mk_records: List[Tuple[int, object]] = []
        self.merged_path: Optional[str] = None

    # -- path compatibility ------------------------------------------

    def __fspath__(self) -> str:
        return self.path

    def __str__(self) -> str:
        return self.path

    # -- lifecycle ----------------------------------------------------

    def __enter__(self) -> "TraceSession":
        os.makedirs(self.path, exist_ok=True)
        if self.xprof is False:
            self.xprof_reason = "xprof disabled by caller (xprof=False)"
            return self
        try:
            # The shared capture entry point: one xprof session in this
            # directory, with the Perfetto-ready artifact materialized
            # alongside the raw capture (on jax versions that can).
            from triton_dist_tpu.profiler_utils import group_profile

            self._xprof_cm = group_profile(
                os.path.basename(self.path),
                log_dir=os.path.dirname(self.path) or ".",
                create_perfetto_link=self.create_perfetto_link,
                create_perfetto_trace=True)
            self._xprof_cm.__enter__()
        except Exception as e:  # noqa: BLE001 — degrade, don't kill
            self._xprof_cm = None
            if self.xprof is True:
                raise
            self.xprof_reason = f"xprof capture unavailable: {e!r}"
        return self

    def __exit__(self, *exc) -> bool:
        if self._xprof_cm is not None:
            try:
                self._xprof_cm.__exit__(*exc)
            except Exception as e:  # noqa: BLE001 — capture teardown
                self.xprof_reason = f"xprof capture failed on stop: {e!r}"
            self._xprof_cm = None
        return False

    # -- collection ----------------------------------------------------

    def add_slot_record(self, step: int, tracks) -> None:
        """Retain one decode step's megakernel slot tracks
        ((num_cores, qlen, 2) — ``ModelBuilder.prof_tracks``); newest
        ``mk_keep`` steps win."""
        self._mk_records.append((int(step), tracks))
        if len(self._mk_records) > self.mk_keep:
            self._mk_records.pop(0)

    # -- export ---------------------------------------------------------

    def export(self, path: Optional[str] = None) -> str:
        """Write the merged Perfetto trace (host spans + megakernel
        slot records). Returns the file path."""
        from triton_dist_tpu.profiler.viewer import export_merged_trace

        path = path or os.path.join(self.path, "merged_trace.json")
        tel = self.telemetry
        meta = {"telemetry_mode": getattr(tel, "mode", None)}
        if tel is not None and tel.spans_on and tel.log.dropped:
            meta["host_spans_dropped"] = tel.log.dropped
        self.merged_path = export_merged_trace(
            path,
            host_spans=(tel.log.spans() if tel is not None
                        and tel.spans_on else ()),
            slot_records=list(self._mk_records),
            tag_names=_mk_tag_names(),
            metadata=meta)
        return self.merged_path

    def export_metrics(self, stats: dict,
                       path: Optional[str] = None) -> str:
        """Write ``metrics.json``: the engine ``stats()`` dict (which
        already embeds the latency-histogram summaries) plus the
        session's trace bookkeeping."""
        path = path or os.path.join(self.path, "metrics.json")
        payload = {"stats": stats,
                   "trace": {"dir": self.path,
                             "merged": self.merged_path,
                             "xprof_reason": self.xprof_reason}}
        with open(path, "w") as f:
            json.dump(payload, f, indent=1, sort_keys=True, default=str)
        return path
