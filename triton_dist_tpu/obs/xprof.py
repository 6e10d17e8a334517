"""Extract device spans from an xprof capture directory.

``jax.profiler.trace(log_dir)`` writes, per host, a TensorBoard
trace-viewer JSON (``plugins/profile/<run>/<host>.trace.json.gz``)
containing every XLA/device event of the capture. This module mines
that file for the spans the serving timeline wants to correlate:

- **marker-keyed spans** — events whose name carries a
  :func:`~triton_dist_tpu.profiler.trace_scalar` label
  (``pltpu.trace_value`` markers; the documented alternative to
  an in-kernel clock). On jax 0.4.x the marker label
  appears verbatim in the event name, so a substring match keys them.
- optionally the longest raw XLA op spans (``top_ops``) — useful
  context when no markers were compiled in (e.g. a CPU interpret run,
  where Mosaic never executes and ``trace_value`` lowers to nothing).

Extraction is best-effort by design: a missing capture, an old jax, or
a markerless build returns ``([], reason)`` — callers surface the
reason (skip-with-reason) instead of failing the trace export.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
from typing import List, Optional, Sequence, Tuple

__all__ = ["extract_xprof_spans"]

# Default marker substrings: trace_scalar labels conventionally start
# with "tdt." in this package; "trace_value" catches unlabeled lowering
# artifacts.
DEFAULT_MARKERS = ("tdt.", "trace_value")


def _trace_files(session_dir: str) -> List[str]:
    pats = (os.path.join(session_dir, "plugins", "profile", "*",
                         "*.trace.json.gz"),
            os.path.join(session_dir, "*.trace.json.gz"))
    out: List[str] = []
    for p in pats:
        out.extend(sorted(glob.glob(p)))
    return out


def extract_xprof_spans(session_dir: str, *,
                        markers: Optional[Sequence[str]] = None,
                        top_ops: int = 0,
                        ) -> Tuple[List[dict], Optional[str]]:
    """Return ``(events, reason)`` from the newest capture under
    ``session_dir``.

    ``events`` are chrome-trace dicts (``ph`` "X"/"i", ``ts``/``dur``
    in µs on the capture's own clock, original ``pid``/``tid``)
    whose names match any ``markers`` substring (default
    ``DEFAULT_MARKERS``), plus — when ``top_ops`` > 0 — the that-many
    longest complete ("X") spans regardless of name. ``reason`` is
    None on success and a human-readable skip reason when nothing
    could be extracted (no capture, unreadable file, no matches).
    """
    markers = tuple(markers) if markers is not None else DEFAULT_MARKERS
    files = _trace_files(session_dir)
    if not files:
        return [], (f"no xprof capture under {session_dir!r} "
                    "(jax.profiler.trace never ran, or an old jax "
                    "wrote no trace.json.gz)")
    path = files[-1]
    try:
        with gzip.open(path, "rt") as f:
            trace = json.load(f)
    except (OSError, ValueError) as e:
        return [], f"unreadable xprof trace {path!r}: {e!r}"
    events = trace.get("traceEvents", [])
    names = {}
    marked: List[dict] = []
    timed: List[dict] = []
    for ev in events:
        ph = ev.get("ph")
        if ph == "M":
            if ev.get("name") == "thread_name":
                names[(ev.get("pid"), ev.get("tid"))] = (
                    ev.get("args", {}).get("name"))
            continue
        name = ev.get("name") or ""
        if ph in ("X", "i"):
            if any(m in name for m in markers):
                marked.append(ev)
            elif ph == "X" and ev.get("dur"):
                timed.append(ev)
    picked = list(marked)
    if top_ops > 0:
        timed.sort(key=lambda e: -float(e.get("dur", 0.0)))
        picked.extend(timed[:top_ops])
    if not picked:
        return [], (f"xprof capture {os.path.basename(path)!r} holds "
                    f"{len(events)} events but none match markers "
                    f"{list(markers)} (markers lower to nothing off-"
                    "TPU; pass top_ops= to keep the longest raw ops)")
    out = []
    for ev in picked:
        e = {k: ev[k] for k in ("name", "ph", "ts", "dur", "pid",
                                "tid", "args") if k in ev}
        thread = names.get((ev.get("pid"), ev.get("tid")))
        if thread:
            e.setdefault("args", {})
            e["args"] = dict(e["args"], xprof_thread=thread)
        out.append(e)
    return out, None
