"""Export profiler slot buffers to a Perfetto-loadable chrome trace.

Reference: ``tools/profiler/viewer.py:115`` ``export_to_perfetto_trace``
(track reconstruction :54-113). Slots carry (tag, value) in program
order; without an in-kernel clock the exporter synthesizes unit-spaced
instant events per device track — enough to inspect schedules and
progress interleaving (real timing lives in the xprof capture).

:func:`export_merged_trace` is the serving-telemetry superset: host
request spans (:mod:`triton_dist_tpu.obs`) and megakernel slot records
merge into ONE trace file — one Perfetto process per component,
correlated by request id and step index carried in every event's
``args``. Device time is not merged in: the profiler's own capture
holds the host spans beside the device's operations, on one clock.
"""

from __future__ import annotations

import json
from typing import Dict, Optional, Sequence

import numpy as np

# Merged-trace process ids: one Perfetto "process" per component.
HOST_PID = 1        # host serving spans (engine clock)
MEGAKERNEL_PID = 2  # in-kernel slot records (program order / cost model)


def _slot_events(buffers, tag_names, durs, *, pid: int,
                 device_names=None, tid_base: int = 0,
                 t_off: float = 0.0, step: Optional[int] = None,
                 timing: str = "reconstructed"):
    """Shared track reconstruction for one (n_devices, capacity, 2)
    slot buffer: unit-spaced instants (program order), or spans at the
    cost model's cumulative times when ``durs`` is given."""
    events = []
    for dev, buf in enumerate(buffers):
        name = (device_names[dev] if device_names else f"device{dev}")
        t_cum = 0.0
        for t, (tag, value) in enumerate(buf):
            if tag == 0 and value == 0 and t > 0:
                continue  # unused slot
            args = {"value": int(value), "device": name,
                    "timing": timing}
            if step is not None:
                args["step"] = int(step)
            ev = {
                "name": tag_names.get(int(tag), f"tag{int(tag)}"),
                "pid": pid,
                "tid": tid_base + dev,
                "args": args,
            }
            if durs is not None:
                d_us = float(durs[dev, t]) * 1e6
                ev.update({"ph": "X", "ts": t_off + t_cum, "dur": d_us})
                t_cum += d_us
            else:
                ev.update({"ph": "i", "ts": t_off + t, "s": "t"})
            events.append(ev)
    return events


def export_to_perfetto_trace(slot_buffers, path: str,
                             tag_names: Optional[Dict[int, str]] = None,
                             device_names: Optional[Sequence[str]] = None,
                             slot_durations=None) -> str:
    """slot_buffers: (n_devices, capacity, 2) int32 array (or a list of
    per-device (capacity, 2) arrays). Writes chrome-trace JSON.

    TIMING HONESTY: every event is labeled with how its time was
    obtained. Without ``slot_durations`` (default) events are
    unit-spaced instants in PROGRAM ORDER — ``timing:
    "reconstructed"``, no duration claim (wall time lives in xprof).
    With ``slot_durations`` ((n_devices, capacity) seconds per slot —
    e.g. ``ModelBuilder.slot_durations`` fed by a MEASURED
    ``calibrate_cost_table``) events become spans at the model's
    cumulative times — ``timing: "calibrated"``, good to the cost
    model's least-squares fit, not a per-span measurement.
    """
    buffers = np.asarray(slot_buffers)
    if buffers.ndim == 2:
        buffers = buffers[None]
    durs = None
    if slot_durations is not None:
        durs = np.asarray(slot_durations, np.float64)
        if durs.ndim == 1:
            durs = durs[None]
    tag_names = tag_names or {}
    timing = "calibrated" if durs is not None else "reconstructed"
    events = [{
        "name": f"timing_model: {timing}",
        "ph": "M", "pid": 0, "tid": 0,
        "args": {"timing": timing},
    }]
    events += _slot_events(buffers, tag_names, durs, pid=0,
                           device_names=device_names, timing=timing)
    trace = {"traceEvents": events,
             "displayTimeUnit": "ns"}
    with open(path, "w") as f:
        json.dump(trace, f)
    return path


def _meta(pid: int, name: str, threads: Dict[int, str]):
    evs = [{"name": "process_name", "ph": "M", "pid": pid, "tid": 0,
            "args": {"name": name}}]
    for tid, tname in sorted(threads.items()):
        evs.append({"name": "thread_name", "ph": "M", "pid": pid,
                    "tid": tid, "args": {"name": tname}})
    return evs


def _host_events(host_spans):
    """Host spans/events (``obs.Span`` objects or their dicts) → one
    Perfetto process: tid = slot + 1 for slot-correlated entries,
    tid 0 ("engine") otherwise; times are µs relative to the first
    span's clock stamp."""
    from triton_dist_tpu.obs.spans import Span

    spans = [s if isinstance(s, Span) else Span.from_dict(s)
             for s in host_spans]
    if not spans:
        return [], {}
    base = min(s.t0 for s in spans)
    threads = {0: "engine"}
    events = []
    for s in spans:
        tid = 0 if s.slot is None else s.slot + 1
        if s.slot is not None:
            threads.setdefault(tid, f"slot{s.slot}")
        args = {"kind": s.kind, "timing": "host_clock"}
        for k in ("request_id", "slot", "step", "tenant"):
            v = getattr(s, k)
            if v is not None:
                args[k] = v
        args.update(s.attrs)
        ev = {"name": s.kind, "pid": HOST_PID, "tid": tid,
              "ts": (s.t0 - base) * 1e6, "args": args}
        if s.instant:
            ev.update({"ph": "i", "s": "t"})
        else:
            ev.update({"ph": "X",
                       "dur": max((s.t1 - s.t0) * 1e6, 1e-3)})
        events.append(ev)
    return events, threads


def export_merged_trace(path: str, *, host_spans=(),
                        slot_records=(),
                        tag_names: Optional[Dict[int, str]] = None,
                        slot_durations=None,
                        metadata: Optional[dict] = None) -> str:
    """Write ONE chrome-trace JSON merging every telemetry tier.

    - ``host_spans``: :class:`~triton_dist_tpu.obs.spans.Span` records
      (or their dicts) — pid 1, one thread per serving slot plus the
      engine thread; timestamps on the engine clock.
    - ``slot_records``: megakernel slot buffers — either one
      (n_cores, capacity, 2) array or a sequence of ``(step_index,
      buffers)`` pairs (one decode step each) — pid 2, one thread per
      core; program-order instants (or cost-model spans when
      ``slot_durations`` is given), each step offset on the synthetic
      axis and stamped with its ``step`` for correlation against the
      host decode spans.

    The two clock domains are NOT aligned (no shared epoch exists
    across host monotonic / program order); correlation is by the
    ``request_id`` / ``step`` keys in ``args``, which is what the
    serving debug loop joins on.
    """
    events = []
    host_evs, host_threads = _host_events(host_spans)
    events += _meta(HOST_PID, "host:serving", host_threads)
    events += host_evs

    tag_names = tag_names or {}
    recs = slot_records
    if recs is not None and not isinstance(recs, (list, tuple)):
        recs = [(0, recs)]
    mk_threads = {}
    if recs:
        durs = None
        if slot_durations is not None:
            durs = np.asarray(slot_durations, np.float64)
            if durs.ndim == 1:
                durs = durs[None]
        t_off = 0.0
        for step_idx, buffers in recs:
            buffers = np.asarray(buffers)
            if buffers.ndim == 2:
                buffers = buffers[None]
            for c in range(buffers.shape[0]):
                mk_threads.setdefault(c, f"core{c}")
            events += _slot_events(
                buffers, tag_names, durs, pid=MEGAKERNEL_PID,
                t_off=t_off, step=step_idx,
                timing=("calibrated" if durs is not None
                        else "reconstructed"))
            # Steps share the core tracks; each gets its own stretch of
            # the synthetic axis (no in-kernel clock to place it by).
            t_off += (float(durs.sum() * 1e6) if durs is not None
                      else buffers.shape[1] + 8)
        events += _meta(MEGAKERNEL_PID, "megakernel", mk_threads)

    meta = {"clock_domains": {
        "host:serving": "engine clock (injectable monotonic)",
        "megakernel": "program order / calibrated cost model",
    }}
    if metadata:
        meta.update(metadata)
    trace = {"traceEvents": events, "displayTimeUnit": "ms",
             "metadata": meta}
    with open(path, "w") as f:
        json.dump(trace, f)
    return path
