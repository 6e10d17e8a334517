"""The program's own spans in the profiler's capture. Every span and
event that goes through the program's ``Telemetry`` is also a
``jax.profiler.TraceAnnotation`` named ``tdt.<kind>``, so a traced run
holds them in the host planes of its ``.xplane.pb``, on the clock of the
device's ``XLA Ops``, with their correlation keys (``tick``, ``slot``,
``request_id``, ``step``, ``batch``, ...) as stats.

``trace_reduce.read_xplane`` keeps only the harness's ``bench.*`` host
rows, so this module makes its own pass over the capture, which still
lies under ``<repo>/.bench_trace`` while the reducers run. The
arithmetic below works on plain rows (a ``trace_reduce`` row plus
``"stats": {...}``), so the tests check it on recorded JSON lines: rows
that already hold ``tdt.*`` events are used as they are.

A program from before these annotations leaves none in the capture: the
readers then return None and the metric is left out. A capture that
holds some ``tdt.*`` spans but not the one a metric names is an error.
"""

from __future__ import annotations

import functools
import os
import re

from .. import loader, trace_reduce as T

SPAN_PREFIX = "tdt."
TRACE_DIR = os.path.join(loader.REPO_ROOT, ".bench_trace")
TICK = SPAN_PREFIX + "tick"
# Spans with spans of their own inside: what they cover counts for their
# children, and the rest of them is the residue no leaf covers.
PARENTS = (TICK, SPAN_PREFIX + "decode")
# A tick that holds one of these handed the device a program.
DISPATCHES = tuple(SPAN_PREFIX + k for k in (
    "decode", "prefill_chunk", "prefill", "spec_verify"))


@functools.lru_cache(maxsize=1)
def read_spans(path: str) -> tuple:
    """The ``tdt.*`` events of the capture's host planes, as rows with
    their stats. One pass over millions of Python frames: cached, so the
    metrics of one run share it."""
    from jax.profiler import ProfileData

    dev = re.compile(T.DEVICE_PLANE)
    rows = []
    for plane in ProfileData.from_file(path).planes:
        if dev.match(plane.name):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIX):
                    rows.append({"plane": plane.name, "line": line.name,
                                 "name": ev.name,
                                 "start_ns": float(ev.start_ns),
                                 "dur_ns": float(ev.duration_ns),
                                 "stats": dict(ev.stats)})
    return tuple(rows)


def spans_of(ctx) -> list:
    """The run's ``tdt.*`` rows: those among ``ctx.rows`` (recorded
    rows), else the capture's. Empty where the program emits none."""
    rows = [r for r in ctx.rows if r["name"].startswith(SPAN_PREFIX)]
    if rows or not os.path.isdir(TRACE_DIR):
        return rows
    return list(read_spans(T.find_xplane(TRACE_DIR)))


def named(spans, kinds, what) -> dict:
    """{kind: its rows} for ``kinds`` (one or a list, without the
    prefix); a kind with no row in a capture that holds other ``tdt.*``
    spans is an error: a renamed span must fail the traced run, as a
    renamed program does."""
    kinds = [kinds] if isinstance(kinds, str) else list(kinds)
    out = {k: [s for s in spans if s["name"] == SPAN_PREFIX + k]
           for k in kinds}
    missing = [k for k, v in out.items() if not v]
    if missing:
        raise T.TraceError(
            f"{what}: no {SPAN_PREFIX}{missing[0]} span in the capture; "
            f"it holds {sorted({s['name'] for s in spans})}")
    return out


def intervals(spans) -> list:
    return T.union((s["start_ns"], s["start_ns"] + s["dur_ns"])
                   for s in spans)


def idle_intervals(rows) -> list:
    """The gaps between the busy intervals of the first device plane:
    what ``trace_reduce.idle_gaps`` totals."""
    planes = T.device_planes(rows)
    if not planes:
        raise T.TraceError("the trace has no device plane")
    busy = T.busy_intervals(rows, planes[0])
    return [[e0, s1] for (_, e0), (s1, _) in zip(busy, busy[1:])]


def overlap_ns(a, b) -> float:
    """Nanoseconds that lie in both of two sorted lists of disjoint
    intervals: a gap is split by time among what covers it."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return total


def dispatching_ticks(spans) -> int:
    """``tdt.tick`` spans that handed the device a program."""
    gave = {s["stats"].get("tick") for s in spans
            if s["name"] in DISPATCHES}
    return sum(1 for s in spans
               if s["name"] == TICK and s["stats"].get("tick") in gave)


def attribution(gaps, spans) -> dict:
    """Where the device's idle time (``gaps``: ``idle_intervals``) went,
    in ns: all of it, the part under ``tdt.tick``, under ``tdt.submit``,
    under no ``tdt.*`` span (the caller's), and the part of a tick's
    that no leaf span covers."""
    idle = sum(e - s for s, e in gaps)
    submit = SPAN_PREFIX + "submit"

    def under(keep):
        return overlap_ns(gaps, intervals(s for s in spans
                                          if keep(s["name"])))

    in_tick = under(lambda n: n == TICK)
    return {"idle": idle,
            "in_tick": in_tick,
            "in_submit": under(lambda n: n == submit),
            "outside": idle - under(lambda n: True),
            "no_leaf": in_tick - under(
                lambda n: n not in PARENTS and n != submit)}
