"""From the profiler's trace to numbers. ``read_xplane`` turns an
``.xplane.pb`` into plain event rows; everything else works on rows, so
the tests check it on a small recorded trace kept as JSON lines.

A row: ``{"plane", "line", "name", "start_ns", "dur_ns"}``. Device planes
are ``/device:TPU:<n>``; their line ``XLA Ops`` holds one event per
operation that ran on the chip and ``XLA Modules`` one per compiled
program. Host planes carry the harness's own ``bench.*`` annotations.
"""

from __future__ import annotations

import bisect
import glob
import json
import os
import re
import statistics

DEVICE_PLANE = r"^/device:TPU:\d+$"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PREFIX = "bench."


class TraceError(RuntimeError):
    """The trace lacks what a metric needs. It is never caught below
    ``run.main``: the traced run exits non-zero and prints no result."""


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise TraceError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def read_xplane(path: str) -> list:
    """Rows of every device plane, and of host planes the ``bench.*``
    annotations only (the host planes hold millions of Python frames)."""
    from jax.profiler import ProfileData

    rows = []
    dev = re.compile(DEVICE_PLANE)
    for plane in ProfileData.from_file(path).planes:
        on_device = bool(dev.match(plane.name))
        for line in plane.lines:
            for ev in line.events:
                if on_device or ev.name.startswith(HOST_PREFIX):
                    rows.append({"plane": plane.name, "line": line.name,
                                 "name": ev.name,
                                 "start_ns": float(ev.start_ns),
                                 "dur_ns": float(ev.duration_ns)})
    return rows


def load_rows(path: str) -> list:
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def dump_rows(rows, path: str) -> None:
    with open(path, "w") as f:
        for r in rows:
            f.write(json.dumps(r) + "\n")


def device_planes(rows) -> list:
    dev = re.compile(DEVICE_PLANE)
    return sorted({r["plane"] for r in rows if dev.match(r["plane"])})


def _line(rows, plane, line):
    return [r for r in rows if r["plane"] == plane and r["line"] == line]


def union(intervals) -> list:
    """Merged ``[start, end)`` intervals, sorted."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy_intervals(rows, plane) -> list:
    ops = _line(rows, plane, OPS_LINE) or _line(rows, plane, MODULES_LINE)
    return union((r["start_ns"], r["start_ns"] + r["dur_ns"]) for r in ops)


def busy_seconds(rows) -> float:
    """Seconds in which an operation ran on the device, averaged over
    the device planes. No device plane, or none with an event, is an
    error: every traced run drives the device."""
    planes = device_planes(rows)
    if not planes:
        raise TraceError("the trace has no device plane")
    per = [sum(e - s for s, e in busy_intervals(rows, p)) * 1e-9
           for p in planes]
    if min(per) <= 0:
        raise TraceError(f"a device plane shows no operation: {per}")
    return sum(per) / len(per)


def program_runs_ms(rows, pattern: str) -> dict:
    """{XLA module name: [device ms of each run]} for the compiled
    programs whose name matches ``pattern``, on the first device plane.
    Programs traced from one function share a name up to the number in
    brackets (one per chunk bucket)."""
    planes = device_planes(rows)
    if not planes:
        raise TraceError("the trace has no device plane")
    rx = re.compile(pattern)
    out = {}
    for r in _line(rows, planes[0], MODULES_LINE):
        if rx.search(r["name"]):
            out.setdefault(r["name"], []).append(r["dur_ns"] * 1e-6)
    return out


def program_median_ms(rows, pattern: str, variant: str = None) -> float:
    """Median device time of a program's runs. Where several programs
    match, ``variant`` "slowest" picks the one with the largest median
    (the 512-row chunk is the slowest chunk program); without it all
    their runs count together. No match is an error, never a zero."""
    runs = program_runs_ms(rows, pattern)
    if not runs:
        names = sorted({r["name"] for r in rows
                        if r["line"] == MODULES_LINE})[:12]
        raise TraceError(f"no program matches {pattern!r}; the trace's "
                         f"programs: {names}")
    medians = [statistics.median(v) for v in runs.values()]
    if variant == "slowest":
        return max(medians)
    if variant is not None:
        raise ValueError(f"variant {variant!r}")
    return statistics.median([x for v in runs.values() for x in v])


_HLO = re.compile(r"^(%[\w.\-]+) = \(?(\w+\[[\d,]*\]).*? ([\w\-]+)\(")
_LAYOUT = re.compile(r"\{[^}]*\}")


def short_op(name: str) -> str:
    """``%copy.209 = bf16[24,129,8,128,128]{...} copy(...)`` as
    ``%copy.209 copy bf16[24,129,8,128,128]``: an XLA op's event name is
    its whole HLO line."""
    m = _HLO.match(_LAYOUT.sub("", name))
    return f"{m[1]} {m[3]} {m[2]}" if m else name[:120]


def top_device_ops(rows, n=10) -> list:
    """[name, seconds] of the operations that took most device time (the
    first device plane)."""
    planes = device_planes(rows)
    total = {}
    for r in _line(rows, planes[0], OPS_LINE) if planes else []:
        key = short_op(r["name"])
        total[key] = total.get(key, 0.0) + r["dur_ns"] * 1e-9
    return [[k, v] for k, v in sorted(total.items(),
                                      key=lambda kv: -kv[1])[:n]]


def idle_gaps(rows, n=10) -> list:
    """[what the host was doing, seconds] for the device's idle gaps,
    longest total first: each gap between two busy intervals goes to the
    ``bench.*`` host span that covers most of it."""
    planes = device_planes(rows)
    if not planes:
        return []
    host = sorted((r for r in rows if r["name"].startswith(HOST_PREFIX)
                   and r["plane"] not in planes),
                  key=lambda r: r["start_ns"])
    starts = [h["start_ns"] for h in host]
    busy = busy_intervals(rows, planes[0])
    total = {}
    for (_, e0), (s1, _) in zip(busy, busy[1:]):
        best, best_cover = "outside any bench span", 0.0
        # The harness's spans do not nest: the one that began last
        # before the gap, and those that begin inside it.
        i = max(bisect.bisect_right(starts, e0) - 1, 0)
        while i < len(host) and host[i]["start_ns"] < s1:
            h = host[i]
            cover = (min(s1, h["start_ns"] + h["dur_ns"])
                     - max(e0, h["start_ns"]))
            if cover > best_cover:
                best, best_cover = h["name"], cover
            i += 1
        total[best] = total.get(best, 0.0) + (s1 - e0) * 1e-9
    return [[k, v] for k, v in sorted(total.items(),
                                      key=lambda kv: -kv[1])[:n]]


def program_totals(rows) -> dict:
    """{XLA module name: [runs, device seconds]} on the first device."""
    return {name: [len(ms), sum(ms) * 1e-3]
            for name, ms in program_runs_ms(rows, "").items()}


def cut(rows, programs_s=1.0, ops_s=0.08, name_chars=100) -> list:
    """A small piece of a trace to keep as test data: from the first
    program's start, ``programs_s`` of programs and host spans and
    ``ops_s`` of device operations, names cut short."""
    mods = [r for r in rows if r["line"] == MODULES_LINE]
    if not mods:
        return []
    t0 = min(r["start_ns"] for r in mods)
    dev = set(device_planes(rows))
    out = []
    for r in rows:
        is_op = r["plane"] in dev and r["line"] != MODULES_LINE
        end = t0 + (ops_s if is_op else programs_s) * 1e9
        if t0 <= r["start_ns"] and r["start_ns"] + r["dur_ns"] <= end:
            out.append(dict(r, name=r["name"][:name_chars],
                            start_ns=r["start_ns"] - t0))
    return out


def summary(rows, n=40) -> dict:
    """Planes, lines, event counts and the commonest names: what to look
    at by hand before writing a pattern against a trace."""
    out = {}
    for r in rows:
        ln = out.setdefault(r["plane"], {}).setdefault(
            r["line"], {"events": 0, "names": {}})
        ln["events"] += 1
        ln["names"][r["name"]] = ln["names"].get(r["name"], 0) + 1
    for plane in out.values():
        for ln in plane.values():
            ln["names"] = dict(sorted(ln["names"].items(),
                                      key=lambda kv: -kv[1])[:n])
    return out
