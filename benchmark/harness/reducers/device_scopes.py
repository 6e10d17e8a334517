"""The program's own names on the DEVICE side of the capture. Every
block of a step program is traced under ``jax.named_scope("tdt.<block>")``
(the program's ``obs.scope``), so the ``op_name`` of each operation XLA
compiles from it holds the segment ``tdt.<block>``, and the profiler
writes that path into the capture as the stat ``tf_op`` of the METADATA
of the operation's ``XLA Ops`` events (name and constant stats, kept once
a plane). ``jax.profiler.ProfileData`` gives an event's own stats and
not its metadata's, and ``trace_reduce.read_xplane`` keeps no stat of a
device event at all, so this module reads the ``.xplane.pb`` itself, by
the field numbers of ``xplane.proto``, the first device plane only, into
plain rows

    {"line", "name", "start_ns", "dur_ns", "scope"}

(``scope``: the whole ``tf_op``, "" where the operation carries none; a
program's ``XLA Modules`` event is a row too, without the key), and
everything else works on such rows, so the tests check it on recorded
JSON lines.

A ``while`` and the operations of its body lie nested on the one line,
and a fused operation may carry several paths (``a;b``), so a block's
time in one run of a program is the length of the UNION of the
intervals of the operations whose path holds its segment: nothing is
counted twice within a block, and an operation under two blocks counts
for both.

XLA writes some operations itself, in place of what the program traced
and without its metadata (``REWRITTEN``): those are read by their HLO
name to the block whose operation they stand for.

A program from before the scopes leaves none in the capture: the readers
then return None and the metric is left out. A capture that holds some
``tdt.`` scopes but not the one a metric names is an error, as is a
program whose operations mostly carry none (the names fell off). An
executable fetched from a compile cache that an unscoped tree filled is
such a program: the cache's key leaves metadata out
(docs/observability.md, "In a profiler capture").
"""

from __future__ import annotations

import bisect
import functools
import os
import re
import statistics

from .. import loader, trace_reduce as T
from .program_spans import SPAN_PREFIX as SCOPE_PREFIX    # one prefix: tdt.

# The stat of an operation's event metadata that holds its op_name, as
# ``<op_name>:<op_type>`` (libtpu 0.0.34, jax 0.9.0).
SCOPE_STAT = "tf_op"
# [pattern on the HLO instruction's name, block]: libtpu's ragged-dot
# rewrite gives its Mosaic call the op_name "ragged-dot-none" whatever
# the product it replaces was traced under (libtpu 0.0.34).
REWRITTEN = ((re.compile(r"^%ragged-dot"), "experts"),)
# Where run.py keeps the capture while the reducers run.
TRACE_DIR = os.path.join(loader.REPO_ROOT, ".bench_trace")
NO_SCOPE = "(no scope)"


def _varint(buf, i):
    x = shift = 0
    while True:
        b = buf[i]
        i += 1
        x |= (b & 0x7F) << shift
        if not b & 0x80:
            return x, i
        shift += 7


def fields(buf):
    """``(number, wire type, value)`` of each field of one protobuf
    message: a varint as an int, a length-delimited field as a
    memoryview of its bytes."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = bytes(buf[i:i + size]), i + size
        else:
            raise T.TraceError(f"protobuf wire type {wire} in the capture")
        yield number, wire, value


def _first(message, number, default=None):
    return next((v for n, _, v in fields(message) if n == number),
                default)


def _text(value) -> str:
    return bytes(value).decode("utf-8", "replace")


def read_capture(path: str) -> list:
    """Rows of the first device plane's programs and operations, each
    operation with its scope path: an ``XSpace`` of planes of lines of
    events, an event's name and ``tf_op`` in the plane's
    ``event_metadata`` under the event's ``metadata_id``."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    dev = re.compile(T.DEVICE_PLANE)
    planes = {}
    for number, _, plane in fields(space):              # XSpace.planes
        if number == 1:
            planes[_text(_first(plane, 2, b""))] = plane    # XPlane.name
    mine = sorted(p for p in planes if dev.match(p))
    if not mine:
        raise T.TraceError("the trace has no device plane")
    stat_names, metadata, lines = {}, {}, []
    for number, _, value in fields(planes[mine[0]]):
        if number == 5:                     # stat_metadata: id -> name
            entry = _first(value, 2)
            stat_names[_first(entry, 1)] = _text(_first(entry, 2, b""))
        elif number == 4:                   # event_metadata: id -> ...
            metadata[_first(value, 1)] = _first(value, 2)
        elif number == 3:
            lines.append(value)
    scope_stat = {i for i, n in stat_names.items() if n == SCOPE_STAT}

    def name_and_scope(meta):
        name, scope = "", ""
        for number, _, value in fields(meta):
            if number == 2:                             # name
                name = _text(value)
            elif number == 5 and _first(value, 1) in scope_stat:   # stats
                ref = _first(value, 7)      # a string, or a reference
                scope = (stat_names.get(ref, "") if ref is not None
                         else _text(_first(value, 5, b"")))
        return name, scope

    named = {i: name_and_scope(m) for i, m in metadata.items()}
    rows = []
    for line in lines:
        kind = _text(_first(line, 2, b""))              # XLine.name
        if kind not in (T.OPS_LINE, T.MODULES_LINE):
            continue
        t0 = _first(line, 3, 0)                         # timestamp_ns
        for number, _, event in fields(line):
            if number != 4:                             # XLine.events
                continue
            got = {n: v for n, w, v in fields(event) if w == 0}
            name, scope = named.get(got.get(1), ("", ""))
            row = {"line": kind, "name": name,
                   "start_ns": t0 + got.get(2, 0) * 1e-3,   # offset_ps
                   "dur_ns": got.get(3, 0) * 1e-3}          # duration_ps
            if kind == T.OPS_LINE:
                row["scope"] = scope
            rows.append(row)
    return rows


@functools.lru_cache(maxsize=1)
def _capture_rows(path: str) -> tuple:
    return tuple(read_capture(path))


def rows_of(ctx) -> tuple:
    """The rows of the run's capture, parsed once for all its metrics
    (``ctx`` is the run's; the capture is found where run.py left it)."""
    return _capture_rows(T.find_xplane(TRACE_DIR))


@functools.lru_cache(maxsize=None)
def _segments(scope: str) -> frozenset:
    return frozenset(seg[len(SCOPE_PREFIX):]
                     for seg in re.split(r"[/;]", scope)
                     if seg.startswith(SCOPE_PREFIX))


def blocks_of(row) -> frozenset:
    """The blocks an operation's row counts for."""
    return _segments(row.get("scope", "")) or frozenset(
        block for rx, block in REWRITTEN if rx.search(row["name"]))


def program_runs(rows, pattern: str, variant: str = None) -> list:
    """``[start, end)`` of each run of the program ``pattern`` picks
    among the ``XLA Modules`` rows, as ``trace_reduce.
    program_median_ms`` picks it: with ``variant`` "slowest" the runs
    of the one name with the largest median, else of every match."""
    rx = re.compile(pattern)
    runs = {}
    for r in rows:
        if r["line"] == T.MODULES_LINE and rx.search(r["name"]):
            runs.setdefault(r["name"], []).append(
                (r["start_ns"], r["start_ns"] + r["dur_ns"]))
    if not runs:
        names = sorted({r["name"] for r in rows
                        if r["line"] == T.MODULES_LINE})[:12]
        raise T.TraceError(f"no program matches {pattern!r}; the trace's "
                           f"programs: {names}")
    if variant == "slowest":
        return max(runs.values(), key=lambda v: statistics.median(
            e - s for s, e in v))
    if variant is not None:
        raise ValueError(f"variant {variant!r}")
    return sorted(x for v in runs.values() for x in v)


def _length(intervals) -> float:
    return sum(e - s for s, e in T.union(intervals))


def blocks_ms(rows, pattern: str, variant: str = None):
    """``{block: median ms a run}`` of the program ``pattern`` picks,
    with ``NO_SCOPE`` (a run's busy time under no block) and ``"busy"``
    among the keys, and the share of all its runs' busy time that lies
    under a block. None where no operation of the whole capture carries
    a ``tdt.`` scope."""
    ops = sorted((r for r in rows if r["line"] == T.OPS_LINE),
                 key=lambda r: r["start_ns"])
    if not any(SCOPE_PREFIX in r.get("scope", "") for r in ops):
        return None
    starts = [r["start_ns"] for r in ops]
    per_run, busy_all, named_all = {}, 0.0, 0.0
    runs = program_runs(rows, pattern, variant)
    for s, e in runs:
        mine = ops[bisect.bisect_left(starts, s):
                   bisect.bisect_left(starts, e)]
        by_block, named, every = {}, [], []
        for r in mine:
            span = (r["start_ns"], r["start_ns"] + r["dur_ns"])
            every.append(span)
            blocks = blocks_of(r)
            for b in blocks:
                by_block.setdefault(b, []).append(span)
            if blocks:
                named.append(span)
        busy, under = _length(every), _length(named)
        busy_all += busy
        named_all += under
        ms = {b: _length(v) * 1e-6 for b, v in by_block.items()}
        ms[NO_SCOPE] = (busy - under) * 1e-6
        ms["busy"] = busy * 1e-6
        for b, v in ms.items():
            per_run.setdefault(b, []).append(v)
    # A block that some runs lack read 0 there.
    medians = {b: statistics.median(v + [0.0] * (len(runs) - len(v)))
               for b, v in per_run.items()}
    return medians, (named_all / busy_all if busy_all else 0.0), len(runs)
