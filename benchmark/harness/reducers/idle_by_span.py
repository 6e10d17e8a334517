"""Device-idle ms a tick under one of the program's spans: for every
idle gap of the first device plane, its overlap with the spans
``params["span"]`` names (a kind without the ``tdt.`` prefix, or a list
whose parts are summed), divided by the number of ``tdt.tick`` spans
that dispatched a program in the traced part. A gap is split by time
among the spans that cover it. Logged beside it: the window's idle time,
the caller's part (under no ``tdt.*`` span) and the part of a tick's
that no leaf span covers."""

from .. import trace_reduce as T
from . import program_spans as P


def reduce(params, ctx):
    spans = P.spans_of(ctx)
    if not spans:
        return None
    parts = P.named(spans, params["span"], "idle_by_span")
    ticks = P.dispatching_ticks(spans)
    if not ticks:
        raise T.TraceError("idle_by_span: no tdt.tick span in the "
                           "capture dispatched a program")
    gaps = P.idle_intervals(ctx.rows)
    ms = {k: P.overlap_ns(gaps, P.intervals(v)) * 1e-6
          for k, v in parts.items()}
    a = {k: v * 1e-6 for k, v in P.attribution(gaps, spans).items()}
    ctx.log(f"idle_by_span: ms under {ms} of {a['idle']:.3f} idle in "
            f"{len(gaps)} gaps (in tick {a['in_tick']:.3f}, in submit "
            f"{a['in_submit']:.3f}, under no tdt span {a['outside']:.3f}; "
            f"of the tick's, under no leaf {a['no_leaf']:.3f}), over "
            f"{ticks} ticks that dispatched")
    return sum(ms.values()) / ticks
