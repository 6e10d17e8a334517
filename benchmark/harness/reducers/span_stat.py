"""One stat of one of the program's spans, over the traced part: the
``params["reduce"]`` ("median" or "mean") of stat ``params["stat"]``
over the ``tdt.<params["span"]>`` events that carry it (``batch`` of
``decode``: sequences a decode dispatch served; ``waited_ms`` of
``admit``: how long the request stood in the queue)."""

import statistics

from .. import trace_reduce as T
from . import program_spans as P

REDUCE = {"median": statistics.median, "mean": statistics.mean}


def reduce(params, ctx):
    spans = P.spans_of(ctx)
    if not spans:
        return None
    kind, stat = params["span"], params["stat"]
    values = [s["stats"][stat] for s in P.named(spans, kind,
                                                "span_stat")[kind]
              if stat in s["stats"]]
    if not values:
        raise T.TraceError(f"span_stat: no {P.SPAN_PREFIX}{kind} span "
                           f"carries the stat {stat!r}")
    ctx.log(f"span_stat: {stat} of {P.SPAN_PREFIX}{kind}: "
            f"{len(values)} events, {min(values)} to {max(values)}")
    return float(REDUCE[params["reduce"]](values))
