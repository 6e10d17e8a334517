"""Device duration of one compiled program in the profiler's trace:
the median over its runs, in ms. ``params["pattern"]`` matches the XLA
module name; ``params["variant"]`` "slowest" picks among programs that
share a name, as ``trace_reduce.program_median_ms`` says. A pattern
that matches no program raises ``TraceError``: the traced run fails,
so a renamed program cannot drop its metrics from the ledger unseen."""

from .. import trace_reduce as T


def reduce(params, ctx):
    return T.program_median_ms(ctx.rows, params["pattern"],
                               params.get("variant"))
