"""A program's share of the bound the chip sets, in %: the least time
the algorithm's bytes (``bound`` "bytes": a decode step) or operations
(``bound`` "flops": a prefill chunk of ``rows`` rows) allow at the
published peak, over the program's median device time. The counts come
from ``opcount`` by shape; the context lengths from what the window
served."""

import statistics

from .. import opcount
from . import program_ms


def _context_at(rec, t):
    """Positions request ``rec`` held in the cache at host time ``t``."""
    return len(rec.prompt) + sum(1 for x in rec.token_times if x <= t)


def mean_decode_context(ctx):
    """Over the traced part's decode ticks: the running sequences'
    context lengths, summed, then the mean over ticks."""
    t0, t1 = ctx.traced
    sums = []
    for tick in ctx.window.ticks:
        if not (t0 <= tick.start and tick.end <= t1 and tick.decoded > 0):
            continue
        live = [r for r in ctx.window.records
                if r.token_times and r.token_times[0] <= tick.start
                and (r.finished_at is None or r.finished_at > tick.start)]
        sums.append(sum(_context_at(r, tick.start) for r in live))
    return statistics.mean(sums) if sums else None


def mean_chunk_context(ctx, rows):
    """Mean keys a row of a ``rows``-row chunk attends, over the full
    chunks of the prompts the window was sent."""
    ctxs = [k * rows + (rows + 1) / 2
            for r in ctx.window.records
            for k in range(len(r.prompt) // rows)]
    return statistics.mean(ctxs) if ctxs else None


def reduce(params, ctx):
    ms = program_ms.reduce(params, ctx)
    tp = int(ctx.cell.config.get("tp", 1))
    if params["bound"] == "bytes":
        context = mean_decode_context(ctx)
        if context is None:
            return None
        least_s = (opcount.decode_step_bytes(ctx.dims, context, tp=tp)
                   / ctx.peaks["hbm_bytes_per_s"])
    elif params["bound"] == "flops":
        rows = int(params["rows"])
        context = mean_chunk_context(ctx, rows)
        if context is None:
            return None
        least_s = (opcount.prefill_chunk_flops(ctx.dims, rows, context,
                                               tp=tp)
                   / ctx.peaks["bf16_flops_per_s"])
    else:
        raise ValueError(f"bound {params['bound']!r}")
    ctx.log(f"roofline_share: bound {params['bound']}, mean context "
            f"{context:.1f}, least {least_s * 1e3:.4f} ms, measured "
            f"{ms:.4f} ms")
    return 100.0 * least_s * 1e3 / ms
