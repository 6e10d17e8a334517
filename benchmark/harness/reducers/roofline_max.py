"""A chunk program's share of the bound the chip sets, in %, where the
bound is the LARGER of two: the least time the algorithm's bytes allow
at the published memory bandwidth and the least its operations allow at
the published bf16 peak, for a chunk of ``params["rows"]`` rows at the
window's mean context (``family.prefill_chunk_bytes``,
``family.prefill_chunk_flops``), over the median device time of the
program ``params["pattern"]`` names. A family whose chunk reads weights
by the row (routed experts) is neither bound for good: which of the two
held is logged. The token-expert pairs that went through an expert are
counted as the traced part served them (``held_pairs`` of the program's
``tdt.expert_load`` events at ``rows`` or more rows a program, a layer's
mean); a capture without such events leaves the family its even share.
The program's decode rows and their attention are in the time and not
in the counts, so the share reads low, never high."""

import statistics

from . import program_ms, program_spans as P
from .roofline_share import mean_chunk_context


def served_pairs(ctx, rows, layers):
    """Mean held pairs a layer of the traced programs that ran at least
    ``rows`` rows, or None where the capture holds no such event."""
    got = [s["stats"]["held_pairs"] for s in P.spans_of(ctx)
           if s["name"] == P.SPAN_PREFIX + "expert_load"
           and s["stats"].get("rows", 0) >= rows
           and "held_pairs" in s["stats"]]
    return statistics.mean(got) / layers if got else None


def reduce(params, ctx):
    rows = int(params["rows"])
    context = mean_chunk_context(ctx, rows)
    if context is None:
        return None
    ms = program_ms.reduce(params, ctx)
    tp = int(ctx.cell.config.get("tp", 1))
    pairs = served_pairs(ctx, rows, ctx.dims.layers)
    by_bytes = (ctx.family.prefill_chunk_bytes(ctx.dims, rows, context,
                                               tp=tp)
                / ctx.peaks["hbm_bytes_per_s"])
    by_flops = (ctx.family.prefill_chunk_flops(
        ctx.dims, rows, context, tp=tp,
        **({} if pairs is None else {"held_pairs": pairs}))
                / ctx.peaks["bf16_flops_per_s"])
    ctx.log(f"roofline_max: {rows} rows at mean context {context:.1f}, "
            f"held pairs a layer {pairs}; least by bytes "
            f"{by_bytes * 1e3:.4f} ms, by operations "
            f"{by_flops * 1e3:.4f} ms; measured {ms:.4f} ms")
    return 100.0 * max(by_bytes, by_flops) * 1e3 / ms
