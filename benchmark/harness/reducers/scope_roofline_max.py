"""One block's share of the bound the chip sets, in %: the LARGER of the
least times the block's bytes allow at the published memory bandwidth
and its operations allow at the published bf16 peak, over the block's
device time in the program ``params["pattern"]`` picks (``scope_ms``'s
reading of the scope ``params["scope"]``: every layer's operations under
it, the median over the runs). Bytes and operations are the family's,
``params["bytes"]`` and ``params["flops"]`` naming its functions of
``(dims, rows)`` for a chunk of ``params["rows"]`` rows, summed over the
layers that run the block: the algorithm's needs, whoever implements the
block. With ``params["pairs"]`` true both are also handed the held
token-expert pairs as the traced part served them
(``roofline_max.served_pairs``; a capture without such events leaves the
family its even share). Which of the two bounds held is logged. The
program's decode rows are in the time and not in the counts, so the
share reads low, never high.

A capture without ``tdt.`` scopes, or with none of this name, gives
None, and the metric is left out: a program from before the block has
nothing to read."""

from . import device_scopes as D
from .roofline_max import served_pairs


def reduce(params, ctx):
    rows, scope = int(params["rows"]), params["scope"]
    got = D.blocks_ms(D.rows_of(ctx), params["pattern"],
                      params.get("variant"))
    if got is None or scope not in got[0]:
        return None
    ms = got[0][scope]
    more = {}
    if params.get("pairs"):
        pairs = served_pairs(ctx, rows, ctx.dims.layers)
        if pairs is not None:
            more["held_pairs"] = pairs
    by_bytes = (getattr(ctx.family, params["bytes"])(ctx.dims, rows, **more)
                / ctx.peaks["hbm_bytes_per_s"])
    by_flops = (getattr(ctx.family, params["flops"])(ctx.dims, rows, **more)
                / ctx.peaks["bf16_flops_per_s"])
    ctx.log(f"scope_roofline_max: {scope} at {rows} rows, {more}: least "
            f"by bytes {by_bytes * 1e3:.4f} ms, by operations "
            f"{by_flops * 1e3:.4f} ms; measured {ms:.4f} ms")
    return 100.0 * max(by_bytes, by_flops) * 1e3 / ms
