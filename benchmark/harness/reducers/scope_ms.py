"""Device time of one block of a compiled program, in ms: for each run
of the program ``params["pattern"]`` picks (``params["variant"]`` as
``program_ms`` has it), the time in which an operation traced under
``tdt.<params["scope"]>`` ran (``device_scopes``: the union of their
intervals, a ``while`` and its body counted once), then the median over
the runs. Logged beside it: every block of that program and the part of
its busy time under none.

A capture without any ``tdt.`` scope (a program from before them) gives
None, and the metric is left out; one that holds some but not the scope
named raises ``TraceError``, as does a program with under half of its
busy time beneath a scope: names that fell off fail the traced run, they
do not read as a faster block."""

from .. import trace_reduce as T
from . import device_scopes as D

LEAST_NAMED_SHARE = 0.5


def block_ms(rows, pattern, variant, scope, log=print):
    got = D.blocks_ms(rows, pattern, variant)
    if got is None:
        return None
    medians, named_share, runs = got
    log(f"scope_ms: program {pattern!r} ({variant}), {runs} runs, median "
        "ms a block: "
        + ", ".join(f"{b} {v:.4f}" for b, v in sorted(
            medians.items(), key=lambda kv: -kv[1]))
        + f"; under a scope {100 * named_share:.2f} % of its busy time")
    if scope not in medians:
        raise T.TraceError(
            f"scope_ms: no operation of program {pattern!r} runs under "
            f"{D.SCOPE_PREFIX}{scope}; it holds {sorted(medians)}")
    if named_share < LEAST_NAMED_SHARE:
        raise T.TraceError(
            f"scope_ms: only {100 * named_share:.1f} % of the busy time "
            f"of program {pattern!r} lies under a {D.SCOPE_PREFIX} scope")
    return medians[scope]


def reduce(params, ctx):
    return block_ms(D.rows_of(ctx), params["pattern"],
                    params.get("variant"), params["scope"], log=ctx.log)
