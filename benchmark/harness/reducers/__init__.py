"""Per-layer metric readers. A metric's file under ``layer_metrics/``
names a reducer (a module here, found by name) and its parameters; the
reducer takes the metric from the run's spans, counters and trace. One
that finds nothing to read returns None, and the metric is left out.
"""

from __future__ import annotations

import dataclasses
import importlib


@dataclasses.dataclass
class RunContext:
    """What a traced run hands every reducer."""
    cell: object              # loader.Cell
    dims: object              # weights.Dims
    peaks: dict               # opcount.peaks_for(device_kind)
    window: object            # serve_loop.Window (host clock)
    traced: tuple             # (start, end) of the traced part, host clock
    rows: list                # trace_reduce rows of the traced part
    compile_s: float          # compile or cache-fetch seconds of set-up
    log: object = print


def read_metric(spec: dict, ctx: RunContext):
    """``spec``: a ``layer_metrics/<name>.json``. Returns a number or
    None."""
    mod = importlib.import_module(f"{__name__}.{spec['reducer']}")
    return mod.reduce(spec.get("params", {}), ctx)
