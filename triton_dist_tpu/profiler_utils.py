"""Host-side benchmarking and profiling helpers.

Reference: ``python/triton_dist/profiler_utils.py`` (629 LoC) —
``perf_func`` :355, ``perf_func_with_l2_reset`` :330, ``group_profile``
:205 (per-rank torch-profiler traces merged to one JSON),
``benchmark_latency_memory`` :372.

TPU redesign: ``jax.profiler`` natively emits Perfetto/TensorBoard
traces for every device in one capture (no per-rank merging needed);
``perf_func`` uses dependency-chained in-jit iteration with two-point
slope timing so fixed dispatch overhead cancels (async dispatch
makes naive wall-clocking meaningless — see bench.py).
"""

from __future__ import annotations

import contextlib
import time
from typing import Callable, Sequence

import numpy as np


def perf_func(fn: Callable, args: Sequence, *, iters_lo: int = 8,
              iters_hi: int = 40, repeats: int = 3,
              chain: bool = True) -> float:
    """Seconds per invocation of ``fn(*args)``.

    With ``chain=True`` (default) runs dependency-chained iterations
    inside one jit and returns the two-point slope — use for
    device-bound measurements. ``chain=False`` wall-clocks dispatches
    (only meaningful with a locally-attached backend).
    """
    import jax
    import jax.numpy as jnp

    if not chain:
        r = fn(*args)
        jax.block_until_ready(r)
        best = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            for _ in range(iters_hi):
                r = fn(*args)
            jax.block_until_ready(r)
            best = min(best, (time.perf_counter() - t0) / iters_hi)
        return best

    lead = args[0]

    def make_chain(iters):
        @jax.jit
        def chained(*a):
            def body(_, x):
                out = fn(x, *a[1:])
                first = jax.tree.leaves(out)[0]
                bump = (first.reshape(-1)[0].astype(jnp.float32) * 1e-3
                        ).astype(x.dtype)
                return jnp.clip(x + bump, -4.0, 4.0)
            s = jax.lax.fori_loop(0, iters, body, a[0])
            return jnp.sum(s.astype(jnp.float32))
        return chained

    times = {}
    for iters in (iters_lo, iters_hi):
        chained = make_chain(iters)
        v = np.asarray(chained(*args))
        if not np.isfinite(v):
            raise FloatingPointError("perf chain produced non-finite value")
        best = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            np.asarray(chained(*args))
            best = min(best, time.perf_counter() - t0)
        times[iters] = best
    return (times[iters_hi] - times[iters_lo]) / (iters_hi - iters_lo)


@contextlib.contextmanager
def group_profile(name: str = "trace", *, log_dir: str = "/tmp/tdt_traces",
                  create_perfetto_link: bool = False,
                  create_perfetto_trace: bool = False):
    """Capture a multi-device profile viewable in Perfetto/TensorBoard.

    Reference ``group_profile`` merges per-rank torch traces
    (``profiler_utils.py:100-204``); ``jax.profiler.trace`` already
    captures every local device into one trace directory.
    ``create_perfetto_trace`` additionally materializes the capture as
    ``perfetto_trace.json.gz`` in the session directory (forwarded to
    ``jax.profiler.trace`` when this jax supports it; silently dropped
    on older versions).
    """
    import inspect

    import jax

    path = f"{log_dir}/{name}"
    kw = {"create_perfetto_link": create_perfetto_link}
    if create_perfetto_trace:
        sig = inspect.signature(jax.profiler.trace)
        if "create_perfetto_trace" in sig.parameters:
            kw["create_perfetto_trace"] = True
    with jax.profiler.trace(path, **kw):
        yield path


def benchmark_latency(fn, args, **kw) -> dict:
    """Latency + achieved-bytes helper (reference
    ``benchmark_latency_memory``)."""
    sec = perf_func(fn, args, **kw)
    return {"seconds": sec, "ms": sec * 1e3}
