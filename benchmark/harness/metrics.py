"""Metric arithmetic on what the serving loop recorded. Host-clock times
in, plain numbers out; nothing here knows the program."""

from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """The ``q``-th percentile (0..100), linear between order statistics
    (numpy's default). NaN for no values."""
    v = sorted(values)
    if not v:
        return math.nan
    k = (len(v) - 1) * q / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    return v[lo] + (v[hi] - v[lo]) * (k - lo)


def ttfts_ms(records) -> list:
    """First token minus when the request was DUE, per request that got
    a first token."""
    return [(r.token_times[0] - r.due_at) * 1e3
            for r in records if r.token_times]


def itl_gaps_ms(records, window_end: float) -> list:
    """Every gap between consecutive output tokens of a request that
    closed inside the window."""
    out = []
    for r in records:
        t = r.token_times
        out.extend((b - a) * 1e3 for a, b in zip(t, t[1:])
                   if b <= window_end)
    return out


def tokens_per_s(records, window_start: float, window_end: float) -> float:
    """Prompt plus generated tokens of the requests COMPLETED inside the
    window, over the window's length."""
    done = [r for r in records
            if r.status == "done" and r.finished_at is not None
            and window_start <= r.finished_at <= window_end]
    total = sum(len(r.prompt) + len(r.tokens) for r in done)
    return total / (window_end - window_start)


def end_to_end(records, window_start, window_end) -> dict:
    """Every end-to-end number a loop can give; the caller keeps those
    its cell reports."""
    gaps = itl_gaps_ms(records, window_end)
    first = ttfts_ms(records)
    return {
        "ttft_p90_ms": percentile(first, 90),
        "ttft_p50_ms": percentile(first, 50),
        "itl_p50_ms": percentile(gaps, 50),
        "itl_p95_ms": percentile(gaps, 95),
        "tokens_per_s": tokens_per_s(records, window_start, window_end),
        "_samples": {"ttft": len(first), "itl": len(gaps)},
    }
