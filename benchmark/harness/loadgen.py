"""The one traffic generator: a mix is a data file of parameters.

Every seed gets the SAME set of sizes and arrival gaps, in another
order, and other token ids: a law is sampled at evenly spaced quantiles
(not at random), in blocks of ``BLOCK`` requests, and the seed permutes
each block. So two seeds differ in which request meets which, not in how
much work the window holds.

Mix file keys: ``loop`` ("open" with ``rate_per_s``, Poisson arrivals;
"closed" with ``clients``), ``prompt_tokens`` and ``output_tokens``
(each a law), ``check_requests`` (how many finished requests the
reference compares).
Laws: ``{"law": "lognormal", "median", "sigma", "min", "max"}``,
``{"law": "uniform", "min", "max"}``, ``{"law": "constant", "value"}``.
"""

from __future__ import annotations

import dataclasses
from statistics import NormalDist

import numpy as np

BLOCK = 64      # requests to a block of quantiles


@dataclasses.dataclass
class Planned:
    """One request as the generator plans it."""
    rid: int
    due_s: float          # open loop: seconds after the window opens
    prompt: list
    max_new_tokens: int


def quantiles(law: dict, n: int) -> np.ndarray:
    """``law`` at the ``n`` quantiles (i + 0.5) / n, as whole numbers."""
    u = (np.arange(n) + 0.5) / n
    kind = law["law"]
    if kind == "constant":
        v = np.full(n, float(law["value"]))
    elif kind == "uniform":
        v = law["min"] + u * (law["max"] - law["min"])
    elif kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(x)) for x in u])
        v = np.clip(law["median"] * np.exp(law["sigma"] * z),
                    law["min"], law["max"])
    else:
        raise ValueError(f"unknown law {kind!r}")
    return np.rint(v).astype(np.int64)


def largest(law: dict) -> int:
    """The most a law can give."""
    return int(law["value"] if law["law"] == "constant" else law["max"])


def exp_gaps(rate: float, n: int) -> np.ndarray:
    """Exponential gaps of mean 1 / rate at evenly spaced quantiles."""
    u = (np.arange(n) + 0.5) / n
    return -np.log1p(-u) / rate


def _rng(seed: int, stream: int):
    return np.random.default_rng([int(seed), stream])


class Plan:
    """Requests in order, made as they are asked for. ``open``: all of
    a window's arrivals with their due times. ``closed``: an endless
    list a client takes its next document from."""

    def __init__(self, mix: dict, vocab: int, seed: int):
        self.mix, self.vocab, self.seed = mix, int(vocab), int(seed)
        self._next_rid = 0
        self._sizes = []
        self._blocks = 0

    def _more_sizes(self):
        rng = _rng(self.seed, 1000 + self._blocks)
        p = quantiles(self.mix["prompt_tokens"], BLOCK)
        o = quantiles(self.mix["output_tokens"], BLOCK)
        # Prompt and output lengths permuted apart: independent laws.
        self._sizes.extend(zip(rng.permutation(p).tolist(),
                               rng.permutation(o).tolist()))
        self._blocks += 1

    def _request(self, due_s: float) -> Planned:
        rid = self._next_rid
        self._next_rid += 1
        while rid >= len(self._sizes):
            self._more_sizes()
        n_prompt, n_out = self._sizes[rid]
        ids = _rng(self.seed, 5_000_000 + rid).integers(
            0, self.vocab, size=n_prompt)
        return Planned(rid, due_s, ids.tolist(), int(n_out))

    def next(self) -> Planned:
        """Closed loop: the next document, due as soon as it is asked."""
        return self._request(0.0)

    def arrivals(self, seconds: float) -> list:
        """Open loop: every request due in ``[0, seconds)``."""
        rate = float(self.mix["rate_per_s"])
        n = max(int(round(rate * seconds)), 1)
        gaps = _rng(self.seed, 1).permutation(exp_gaps(rate, n))
        # The quantile midpoints sum a little short of n / rate: stretch
        # to the window, so that the offered rate is the stated one.
        due = np.cumsum(gaps)
        due = due * (seconds * (n - 0.5) / n / due[-1])
        return [self._request(float(t)) for t in due]
