"""The one traffic generator. A mix is a data file under ``traffic/``:

    {"kind": "requests", "loop": "closed", "clients": 32, "ramp_s": 6,
     "prompt_len": {"dist": "lognormal", "median": 256, "sigma": 0.8, "min": 16, "max": 1024},
     "output_len": {"dist": "lognormal", "median": 128, "sigma": 0.6, "min": 16, "max": 256},
     "pool": 64}
    {"kind": "requests", "loop": "open", "rate_per_s": 1.7, ...}

A stratified pool, not independent draws: every seed sends the same work in
another order. The lengths (and, in an open loop, the gaps between arrivals)
are the ``pool`` quantile midpoints of each distribution, paired once by a
fixed shuffle. The run's seed only permutes the pool, block after block, and
draws the token ids. So each block of ``pool`` requests holds the same prompt
tokens and the same output tokens, whatever the seed, and in an open loop
spans exactly ``pool / rate_per_s`` seconds: the gaps have the exponential's
mean and shape, but the count of arrivals in a window has none of a Poisson
process's variance. (The builder's contract asks for this where seeds would
otherwise change the work; PERF.md section 6.)
"""
from __future__ import annotations

import math
import statistics

import numpy as np

PAIRING_SEED = 1     # the fixed shuffle that pairs outputs and gaps with prompts


def quantiles(spec, n):
    """The ``n`` quantile midpoints of a length or gap distribution."""
    ps = [(i + 0.5) / n for i in range(n)]
    dist = spec["dist"]
    if dist == "lognormal":
        nd = statistics.NormalDist()
        xs = [math.exp(math.log(spec["median"]) + spec["sigma"] * nd.inv_cdf(p))
              for p in ps]
    elif dist == "exponential":
        xs = [-math.log(1.0 - p) * spec["mean"] for p in ps]
        scale = spec["mean"] * n / sum(xs)       # keep the mean exact
        xs = [x * scale for x in xs]
    else:
        raise ValueError("unknown distribution %r" % dist)
    if "min" in spec or "max" in spec:
        xs = [min(max(x, spec.get("min", x)), spec.get("max", x)) for x in xs]
    return xs


class Stream:
    """Requests in the order this seed sends them: ``next()`` gives
    ``(gap_s, prompt_ids, max_new)``; ``gap_s`` is the time since the
    previous arrival (0 in a closed loop)."""

    def __init__(self, mix, vocab, seed):
        n = int(mix["pool"])
        fixed = np.random.default_rng(PAIRING_SEED)
        prompts = np.rint(quantiles(mix["prompt_len"], n)).astype(int)
        outputs = np.rint(quantiles(mix["output_len"], n)).astype(int)
        outputs = outputs[fixed.permutation(n)]
        if mix["loop"] == "open":
            gaps = np.asarray(quantiles(
                {"dist": "exponential", "mean": 1.0 / mix["rate_per_s"]}, n))
            gaps = gaps[fixed.permutation(n)]
        else:
            gaps = np.zeros(n)
        self.pool = list(zip(gaps.tolist(), prompts.tolist(),
                             outputs.tolist()))
        self._rng = np.random.default_rng(int(seed))
        self._vocab = int(vocab)
        self._block = []

    def mean_output_len(self):
        return sum(p[2] for p in self.pool) / len(self.pool)

    def mean_prompt_len(self):
        return sum(p[1] for p in self.pool) / len(self.pool)

    def next(self):
        if not self._block:
            order = self._rng.permutation(len(self.pool))
            self._block = [self.pool[i] for i in order]
        gap, plen, out = self._block.pop()
        ids = self._rng.integers(0, self._vocab, size=plen, dtype=np.int64)
        return gap, ids.astype(np.int32), out
