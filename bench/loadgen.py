"""The general generator of open-loop serving traffic, read from a mix's
parameters (``bench/traffic/<mix>.json``).

A run of ``seconds`` at ``rate_per_s`` offers N = ceil(rate * seconds)
requests. Every seed gets the same set of sizes and gaps in another order,
so that seeds change which request meets which, not how much work there is:

* gaps between arrivals: the N quantiles (i + 1/2) / N of the exponential
  distribution of mean 1 / rate (a Poisson stream's gaps), shuffled;
* prompt lengths: the N quantiles of a lognormal (``median``, ``sigma``)
  truncated to [``min``, ``max``], rounded, and made distinct (a duplicate
  moves to the nearest free length), shuffled;
* output lengths: likewise, without the distinctness;
* prompt tokens: uniform over the vocabulary.

Each request is timed from when it was due, so a stall of the server also
delays every request that arrives behind it.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import Dict, List

import numpy as np


@dataclass
class Arrival:
    due_s: float  # offset from the start of the window
    prompt: np.ndarray  # int32 [prompt_len]
    max_new: int


def _quantiles(n: int):
    return [(i + 0.5) / n for i in range(n)]


def lognormal_lengths(n: int, median: float, sigma: float, lo: int, hi: int) -> List[int]:
    """The n quantiles of lognormal(median, sigma) truncated to [lo, hi]."""
    nd = NormalDist()
    mu = math.log(median)
    f_lo = nd.cdf((math.log(lo) - mu) / sigma)
    f_hi = nd.cdf((math.log(hi) - mu) / sigma)
    out = []
    for q in _quantiles(n):
        z = nd.inv_cdf(f_lo + q * (f_hi - f_lo))
        out.append(int(min(hi, max(lo, round(math.exp(mu + sigma * z))))))
    return out


def distinct(lengths: List[int], lo: int, hi: int) -> List[int]:
    """Move each repeated length to the nearest free one in [lo, hi]."""
    if len(lengths) > hi - lo + 1:
        raise ValueError(f"{len(lengths)} distinct lengths do not fit in [{lo}, {hi}]")
    used, out = set(), []
    for x in lengths:
        d = 0
        while True:
            for c in (x + d, x - d):
                if lo <= c <= hi and c not in used:
                    used.add(c)
                    out.append(c)
                    break
            else:
                d += 1
                continue
            break
    return out


def arrivals(traffic: Dict, seed: int, seconds: float, vocab: int) -> List[Arrival]:
    rate = float(traffic["rate_per_s"])
    n = max(1, math.ceil(rate * seconds))
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 7])
    gaps = np.array([-math.log(1.0 - q) / rate for q in _quantiles(n)])
    p, o = traffic["prompt_len"], traffic["output_len"]
    prompts = distinct(lognormal_lengths(n, p["median"], p["sigma"], p["min"], p["max"]),
                       p["min"], p["max"])
    outputs = lognormal_lengths(n, o["median"], o["sigma"], o["min"], o["max"])
    gaps, prompts, outputs = (rng.permutation(np.asarray(x)) for x in (gaps, prompts, outputs))
    due = np.cumsum(gaps) - gaps[0]  # the first request is due when the window opens
    return [Arrival(float(t), rng.integers(0, vocab, int(s), dtype=np.int32), int(m))
            for t, s, m in zip(due, prompts, outputs)]
