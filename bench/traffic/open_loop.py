"""Open-loop request schedules: arrivals on a fixed timetable,
whatever the server does.

The mix's file gives a rate, an arrival process and the length
distributions of prompts and outputs.  Each block of the schedule holds
``round(rate * seconds)`` requests whose gaps and lengths are fixed
quantiles of those distributions (stratified at ``(j + 0.5) / n``), in
an order drawn from the seed (gaps, prompt lengths and output lengths
each on their own): every seed offers the same work and the same set of
gaps, and the seed decides where the bursts fall and which request
comes in each.  The seed also draws the prompt tokens.  The first block
fills the measured window; later blocks keep the load on while the
window's last requests finish.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import List

import numpy as np

# the standard gamma's quantiles are read off one fixed sample
_GAMMA_SAMPLE = 1 << 18


@dataclass
class Req:
    due_s: float          # seconds after the window opens
    prompt: np.ndarray    # int32 token ids
    max_new: int
    in_window: bool


def gaps(arrival: dict, rate: float, n: int) -> np.ndarray:
    """``n`` inter-arrival gaps (seconds) at the stratified quantiles of
    the arrival process, summing to ``n / rate``."""
    p = (np.arange(n) + 0.5) / n
    if arrival["process"] == "poisson":
        g = -np.log1p(-p)
    elif arrival["process"] == "gamma":
        sample = np.sort(np.random.default_rng(0).standard_gamma(
            arrival["shape"], _GAMMA_SAMPLE))
        g = np.quantile(sample, p)
    else:
        raise ValueError(f"arrival process {arrival['process']!r}")
    return g * (n / rate) / g.sum()


def lengths(dist: dict, n: int) -> np.ndarray:
    """``n`` lengths at the stratified quantiles of a lognormal of the
    given median and sigma, rounded and clipped to [min, max]."""
    p = (np.arange(n) + 0.5) / n
    z = np.array([NormalDist().inv_cdf(x) for x in p])
    x = np.rint(dist["median"] * np.exp(dist["sigma"] * z))
    return np.clip(x, dist["min"], dist["max"]).astype(int)


def schedule(mix: dict, seed: int, seconds: float, vocab: int, *,
             tail_s: float = 0.0, rate: float = None) -> List[Req]:
    """The timetable: the window's block, then blocks until ``seconds +
    tail_s``, all due times from the window's opening."""
    rate = rate or mix["rate_rps"]
    n = max(1, int(round(rate * seconds)))
    blocks = 1 + math.ceil(tail_s / seconds) if tail_s else 1
    g0, p0, o0 = (gaps(mix["arrival"], rate, n),
                  lengths(mix["prompt"], n), lengths(mix["output"], n))
    out, t = [], 0.0
    for b in range(blocks):
        rng = np.random.default_rng(np.random.SeedSequence([int(seed), b]))
        g, p, o = (rng.permutation(g0), rng.permutation(p0),
                   rng.permutation(o0))
        for j in range(n):
            out.append(Req(due_s=t, max_new=int(o[j]), in_window=b == 0,
                           prompt=rng.integers(0, vocab, int(p[j]),
                                               dtype=np.int32)))
            t += float(g[j])
    return out
