"""The open-loop request generator.

A mix file gives a rate, and lognormal prompt and output lengths (median,
sigma, clipped to [min, max]). A run of ``seconds`` seconds offers
``round(rate * seconds)`` requests. Every seed gets the same multiset of
lengths and of gaps between arrivals, each drawn at evenly spaced
quantiles of its distribution (lengths lognormal, gaps exponential, which
makes the arrivals a Poisson process's), in an order and with prompt tokens
that the seed sets. So seeds change which request comes when and what it
says, not how much work a run holds.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import Dict, List

import numpy as np

from benchmarks.chip.lib import tokens


@dataclass
class Request:
    due: float                  # seconds after the window opens
    prompt: List[int]
    max_new: int


def quantile_lengths(spec: Dict, n: int) -> List[int]:
    """``n`` lognormal lengths at the quantiles (i + 1/2) / n, clipped."""
    nd = NormalDist()
    mu = math.log(spec["median"])
    return [int(min(max(round(math.exp(mu + spec["sigma"]
                                       * nd.inv_cdf((i + 0.5) / n))),
                        spec["min"]), spec["max"]))
            for i in range(n)]


def quantile_gaps(rate: float, n: int) -> List[float]:
    """``n`` exponential gaps of mean 1 / rate at evenly spaced quantiles."""
    return [-math.log(1.0 - (i + 0.5) / n) / rate for i in range(n)]


def schedule(mix: Dict, seconds: float, seed: int, vocab: int
             ) -> List[Request]:
    """The requests of one run, in order of arrival."""
    n = max(1, round(mix["rate"] * seconds))
    rng = np.random.RandomState(seed % 2 ** 31)
    prompts = rng.permutation(quantile_lengths(mix["prompt"], n))
    outputs = rng.permutation(quantile_lengths(mix["output"], n))
    gaps = rng.permutation(quantile_gaps(mix["rate"], n))
    due = np.cumsum(gaps) - gaps[0]
    out = []
    for i in range(n):
        toks = tokens.gen_tokens(seed, i, 1, int(prompts[i]) - 1, vocab)[0]
        out.append(Request(float(due[i]), [int(t) for t in toks],
                           int(outputs[i])))
    return out
