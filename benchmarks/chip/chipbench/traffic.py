"""One general generator for every traffic mix.

A mix file states the arrival process, the length distributions and a
``schedule_seed``.  Every run gets the same work in the same arrangement:
the midpoint quantiles of the stated distributions (prompt lengths, output
budgets, inter-arrival gaps) in an order drawn from the schedule seed.
The run's own seed draws the prompt tokens (and, elsewhere, the weights).
Below and above the knee the order of arrivals decides which requests are
admitted together, so an order drawn from the run's seed moved tokens/s
by 20% between seeds while two runs of one seed agreed within a few.
"""
from __future__ import annotations

import math
from typing import Dict, List

import numpy as np


def _largest_remainder(weights, n: int) -> List[int]:
    w = np.asarray(weights, np.float64)
    exact = w / w.sum() * n
    counts = np.floor(exact).astype(int)
    for i in np.argsort(-(exact - counts))[:n - counts.sum()]:
        counts[i] += 1
    return counts.tolist()


def prompt_lengths(spec: Dict, n: int) -> np.ndarray:
    """``{"classes": [...], "weights": [...]}``: each class as often as its
    weight says, to the nearest whole request."""
    counts = _largest_remainder(spec["weights"], n)
    return np.repeat(np.asarray(spec["classes"], np.int64), counts)


def output_lengths(spec: Dict, n: int) -> np.ndarray:
    """``{"log_uniform": [lo, hi]}``: the n midpoint quantiles."""
    lo, hi = spec["log_uniform"]
    q = (np.arange(n) + 0.5) / n
    return np.rint(np.exp(math.log(lo) + q * (math.log(hi) - math.log(lo)))
                   ).astype(np.int64)


def arrival_times(n: int, seconds: float, rng) -> np.ndarray:
    """Poisson arrivals over [0, seconds): the n midpoint quantiles of the
    exponential gap, in random order, scaled to span the window."""
    q = (np.arange(n) + 0.5) / n
    gaps = rng.permutation(-np.log1p(-q))
    t = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    return t * (seconds / gaps.sum())


def generate(mix: Dict, rate_rps: float, seconds: float, seed: int,
             vocab_size: int) -> List[Dict]:
    """Requests ``{"rid", "prompt", "max_new", "arrival_s"}`` arriving at
    ``rate_rps`` over ``seconds``, sorted by arrival."""
    if mix.get("arrivals", "poisson") != "poisson":
        raise ValueError(f"unknown arrival process {mix['arrivals']!r}")
    n = max(1, int(round(rate_rps * seconds)))
    order = np.random.default_rng(int(mix["schedule_seed"]))
    plens = order.permutation(prompt_lengths(mix["prompt_len"], n))
    olens = order.permutation(output_lengths(mix["output_len"], n))
    t = arrival_times(n, seconds, order)
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32])
    return [{"rid": i, "arrival_s": float(t[i]), "max_new": int(olens[i]),
             "prompt": rng.integers(0, vocab_size, int(plens[i]),
                                    dtype=np.int64).astype(np.int32)}
            for i in range(n)]
