"""The benchmark's own arithmetic on samples: the percentile rule, the
spread the driver uses, and the seed-independent draws of lengths and
arrival gaps."""

from __future__ import annotations

import math
import statistics

import numpy as np

_NORMAL = statistics.NormalDist()

#: A percentile is reported only where at least this many samples lie
#: beyond it; otherwise the metric is absent and the run says so.
MIN_BEYOND = 10


def percentile(samples, q: float):
    """Nearest-rank ``q`` (0..1) of ``samples``, or None where fewer
    than :data:`MIN_BEYOND` samples lie beyond it."""
    n = len(samples)
    if n == 0:
        return None
    rank = math.ceil(q * n)              # 1-based nearest rank
    if n - rank < MIN_BEYOND:
        return None
    return float(sorted(samples)[rank - 1])


def spread(values) -> float:
    """Distance between the first and third quartile as a share of the
    median — the contract's measure (``statistics.quantiles(n=4)``)."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def lognormal_quantiles(n: int, median: float, sigma: float,
                        lo: int, hi: int) -> np.ndarray:
    """``n`` whole numbers at the mid-quantiles of a log-normal
    distribution clipped to [lo, hi]: the same multiset whatever the
    seed, so the seed changes the order of the work and not the work."""
    p = (np.arange(n) + 0.5) / n
    z = np.array([_NORMAL.inv_cdf(q) for q in p])
    x = median * np.exp(sigma * z)
    return np.clip(np.rint(x), lo, hi).astype(np.int64)


def exponential_quantiles(n: int, mean: float) -> np.ndarray:
    """``n`` gaps at the mid-quantiles of an exponential distribution,
    rescaled to sum to exactly ``n * mean``: a Poisson process's gaps as
    a fixed multiset, to be put in a seeded order."""
    p = (np.arange(n) + 0.5) / n
    g = -np.log1p(-p)
    return g * (n * mean / g.sum())


def length_pairs(n: int, prompt: dict, output: dict, total_max: int):
    """The cell's multiset of (prompt, output) lengths: each marginal at
    its quantiles, paired by a FIXED permutation (seed 0) so the pairing
    too is the same for every run seed.  A pair whose sum would pass
    ``total_max`` has its output cut to fit."""
    pl = lognormal_quantiles(n, prompt["median"], prompt["sigma"],
                             prompt["min"], prompt["max"])
    ol = lognormal_quantiles(n, output["median"], output["sigma"],
                             output["min"], output["max"])
    ol = ol[np.random.default_rng(0).permutation(n)]
    ol = np.minimum(ol, total_max - pl)
    if (ol < 1).any():
        raise ValueError("a prompt leaves no room for any output token")
    return pl, ol


def seed31(seed: int) -> int:
    """Any whole ``--seed`` (the driver's pass 2**31) folded to 31 bits
    for the APIs that want a C int."""
    return int(np.random.SeedSequence(int(seed)).generate_state(1)[0] >> 1)
