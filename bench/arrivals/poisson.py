"""Poisson arrivals at the mix's ``rate_per_s``.

The gaps are the ``n = rate·seconds`` quantiles of the exponential
distribution, shuffled by the seed: the same count and the same gaps for
every seed, in another order.
"""
import numpy as np


def due(mix: dict, g: np.random.Generator, seconds: float) -> np.ndarray:
    rate = mix["rate_per_s"]
    n = int(round(rate * seconds))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    return np.cumsum(g.permutation(gaps))
