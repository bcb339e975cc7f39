"""Poisson arrivals at the mix's ``rate_per_s``: the exponential
distribution's quantile gaps ``(k + 1/2) / N``, in an order drawn from
the seed, so that every order holds the same set of gaps."""
import math

import numpy as np


def dues(mix: dict, seconds: float, seed: int):
    """Due times in ``[0, seconds)``, seconds after the window opens."""
    rate = float(mix["rate_per_s"])
    if rate <= 0:
        raise ValueError(f"rate_per_s must be positive, got {rate}")
    n = int(math.ceil(rate * seconds * 1.5)) + 16
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q) / rate
    np.random.default_rng(np.random.SeedSequence([int(seed), 2])).shuffle(
        gaps)
    d = np.cumsum(gaps) - gaps[0]
    return [float(x) for x in d if x < seconds]
