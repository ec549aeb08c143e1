"""Bursty arrivals: gamma-distributed gaps with coefficient of variation
``cv`` (1 is Poisson; 3 is long silences between bursts), conditioned on
the count like ``poisson``: the gaps are scaled so that the window holds
exactly ``round(rate * seconds)`` arrivals."""

import numpy as np


def times(spec: dict, seconds: float, rate: float, rng) -> np.ndarray:
    n = max(int(round(rate * seconds)), 1)
    shape = 1.0 / float(spec["cv"]) ** 2
    gaps = rng.gamma(shape, 1.0, n + 1)     # the last gap closes the window
    return np.cumsum(gaps[:n]) / gaps.sum() * seconds
