"""A Poisson process conditioned on its expected count: sorted uniform
instants."""

import numpy as np


def times(spec: dict, seconds: float, rate: float, rng) -> np.ndarray:
    n = max(int(round(rate * seconds)), 1)
    return np.sort(rng.uniform(0.0, seconds, n))
