"""Log-normal around ``median`` with log-scale ``sigma``, rounded and
clipped to ``lo..hi``."""

import numpy as np


def draw(spec: dict, n: int, rng):
    x = rng.lognormal(np.log(spec["median"]), spec["sigma"], n)
    return np.clip(np.rint(x), spec["lo"], spec["hi"]).astype(np.int64)
