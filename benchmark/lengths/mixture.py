"""Each length from one of ``parts`` (specs of any distribution, each
with a ``weight``): 90% short answers and 10% long ones, say. ``lo`` and
``hi`` of the mixture span its parts."""

import numpy as np

import traffic_gen


def draw(spec: dict, n: int, rng):
    parts = spec["parts"]
    w = np.array([p["weight"] for p in parts], np.float64)
    which = rng.choice(len(parts), n, p=w / w.sum())
    out = np.zeros(n, np.int64)
    for k, part in enumerate(parts):
        out[which == k] = traffic_gen.draw_lengths(
            part, int((which == k).sum()), rng)
    return out
