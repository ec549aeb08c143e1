"""Whole numbers, each of ``lo..hi`` equally likely."""


def draw(spec: dict, n: int, rng):
    return rng.integers(spec["lo"], spec["hi"] + 1, n)
