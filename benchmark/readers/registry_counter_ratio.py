"""Ratio of two of the program's counters over the window, in percent:
``numerator / denominator``, or ``1 - that`` with ``one_minus``."""


def read(params, run):
    d = run.registry_delta
    den = d.get(params["denominator"], 0.0)
    if den <= 0:
        return None
    r = d.get(params["numerator"], 0.0) / den
    if params.get("one_minus"):
        r = 1.0 - r
    return 100.0 * r
