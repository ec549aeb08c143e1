"""One labelled counter's share of a sum over the window, in percent:
``part / (part + rest...)``. Each side is written as
``registry_counter_sum_ratio`` writes one; a ``part`` the program does
not feed reads nothing."""

from readers.registry_counter_sum_ratio import _total


def read(params, run):
    part = _total(run.registry_delta, params["part"])
    if part is None:
        return None
    whole = part + sum(_total(run.registry_delta, side) or 0.0
                       for side in params["rest"])
    return 100.0 * part / whole if whole > 0 else None
