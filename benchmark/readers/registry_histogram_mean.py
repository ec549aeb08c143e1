"""Mean of one of the program's histograms over the window: the
difference of its ``_sum`` over the difference of its ``_count``
(exact, where a quantile from its buckets would be interpolated)."""


def read(params, run):
    d = run.registry_delta
    n = d.get(params["histogram"] + "_count", 0.0)
    if n <= 0:
        return None
    return params.get("scale", 1.0) * d[params["histogram"] + "_sum"] / n
