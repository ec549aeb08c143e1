"""Device trace: 1 - (union of operation intervals) / window, averaged
over the devices used."""


def read(params, run):
    tr = run.trace
    if tr is None or tr.window_s <= 0:
        return None
    return 100.0 * (1.0 - tr.busy_s / tr.window_s)
