"""Device trace: collective time during which no compute operation runs
on that device, as a share of the traced window."""


def read(params, run):
    tr = run.trace
    if tr is None or tr.window_s <= 0 or tr.collective_s <= 0:
        return None
    return 100.0 * tr.collective_exposed_s / tr.window_s
