"""Device trace: self time of the operations whose name (or long name)
matches any of ``patterns``, as a share of device busy time."""


def read(params, run):
    tr = run.trace
    if tr is None or tr.busy_s <= 0:
        return None
    return 100.0 * tr.seconds_matching(params["patterns"], params.get("opcode")) / tr.busy_s
