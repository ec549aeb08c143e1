"""An arithmetic expression over the runner's values and the device's
published peaks (names as in ``RunResult.values``)."""


def read(params, run):
    names = dict(run.values)
    try:
        return float(eval(params["expr"], {"__builtins__": {}}, names))
    except (KeyError, NameError, ZeroDivisionError):
        return None
