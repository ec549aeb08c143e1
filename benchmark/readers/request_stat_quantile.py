"""A quantile, over the requests that were due in the window and
finished, of one field of the engine's ``request_stats(rid)``."""

import numpy as np


def read(params, run):
    xs = [s[params["stat"]] for s in run.request_stats
          if params["stat"] in s]
    if not xs:
        return None
    return params.get("scale", 1.0) * float(np.quantile(xs, params["q"]))
