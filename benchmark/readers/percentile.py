"""A percentile over ALL samples of one kind taken in the window: params
{"samples": key of rec["samples"], "q": 0..100}. Nothing to read: None."""
import numpy as np


def read(params, rec, ctx):
    xs = rec["samples"].get(params["samples"], [])
    if not len(xs):
        return None
    return float(np.percentile(np.asarray(xs, np.float64), params["q"]))
