"""A percentile over ALL samples of one kind taken in the window, by the
Harrell-Davis estimator: the weighted mean of the order statistics whose
weights are the Beta((n+1)q, (n+1)(1-q)) mass of each sample's share of
[0, 1]. params {"samples": key of rec["samples"], "q": 0..100}.

For some tens of samples: there a plain percentile is ONE sample (the
22nd of 31 waits), and one request's wait jitters by a whole engine step
with where its arrival falls in it; this one averages the handful of
order statistics around the percentile. Nothing to read: None."""
import numpy as np
from scipy.special import betainc


def estimate(xs, q: float) -> float:
    x = np.sort(np.asarray(xs, np.float64))
    n = len(x)
    a, b = (n + 1) * q, (n + 1) * (1.0 - q)
    w = np.diff(betainc(a, b, np.arange(n + 1) / n))
    return float(w @ x)


def read(params, rec, ctx):
    xs = rec["samples"].get(params["samples"], [])
    if not len(xs):
        return None
    return estimate(xs, params["q"] / 100.0)
