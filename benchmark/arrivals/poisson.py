"""Poisson arrivals: exponential gaps at `rate_per_s`. A mix names an
arrival process by the name of its module here (`"process": "poisson"`)."""
import numpy as np


def gap_quantiles(arrivals: dict, q: np.ndarray) -> np.ndarray:
    """The inter-arrival gap, in seconds, at each probability of `q`."""
    return -np.log1p(-q) / float(arrivals["rate_per_s"])
