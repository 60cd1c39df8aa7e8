"""Log-normal sizes: spec {"median", "sigma"}. A mix names a distribution
by the name of its module here (`"dist": "lognormal"`)."""
from statistics import NormalDist

import numpy as np


def quantiles(spec: dict, q: np.ndarray) -> np.ndarray:
    """The distribution's value at each probability of `q` (unclipped)."""
    z = np.array([NormalDist().inv_cdf(float(p)) for p in q])
    return spec["median"] * np.exp(spec["sigma"] * z)
