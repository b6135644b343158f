"""Lognormal lengths: ``median`` and ``sigma`` of the underlying normal."""

from statistics import NormalDist

import numpy as np


def quantile(spec: dict, u: np.ndarray) -> np.ndarray:
    z = np.array([NormalDist().inv_cdf(p) for p in u])
    return spec["median"] * np.exp(spec["sigma"] * z)
