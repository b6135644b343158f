"""Poisson arrivals: exponential gaps at ``rate_per_s``."""

import numpy as np


def quantile(spec: dict, u: np.ndarray) -> np.ndarray:
    return -np.log1p(-u) / spec["rate_per_s"]
