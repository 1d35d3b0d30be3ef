"""Poisson arrivals: exponential gaps at the cell's rate.

The gaps are the exponential distribution's quantiles at levels
``(i + 0.5) / n``, so every seed offers the same gaps; the generator puts
them in the seed's order.
"""

from __future__ import annotations

import numpy as np

#: keys of the mix's ``arrival_params`` this kind reads
PARAMS: tuple[str, ...] = ()


def gaps(levels: np.ndarray, rate: float, params: dict) -> np.ndarray:
    """Seconds between consecutive requests, one per quantile level."""
    return -np.log1p(-levels) / rate
