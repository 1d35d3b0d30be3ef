"""Percentiles and spreads, the same everywhere in the benchmark."""

from __future__ import annotations

import statistics

import numpy as np


def percentile(values, q: float) -> float | None:
    """The ``q``-th percentile (linear interpolation), None when empty."""
    vals = np.asarray(list(values), np.float64)
    return float(np.percentile(vals, q)) if vals.size else None


def spread(values) -> float:
    """Distance between the first and third quartiles as a share of the
    median, as ``statistics.quantiles(values, n=4)`` gives them."""
    q1, q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / abs(q2) if q2 else float("inf")
