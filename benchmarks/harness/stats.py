"""The arithmetic every metric uses, over raw samples (never over histogram
buckets): median, percentile by linear interpolation between the two nearest
ranks, and the geometric mean."""

from __future__ import annotations

import numpy as np


def percentile(samples: list, q: float) -> float:
    """q in [0, 1]."""
    if not len(samples):
        raise ValueError("no samples")
    return float(np.percentile(samples, 100.0 * q))


def median(samples: list) -> float:
    return percentile(samples, 0.5)


def geomean(values: list) -> float:
    return float(np.exp(np.mean(np.log(values))))
