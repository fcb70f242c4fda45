"""Order statistics used by the metrics, in one place."""

from __future__ import annotations

import numpy as np


def percentile(values, q: float) -> float | None:
    """The q-th percentile (linear interpolation between order
    statistics), or None for no samples."""
    v = np.asarray(list(values), np.float64)
    if v.size == 0:
        return None
    return float(np.percentile(v, q))


def mean(values) -> float | None:
    v = np.asarray(list(values), np.float64)
    return float(v.mean()) if v.size else None
