"""Percentiles and rates over one run's requests."""
from __future__ import annotations

from typing import Optional, Sequence

import numpy as np


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """The ``q``-th percentile (linear between order statistics), or None
    for no values."""
    if len(values) == 0:
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))


def mean(values: Sequence[float]) -> Optional[float]:
    return float(np.mean(values)) if len(values) else None


def rate(count: float, seconds: float) -> Optional[float]:
    return count / seconds if seconds > 0 else None


def share(part: float, whole: float) -> Optional[float]:
    """``part / whole`` in percent, or None when there is no whole."""
    return 100.0 * part / whole if whole > 0 else None
