"""Arithmetic on samples: medians, percentiles, spreads.  Numpy only.

``percentile`` is the arithmetic of ``serving.driver.percentiles`` (numpy's
linear interpolation), copied so that the yardstick does not move when the
program's own summary does."""

from __future__ import annotations

from typing import Optional, Sequence

import numpy as np


def percentile(values: Sequence[float], p: float) -> Optional[float]:
    """The ``p``-th percentile (linear interpolation), None when empty."""
    if len(values) == 0:
        return None
    return float(np.percentile(np.asarray(list(values), dtype=float), p))


def median(values: Sequence[float]) -> Optional[float]:
    return percentile(values, 50)


def mean(values: Sequence[float]) -> Optional[float]:
    if len(values) == 0:
        return None
    return float(np.mean(np.asarray(list(values), dtype=float)))


def spread(values: Sequence[float]) -> Optional[float]:
    """Distance between the quartiles over the median — the driver's
    measure of how far runs of the same code disagree."""
    if len(values) == 0:
        return None
    q1, q2, q3 = np.percentile(np.asarray(list(values), dtype=float),
                               (25, 50, 75))
    return float((q3 - q1) / q2) if q2 else None
