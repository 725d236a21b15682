"""Percentiles and Jain's index.

``jain`` is copied from the program's ``repro.core.metrics.jain`` and
``OUT_TOKEN_WEIGHT`` from ``repro.core.counters`` (paper section 3.1:
an output token is billed as four input tokens), so that a change to the
program cannot move the yardstick.
"""
from __future__ import annotations

import math

import numpy as np

OUT_TOKEN_WEIGHT = 4.0


def percentile(xs, q: float) -> float:
    """The ``q``-th percentile by linear interpolation between closest
    ranks (numpy's default), or NaN for no samples."""
    xs = np.asarray(list(xs), float)
    if xs.size == 0:
        return math.nan
    return float(np.percentile(xs, q))


def jain(xs) -> float:
    """Jain's index over the finite values: 1 when all are equal, 1/n
    when one holds everything; 1 for no values or all zeros."""
    xs = np.asarray([x for x in xs if np.isfinite(x)], float)
    if len(xs) == 0 or np.all(xs == 0):
        return 1.0
    return float(xs.sum() ** 2 / (len(xs) * np.sum(xs ** 2)))


def weighted_service(prefilled: int, generated: int) -> float:
    """Delivered service: prompt tokens prefilled plus output tokens
    times ``OUT_TOKEN_WEIGHT``."""
    return prefilled + OUT_TOKEN_WEIGHT * generated
