"""The comparison that decides ``correct``: the widest gap between what
the timed path produced and the plain reference."""
from __future__ import annotations

import numpy as np


def series_gap(got, ref) -> float:
    """Widest gap between two (intervals, columns) telemetry series,
    each column's gap taken as a share of the reference column's largest
    magnitude (a column that is zero throughout must be zero); a shape
    mismatch is a gap of infinity."""
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    if got.shape != ref.shape or not np.isfinite(got).all():
        return float("inf")
    scale = np.abs(ref).max(axis=0)
    diff = np.abs(got - ref).max(axis=0)
    zero = scale == 0
    if (diff[zero] > 0).any():
        return float("inf")
    return float((diff[~zero] / scale[~zero]).max(initial=0.0))


def summary_gap(got: dict, ref: dict) -> float:
    """Widest relative gap over the reference summary's metrics."""
    worst = 0.0
    for k, r in ref.items():
        g = got.get(k)
        if g is None or not np.isfinite(g):
            return float("inf")
        d = abs(float(g) - float(r))
        if d > 0:
            worst = max(worst, d / abs(r) if r else float("inf"))
    return worst
