"""Arithmetic the benchmark owns: sample percentiles and the windowed
delta of the broker's histograms."""

from __future__ import annotations

import numpy as np


def percentile(values, q: float) -> float:
    """The q-quantile (0..1) of a sample, linear between order
    statistics (numpy's default)."""
    a = np.asarray(values, dtype=np.float64)
    if not len(a):
        return float("nan")
    return float(np.quantile(a, q))


def hist_delta(start: dict, end: dict) -> dict:
    """Window delta of two histogram snapshots ``{"count", "sum_ns"}``:
    the observations and their exact sum in ns between the two."""
    return {"count": int(end["count"] - start["count"]),
            "sum_ns": int(end["sum_ns"] - start["sum_ns"])}
