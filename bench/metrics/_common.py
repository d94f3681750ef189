"""Shared arithmetic of the metric readers."""
from __future__ import annotations

import statistics


def window_waves(run):
    """The waves that folded the window's batches."""
    ids = {b["wave"] for b in run.records["batches"] if b["wave"] is not None}
    return [w for w in run.records["waves"] if w["wave"] in ids]


def quantile(values, q: float):
    """The ``q`` quantile by Python's ``statistics.quantiles``
    (exclusive method), or the one value there is."""
    values = sorted(values)
    if not values:
        return None
    if len(values) == 1:
        return values[0]
    cuts = statistics.quantiles(values, n=100)
    return cuts[int(round(q * 100)) - 1]
