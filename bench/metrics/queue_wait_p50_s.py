"""Median seconds the window's batches waited between submission and
admission to a wave."""
from bench.metrics._common import quantile


def read(run):
    waits = [b["admitted"] - b["submitted"] for b in run.records["batches"]
             if b["admitted"] is not None]
    return quantile(waits, 0.5)
