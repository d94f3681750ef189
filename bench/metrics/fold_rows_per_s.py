"""Rows folded per second: the rows of the waves that folded the
window's batches, over the time from the first of those batches' due
time (when its client began to make and submit it) to the last wave's
completion. Each wave in it is whole, with its own submit, so the rate
does not depend on how many waves the window holds."""
from bench.metrics._common import window_waves


def read(run):
    waves = window_waves(run)
    if not waves:
        return None
    start = min(b["due"] for b in run.records["batches"])
    end = max(w["completed"] for w in waves)
    return sum(w["rows"] for w in waves) / (end - start)
