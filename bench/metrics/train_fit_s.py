"""Seconds per converged fit: the window, from the first fit's start
to the last one's end, over the fits completed in it."""


def read(run):
    fits = run.records["fits"]
    if not fits:
        return None
    return (max(f["end"] for f in fits)
            - min(f["start"] for f in fits)) / len(fits)
