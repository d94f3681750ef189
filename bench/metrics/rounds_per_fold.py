"""Mean MapReduce rounds (eq. 8 loop) of the snapshots the tenants
published in the window."""


def read(run):
    lo, hi = run.records["open"], run.records["close"]
    rounds = [v["rounds"] for vs in run.records["versions"].values()
              for v in vs.values() if lo <= v["seen_s"] <= hi]
    return sum(rounds) / len(rounds) if rounds else None
