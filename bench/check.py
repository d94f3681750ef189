"""After the window: pick the folds or fits to compare, free the
program's state, and compare each with the reference.

A sample of ``compare_folds`` (``compare_fits``) jobs is drawn from the
seed among those the window completed; the last fold of the first
tenant is always in it, since its chain of folds is the longest.
Folds whose versions were not all seen, or that the program never
published, count in ``unchecked``, whose limit is 0.
"""
from __future__ import annotations

import gc
from typing import Callable, Dict, List, NamedTuple, Tuple

import numpy as np

from bench import compare
from bench.chain import Chain, job_rows


def _host(v):
    return np.asarray(v)


def _rng(seed: int, salt: int):
    return np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, salt])


class Job(NamedTuple):
    """One fold or fit to compare: ``make(acc)`` gives the reference's
    ``(rows, y, mask, n)`` with values in ``acc``; ``prog`` the
    program's answers."""
    make: Callable
    prog: dict


class Plan(NamedTuple):
    jobs: List[Job]
    extra: Dict[str, float]
    attempted: int
    failed: int


def collect(driver, records, cfg, tr, seed) -> Plan:
    """Pick the jobs to compare, take the program's answers to the host
    and free the program's state."""
    if tr["mode"] == "train":
        return _train(driver, records, cfg, tr, seed)
    return _serve(driver, records, cfg, tr, seed)


def numbers(plan: Plan, cfg) -> Tuple[Dict[str, float], dict]:
    """The compared numbers, worst over the jobs, and the reference's
    closest calls of the top-k merge over them: ``{"sv_gap": least gap
    or None, "sv_ties": ties at the bound}``."""
    import jax.numpy as jnp
    per_job, gaps, ties = [], [], 0
    for job in plan.jobs:
        rows, y, mask, n = job.make(jnp.float32)
        nums, (gap, tie) = compare.fold_numbers(rows, y, mask, n, cfg,
                                                job.prog)
        per_job.append(nums)
        gaps.append(gap)
        ties += tie
        del rows
    nums = compare.worst(per_job)
    nums.update(plan.extra)
    gap = min(gaps, default=float("inf"))
    return nums, {"sv_gap": gap if gap < float("inf") else None,
                  "sv_ties": ties}


def control_answers(rows, y, mask, n, cfg, acc) -> dict:
    """The reference in the program's place, computed in ``acc``: its
    own answers, in the form the comparison reads from the program."""
    from bench import reference as ref
    fit = ref.fit(rows, y, mask, n, cfg, acc=acc)
    r = fit.risks[fit.best_round]
    ids = fit.sv_ids[-1]
    fw, fb, _ = ref.final(rows, y, n, ids, cfg, acc=acc)
    return {"ids": ids, "rounds": fit.rounds, "risk": float(np.min(r)),
            "w": _host(fit.ws[fit.best_round][fit.best_part]),
            "b": _host(fit.bs[fit.best_round][fit.best_part]),
            "final_w": _host(fw), "final_b": _host(fb)}


def control_numbers(plan: Plan, cfg, acc) -> Dict[str, float]:
    """The numbers the comparison reads when the control answers in the
    program's place (``plan.extra`` included, as in a run)."""
    import jax.numpy as jnp
    per_job = []
    for job in plan.jobs:
        low = control_answers(*job.make(acc), cfg, acc)
        rows, y, mask, n = job.make(jnp.float32)
        per_job.append(compare.fold_numbers(rows, y, mask, n, cfg,
                                            low)[0])
    nums = compare.worst(per_job)
    nums.update(plan.extra)
    return nums


def _prog(rec: dict) -> dict:
    return {k: _host(rec[k]) if k != "rounds" else int(rec[k])
            for k in ("ids", "rounds", "risk", "w", "b", "final_w",
                      "final_b")}


def _serve(driver, records, cfg, tr, seed) -> Plan:
    cap = int(cfg["sv_capacity"])
    batch_rows = int(tr["batch_rows"])
    svc = driver.svc
    # which batches each tenant folded in which wave, in queue order
    waves: Dict[int, Dict[int, List[Tuple[int, int]]]] = {}
    for mb in svc.done:
        s, k = driver.batch_of_uid[mb.uid]
        waves.setdefault(mb.wave, {}).setdefault(s, []).append((mb.uid, k))
    window_waves = {b["wave"] for b in records["batches"]
                    if b["wave"] is not None}
    folds: Dict[int, Dict[int, Tuple[List[int], int]]] = {}
    fold_wave: Dict[int, Dict[int, int]] = {}
    for w in sorted(waves):
        group = waves[w]
        longest = max(len(v) for v in group.values()) * batch_rows
        for s, items in group.items():
            ks = [k for _, k in sorted(items)]
            n_job = (longest if len(group) > 1
                     else len(ks) * batch_rows) + cap
            v = len(folds.setdefault(s, {})) + 1
            folds[s][v] = (ks, n_job)
            fold_wave.setdefault(s, {})[v] = w
    seen = {driver.names.index(name): vs
            for name, vs in driver.tap.seen.items()}
    tenants = sorted(seen)
    progs = {s: {v: _prog(r) for v, r in seen[s].items()} for s in tenants}
    attempted = len(records["batches"])
    failed = sum(b["completed"] is None for b in records["batches"])
    rows_model = driver.rows
    chains = {s: Chain(driver.archive_rows, batch_rows, cap,
                       folds.get(s, {}),
                       {u: p["ids"] for u, p in progs[s].items()})
              for s in tenants}

    def maker(s, v):
        return lambda acc: job_rows(rows_model, s, chains[s], v, acc)

    # free the program's state before the reference runs
    driver.stop()
    driver.svc = driver.tap = None
    svc = None
    gc.collect()

    cands = [(s, v) for s in tenants for v, w in fold_wave.get(s, {}).items()
             if w in window_waves]
    unchecked = 0 if cands else 1
    pick = []
    if cands:
        last = max((c for c in cands if c[0] == tenants[0]),
                   key=lambda c: c[1], default=cands[-1])
        rest = [c for c in cands if c != last]
        k = min(int(tr.get("compare_folds", 1)) - 1, len(rest))
        idx = _rng(seed, 13).choice(len(rest), size=k, replace=False)
        pick = [last] + [rest[i] for i in sorted(idx)]
    jobs = []
    for s, v in pick:
        if any(u not in progs[s] for u in range(v + 1)):
            unchecked += 1
            continue
        jobs.append(Job(maker(s, v), progs[s][v]))
    return Plan(jobs, {"unchecked": float(unchecked)}, attempted, failed)


def _train(driver, records, cfg, tr, seed) -> Plan:
    from bench import reference as ref
    fits = driver.fits
    progs = [_prog(f) for f in fits]
    sets = [f["set"] for f in fits]
    rows_model, n = driver.rows, driver.n
    driver.stop()
    gc.collect()
    # fits of one row set are the same computation: they must agree
    repeat = 0.0
    first = {}
    for p, j in zip(progs, sets):
        if j in first:
            q = first[j]
            repeat = max(repeat, float(np.any(p["ids"] != q["ids"])),
                         compare.rel_gap(p["final_w"], q["final_w"]),
                         compare.rel_gap(p["w"], q["w"]))
        else:
            first[j] = p
    k = min(int(tr.get("compare_fits", 1)), len(fits))
    pick = sorted(_rng(seed, 19).choice(len(fits), size=k, replace=False))

    def maker(j):
        def make(acc):
            import jax.numpy as jnp
            X, y = rows_model.make(0, j, n)
            return (ref.rows_from(X, acc), y.astype(jnp.float32),
                    jnp.ones((n,), jnp.float32), n)
        return make

    jobs = [Job(maker(sets[i]), progs[i]) for i in pick]
    return Plan(jobs, {"repeat": repeat,
                           "unchecked": float(0 if pick else 1)},
                len(fits), 0)
