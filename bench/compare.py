"""The comparison that decides ``correct``.

A fold (and a batch fit, which is a fold with nothing carried) is one
job: rows, labels and a mask. The reference makes the job's rows again
from the seed and fits it in float32, to its own eq. 8 stop. From the
program it takes only the row numbers the SV buffer kept (never their
values), for the consolidated solve. Each number is a worst case over
the jobs compared:

``sv``     share of the SV buffer's rows after the last round that differ
           from the reference's (the merge's top-k);
``risk``   relative gap of the selected hypothesis' risk to the least
           risk the reference reached (eq. 6-7 and the best-round pick);
``rounds`` rounds the program ran less the rounds the reference ran
           to its own eq. 8 stop (or ``max_rounds``), absolute;
``w``      relative gap of the selected hypothesis ``(w, b)`` to the
           nearest reference hypothesis whose risk is within ``RISK_TIE``
           of the least (a near tie of eq. 7 may go either way);
``final``  relative gap of the consolidated model ``(w, b)`` to the
           reference's solve on the program's SV rows.

A relative gap is ``‖a − r‖ / ‖r‖``. Beside the numbers, each job gives
the reference's closest calls of the top-k merge over its rounds
(``reference.closest_call``: the least gap in α between the last row
kept and the first left out, and the number of ties at the bound C),
which say how near a tie came; they are reported, not compared.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

from bench import reference as ref

ORDER = ("sv", "risk", "rounds", "w", "final")
# relative risk within which two hypotheses of eq. 7 count alike, for
# ``w``: the program's selected risk lies within 1.7e-5 of the
# reference's least on the chip, the bfloat16 control's 5e-3 and more
RISK_TIE = 2e-4


def rel_gap(a, r) -> float:
    a = np.asarray(a, np.float64).ravel()
    r = np.asarray(r, np.float64).ravel()
    return float(np.linalg.norm(a - r) / max(np.linalg.norm(r), 1e-30))


def _wb(w, b):
    return np.concatenate([np.asarray(w, np.float64).ravel(),
                           np.asarray(b, np.float64).ravel()])


def id_mismatch(p_ids, r_ids) -> float:
    p = set(int(i) for i in np.asarray(p_ids) if i >= 0)
    r = set(int(i) for i in np.asarray(r_ids) if i >= 0)
    return len(p ^ r) / max(len(p), len(r), 1)


def fold_numbers(rows, y, mask, n: int, cfg: dict, prog: dict,
                 risk_tie: float = RISK_TIE,
                 acc=None) -> Tuple[Dict[str, float], tuple]:
    """Numbers of one job, and the reference's closest calls
    ``(gap, ties)`` over its rounds. ``prog`` holds the program's
    answers: ``ids`` (after the last round),
    ``rounds``, ``risk``, ``w``, ``b``, ``final_w``, ``final_b``.
    ``risk_tie`` is the relative risk within which two hypotheses count
    as tied."""
    import jax.numpy as jnp
    acc = jnp.float32 if acc is None else acc
    fit = ref.fit(rows, y, mask, n, cfg, acc=acc)
    best = min(float(np.min(r)) for r in fit.risks)
    pw = _wb(prog["w"], prog["b"])
    w_gap = np.inf
    for t, r in enumerate(fit.risks):
        for l in np.flatnonzero(r <= best * (1 + risk_tie)):
            cand = _wb(fit.ws[t][l], fit.bs[t][l])
            w_gap = min(w_gap, rel_gap(pw, cand))
    fw, fb, _ = ref.final(rows, y, n, prog["ids"], cfg, acc=acc)
    return {
        "sv": id_mismatch(prog["ids"], fit.sv_ids[-1]),
        "risk": abs(float(prog["risk"]) - best) / max(best, 1e-30),
        "rounds": float(abs(int(prog["rounds"]) - fit.rounds)),
        "w": float(w_gap),
        "final": rel_gap(_wb(prog["final_w"], prog["final_b"]), _wb(fw, fb)),
    }, (min(g for g, _ in fit.gaps), sum(t for _, t in fit.gaps))


def worst(per_job: List[Dict[str, float]]) -> Dict[str, float]:
    out: Dict[str, float] = {}
    for nums in per_job:
        for k, v in nums.items():
            out[k] = max(out.get(k, 0.0), v)
    return out


def verdict(numbers: Dict[str, float], limits: Dict[str, float]):
    """``(correct, checks)``: every number at most its limit. A number
    with no limit, or a limit with no number, is not correct."""
    checks = {}
    ok = True
    for k in sorted(set(numbers) | set(limits),
                    key=lambda k: (ORDER.index(k) if k in ORDER
                                   else len(ORDER), k)):
        v, lim = numbers.get(k), limits.get(k)
        checks[k] = {"value": v, "limit": lim}
        if v is None or lim is None or not np.isfinite(v) or v > lim:
            ok = False
    return ok, checks
