"""Plain reference of the MapReduce-SVM fit, written from the paper
(Çatak 2014, eq. 6-9) and the repository's documented semantics, in
straightforward ``jax.numpy``. It imports nothing of the program.

One *job* is a matrix of rows with labels and a mask. A fit pads the
job to ``L·per`` rows, partitions it (row ``g`` lives on partition
``g // per``), and loops rounds:

* each partition solves the L1-loss dual of the soft-margin SVM by dual
  coordinate descent (bias as a constant feature, rows walked in order,
  home rows first and then the global SV buffer's rows, epochs until the
  largest projected gradient is at most ``tol`` or ``max_epochs``);
* a row's SV evidence is the largest α over its copies; each partition
  keeps its ``cap / L`` rows of largest evidence (ties to the lower row),
  those above ``sv_threshold`` form the next SV buffer;
* every partition's hypothesis is scored by the mean hinge loss over
  the whole job (eq. 6); the round's hypothesis is the partition of
  least risk (eq. 7), the fit keeps the round of least risk, and stops
  when two rounds' risks differ by at most γ (eq. 8);
* the consolidated model is one more solve on the SV buffer alone.

Rows are held once, with a zero row at the end (an empty SV slot reads
it): a dense ``(n + 1, d)`` array, or blocked-CSR rows (``Csr``: column
ids and values, ``(n + 1, nnz_cap)`` each, a dead slot ``(0, 0.0)``).
A CSR row step gathers ``w`` at the row's columns for ``w·x`` and
scatter-adds its update there, ``Q_ii`` sums the row's squared values,
and the eq. 6 scores gather each hypothesis at the columns, one
hypothesis at a time; so no array of the CSR path has a row axis and the
feature axis together, and a job of any width needs beside its rows only
the ``L × d`` hypotheses. A partition's solve walks its home rows in
place and then one shared copy of the SV buffer's rows, so no union is
copied. ``acc`` is the precision of the solver's state and of its
products (and of the rows' values): float32 for the reference, bfloat16
for its control.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


class Csr(NamedTuple):
    """Blocked-CSR rows: ``cols`` (int32) and ``vals``, ``(..., n,
    nnz_cap)`` each; the columns of a row's live slots are distinct."""
    cols: jax.Array
    vals: jax.Array


def rows_from(X, dtype):
    """Job rows with the zero row appended, values in ``dtype``: a dense
    ``(n, d)`` array, or a ``Csr`` from a pair ``(cols, vals)``."""
    if isinstance(X, tuple):
        cols, vals = (jnp.asarray(a) for a in X)
        return Csr(jnp.concatenate([cols.astype(jnp.int32),
                                    jnp.zeros((1, cols.shape[1]),
                                              jnp.int32)]),
                   rows_from(vals, dtype))
    X = jnp.asarray(X).astype(dtype)
    return jnp.concatenate([X, jnp.zeros((1, X.shape[1]), dtype)])


def _rows(rows, f):
    """``f`` applied to the row arrays: the dense array, or both of a
    ``Csr``'s."""
    return jax.tree_util.tree_map(f, rows)


def _count(rows) -> int:
    return jax.tree_util.tree_leaves(rows)[0].shape[0]


def _sq_norms(B, acc):
    """``Q_ii`` less the bias' 1: the squared values of each row."""
    v = B.vals if isinstance(B, Csr) else B
    return jnp.sum(jnp.square(v.astype(acc)), axis=1)


def solve(blocks, y, m, d: int, C, tol, max_epochs: int, acc):
    """Dual CD over the rows of ``blocks`` (a tuple of ``(n_j, d)``
    arrays or of ``Csr`` rows), walked in order, block after block.
    Returns ``(alpha, w, b, epochs)``."""
    sizes = [_count(B) for B in blocks]
    offs = [sum(sizes[:j]) for j in range(len(blocks))]
    n = sum(sizes)
    y = y.astype(acc)
    m = m.astype(acc)
    q = jnp.concatenate([_sq_norms(B, acc) for B in blocks])
    q = jnp.where(m > 0, q + 1.0, 1.0)
    C = jnp.asarray(C, acc)

    def walk(B, off):
        csr = isinstance(B, Csr)

        def step(r, s):
            alpha, w, b, viol = s
            i = r + off
            if csr:
                c = jax.lax.dynamic_index_in_dim(B.cols, r, keepdims=False)
                x = jax.lax.dynamic_index_in_dim(B.vals, r, keepdims=False)
                x = x.astype(acc)
                wx = jnp.sum(w[c] * x)
            else:
                x = jax.lax.dynamic_index_in_dim(B, r, keepdims=False)
                x = x.astype(acc)
                wx = jnp.sum(w * x)
            g = y[i] * (wx + b) - 1.0
            a = alpha[i]
            pg = jnp.where(a <= 0, jnp.minimum(g, 0.0),
                           jnp.where(a >= C, jnp.maximum(g, 0.0), g))
            delta = (jnp.clip(a - g / q[i], 0.0, C) - a) * m[i]
            alpha = alpha.at[i].set(a + delta)
            if csr:
                w = w.at[c].add((delta * y[i]) * x)
            else:
                w = w + (delta * y[i]) * x
            b = b + delta * y[i]
            viol = jnp.maximum(viol, jnp.abs(pg) * m[i])
            return alpha, w, b, viol
        return step

    def epoch(s):
        alpha, w, b, _, t = s
        st = (alpha, w, b, jnp.zeros((), acc))
        for B, off, size in zip(blocks, offs, sizes):
            st = jax.lax.fori_loop(0, size, walk(B, off), st)
        alpha, w, b, viol = st
        return alpha, w, b, viol, t + 1

    def more(s):
        viol, t = s[3], s[4]
        return (t < max_epochs) & ((t == 0) | (viol > tol))

    s = (jnp.zeros((n,), acc), jnp.zeros((d,), acc), jnp.zeros((), acc),
         jnp.asarray(jnp.inf, acc), jnp.zeros((), jnp.int32))
    alpha, w, b, _, t = jax.lax.while_loop(more, epoch, s)
    return alpha, w, b, t


def scores(rows, n: int, W, B):
    """``(n, k)`` decision values of the first ``n`` rows under the
    columns of ``W`` ``(d, k)``."""
    if isinstance(rows, Csr):
        cols, vals = rows.cols[:n], rows.vals[:n].astype(W.dtype)
        s = jax.lax.map(lambda wk: jnp.sum(wk[cols] * vals, axis=1), W.T)
        return s.T + B[None, :]
    X = rows[:n].astype(W.dtype)
    return jnp.matmul(X, W, precision=HIGHEST) + B[None, :]


def closest_call(evidence, k: int, thr, C):
    """The top-k merge's closest calls over the rows of ``evidence``
    ``(L, per)``, counting only partitions whose first row left out
    would also have been an SV: ``(gap, ties)``. ``ties`` is the number
    of those whose row left out sits at the bound C as the last row kept
    does (both are clipped to C exactly, and the lower row is kept);
    ``gap`` is the least α kept less the most left out over the others
    (inf where there is none), a call that rounding could turn."""
    if evidence.shape[1] <= k:
        return (jnp.asarray(jnp.inf, evidence.dtype),
                jnp.zeros((), jnp.int32))
    top = jax.lax.top_k(evidence, k + 1)[0]
    kept, out = top[:, k - 1], top[:, k]
    live = out > thr
    at_c = live & (out >= C)
    gap = jnp.min(jnp.where(live & ~at_c, kept - out, jnp.inf))
    return gap, jnp.sum(at_c.astype(jnp.int32))


@functools.partial(jax.jit, static_argnames=("L", "per", "cap", "d",
                                             "max_epochs", "acc"))
def fit_round(rows, yp, mp, sv_ids, sv_mask, params, *, L, per, cap,
              d, max_epochs, acc):
    """One MapReduce round. ``yp``/``mp`` are ``(L, per)``; the SV buffer
    is ``cap`` job row numbers (-1: empty slot) and its mask."""
    C, tol, thr = params
    null = _count(rows) - 1
    tail = _rows(rows, lambda a: a[jnp.where(sv_ids >= 0, sv_ids, null)])
    flat_y = yp.reshape(-1)
    sv_y = jnp.where(sv_ids >= 0, flat_y[jnp.maximum(sv_ids, 0)], 0.0)
    home = _rows(rows, lambda a: a[:L * per].reshape((L, per)
                                                    + a.shape[1:]))

    def part(home_l, y_home, m_home):
        return solve((home_l, tail), jnp.concatenate([y_home, sv_y]),
                     jnp.concatenate([m_home, sv_mask]), d, C, tol,
                     max_epochs, acc)

    alpha, w, b, _ = jax.vmap(part)(home, yp, mp)
    home = alpha[:, :per].reshape(-1)
    buf = jnp.max(alpha[:, per:], axis=0) * sv_mask.astype(acc)
    folded = jnp.zeros_like(home).at[jnp.maximum(sv_ids, 0)].max(
        jnp.where(sv_ids >= 0, buf, 0.0))
    home = jnp.maximum(home, folded).reshape(L, per) * mp.astype(acc)
    k = cap // L
    gap = closest_call(home, k, thr, C)
    topv, topi = jax.lax.top_k(home, k)
    live = topv > thr
    ids = jnp.arange(L)[:, None] * per + topi
    new_ids = jnp.where(live, ids, -1).reshape(-1).astype(jnp.int32)
    s = scores(rows, L * per, w.T, b)                      # (n, L)
    yf, mf = flat_y.astype(acc), mp.reshape(-1).astype(acc)
    loss = jnp.maximum(0.0, 1.0 - yf[:, None] * s) * mf[:, None]
    risks = jnp.sum(loss, axis=0) / jnp.maximum(jnp.sum(mf), 1.0)
    return (new_ids, live.reshape(-1).astype(jnp.float32), risks, w, b,
            gap)


@functools.partial(jax.jit, static_argnames=("d", "max_epochs", "acc"))
def fit_final(rows, flat_y, sv_ids, params, *, d, max_epochs, acc):
    """The consolidated solve on the SV buffer's rows alone."""
    C, tol, _ = params
    block = _rows(rows, lambda a: a[jnp.where(sv_ids >= 0, sv_ids,
                                              _count(rows) - 1)])
    y = jnp.where(sv_ids >= 0, flat_y[jnp.maximum(sv_ids, 0)], 0.0)
    m = (sv_ids >= 0).astype(acc)
    alpha, w, b, t = solve((block,), y, m, d, C, tol, max_epochs, acc)
    return w, b, t


class Fit(NamedTuple):
    risks: list        # per round: (L,) risks
    ws: list           # per round: (L, d)
    bs: list           # per round: (L,)
    sv_ids: list       # per round: (cap,) ids after the round
    gaps: list         # per round: the merge's closest call (gap, ties)
    rounds: int        # rounds run
    best_round: int
    best_part: int


def fit(rows, y, mask, n: int, cfg: dict, acc=jnp.float32) -> Fit:
    """Loop rounds over the job's first ``n`` rows until eq. 8 holds or
    ``max_rounds`` have run."""
    L, cap = int(cfg["partitions"]), int(cfg["sv_capacity"])
    per = -(-n // L)
    d = int(cfg["num_features"])
    pad = L * per - n
    yp = jnp.pad(jnp.asarray(y, jnp.float32), (0, pad)).reshape(L, per)
    mp = jnp.pad(jnp.asarray(mask, jnp.float32), (0, pad)).reshape(L, per)
    if pad:                     # row numbers past n must be zero rows
        rows = _pad_rows(rows, n, pad)
    params = (jnp.asarray(cfg["C"], acc), jnp.asarray(cfg["tol"], acc),
              jnp.asarray(cfg["sv_threshold"], acc))
    ids = -jnp.ones((cap,), jnp.int32)
    smask = jnp.zeros((cap,), jnp.float32)
    out = Fit([], [], [], [], [], 0, 0, 0)
    best, prev = np.inf, np.inf
    for t in range(int(cfg["max_rounds"])):
        ids, smask, risks, w, b, gap = fit_round(
            rows, yp, mp, ids, smask, params, L=L, per=per, cap=cap, d=d,
            max_epochs=int(cfg["max_epochs"]), acc=acc)
        r = np.asarray(risks, np.float64)
        out.risks.append(r)
        out.ws.append(w)
        out.bs.append(b)
        out.sv_ids.append(np.asarray(ids))
        out.gaps.append((float(gap[0]), int(gap[1])))
        l_star = int(np.argmin(r))
        if r[l_star] < best:
            best = r[l_star]
            out = out._replace(best_round=t, best_part=l_star)
        out = out._replace(rounds=t + 1)
        if t > 0 and abs(prev - r[l_star]) <= float(cfg["gamma"]):
            break
        prev = r[l_star]
    return out


def _pad_rows(rows, n: int, pad: int):
    """Insert ``pad`` zero rows after the job's ``n`` rows."""
    def one(a):
        z = jnp.zeros((pad,) + a.shape[1:], a.dtype)
        return jnp.concatenate([a[:n], z, a[n:]])
    return _rows(rows, one)


def final(rows, y, n: int, sv_ids, cfg: dict, acc=jnp.float32):
    """The consolidated model ``(w, b, epochs)`` on the SV buffer
    ``sv_ids`` (job row numbers, -1 empty) of a job of ``n`` rows."""
    L = int(cfg["partitions"])
    per = -(-n // L)
    pad = L * per - n
    if pad:
        rows = _pad_rows(rows, n, pad)
    flat_y = jnp.pad(jnp.asarray(y, jnp.float32), (0, pad))
    params = (jnp.asarray(cfg["C"], acc), jnp.asarray(cfg["tol"], acc),
              jnp.asarray(cfg["sv_threshold"], acc))
    return fit_final(rows, flat_y, jnp.asarray(sv_ids, jnp.int32), params,
                     d=int(cfg["num_features"]),
                     max_epochs=int(cfg["max_epochs"]), acc=acc)
