"""Which rows every fold of a tenant trained on, from the seed and the
program's published SV row numbers, and the reference's copy of them.

Each fold of the streaming service is a fresh fit of one job: the
wave's new rows of the tenant, then the ``cap`` rows its last snapshot
carried (slot order; empty slots are zero rows with mask 0), then zero
rows up to the wave's longest job. A published SV row number indexes
that job, so every row a snapshot holds goes back, fold by fold, to a
made batch or to the tenant's archive: ``(batch, row)``, with batch -1
for the archive. The reference makes those batches again.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np

Source = Optional[Tuple[int, int]]


class Chain:
    """``folds[v]`` for v ≥ 1: ``(new batch numbers, job rows)`` of the
    tenant's v-th fold; ``ids[v]``: the program's SV row numbers after
    it (v = 0: the archive fit)."""

    def __init__(self, archive_rows: int, batch_rows: int, cap: int,
                 folds: Dict[int, Tuple[List[int], int]],
                 ids: Dict[int, np.ndarray]):
        self.archive_rows, self.batch_rows, self.cap = (
            archive_rows, batch_rows, cap)
        self.folds, self.ids = folds, ids
        self._src: Dict[int, List[Source]] = {}

    def sources(self, v: int) -> List[Source]:
        """The job rows of version ``v``, each ``(batch, row)`` or None."""
        if v in self._src:
            return self._src[v]
        if v == 0:
            out = [(-1, r) for r in range(self.archive_rows)]
        else:
            batches, n_job = self.folds[v]
            out = [(k, r) for k in batches for r in range(self.batch_rows)]
            prev = self.sources(v - 1)
            for g in self.ids[v - 1]:
                if g < 0:
                    out.append(None)
                elif g >= len(prev) or prev[g] is None:
                    raise ValueError(f"version {v - 1} keeps row {g}, "
                                     "which is no row of its job")
                else:
                    out.append(prev[g])
            out += [None] * (n_job - len(out))
        self._src[v] = out
        return out

    def mask(self, v: int) -> np.ndarray:
        return np.array([s is not None for s in self.sources(v)],
                        np.float32)


def job_rows(rows_model, tenant: int, chain: Chain, v: int, acc):
    """The reference's ``(rows, y, mask, n)`` of version ``v``'s job, in
    the row model's format (a dense array, or a CSR pair whose arrays are
    taken row by row alike)."""
    import jax.numpy as jnp
    from jax.tree_util import tree_map
    from bench import reference as ref

    src = chain.sources(v)
    n = len(src)
    by_batch: Dict[int, List[int]] = {}
    for pos, s in enumerate(src):
        if s is not None:
            by_batch.setdefault(s[0], []).append(pos)
    pieces, ys, order = [], [], []
    for k, positions in sorted(by_batch.items()):
        rows = chain.archive_rows if k < 0 else chain.batch_rows
        X, y = rows_model.make(tenant, k, rows)
        r = jnp.asarray([src[p][1] for p in positions], jnp.int32)
        pieces.append(tree_map(lambda a: jnp.take(a, r, axis=0), X))
        ys.append(jnp.take(y, r).astype(jnp.float32))
        order += positions
    empty = [p for p, s in enumerate(src) if s is None]
    if empty:
        pieces.append(tree_map(lambda a: jnp.zeros(
            (len(empty),) + a.shape[1:], a.dtype), pieces[0]))
        ys.append(jnp.zeros((len(empty),), jnp.float32))
        order += empty
    perm = jnp.asarray(np.argsort(np.asarray(order)), jnp.int32)
    X = tree_map(lambda *a: jnp.take(jnp.concatenate(a), perm, axis=0),
                *pieces)
    y = jnp.concatenate(ys)[perm]
    return ref.rows_from(X, acc), y, jnp.asarray(chain.mask(v)), n
