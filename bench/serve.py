"""Drives the streaming service through its public calls
(``StreamingSVMService.register / start / submit_many / stop``) and
records what a user of it sees.

The load is a closed loop (traffic ``mode`` ``closed``): every tenant
submits ``batches_per_wave`` batches of ``batch_rows`` rows together,
and the next set once the service is idle, so the fold is never starved
and never holds a second wave's rows (a back-fill at saturation).

Set-up fits every tenant's archive in one batched sweep
(``fit_mapreduce_sweep``, the library entry the service folds with),
registers the tenants and runs one warm-up wave of the window's shape,
so that nothing compiles inside the window. Rows are the program's row
type for the configuration's ``row_format`` (``program_rows``): dense
arrays, or blocked-CSR ``repro.sparse.SparseRows``; the sweep pads and
stacks them with ``repro.sparse``'s row ops, which are ``jnp``'s for
dense rows.
"""
from __future__ import annotations

import contextlib
import time
from typing import Dict, List


def next_pow2(n: int) -> int:
    w = 1
    while w < n:
        w *= 2
    return w


def mr_config(cfg: dict):
    from bench.gen import nnz_cap
    from repro.core import MRSVMConfig, SVMConfig
    csr = cfg["row_format"] == "sparse_csr"
    svm = SVMConfig(C=float(cfg["C"]), max_epochs=int(cfg["max_epochs"]),
                    tol=float(cfg["tol"]),
                    sv_threshold=float(cfg["sv_threshold"]),
                    row_format=cfg["row_format"],
                    nnz_cap=nnz_cap(cfg) if csr else 0)
    return MRSVMConfig(sv_capacity=int(cfg["sv_capacity"]), svm=svm,
                       gamma=float(cfg["gamma"]),
                       max_rounds=int(cfg["max_rounds"]),
                       shuffle_impl=cfg["shuffle_impl"])


def program_rows(rows_model, tenant: int, batch: int, rows: int):
    """``rows_model.make`` with its rows as the program's row type: a
    CSR pair ``(cols, vals)`` becomes ``repro.sparse.SparseRows``."""
    X, y = rows_model.make(tenant, batch, rows)
    if rows_model.csr:
        from repro.sparse import SparseRows
        X = SparseRows(*X, rows_model.d)
    return X, y


def _annotate(name: str, on: bool):
    if not on:
        return contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation(name)


class Tap:
    """Every published version of the tenants' snapshots, read
    through ``StreamingSVMService.snapshot``. Holds references to the
    small leaves only (the SV rows stay the service's)."""

    def __init__(self, svc, names: List[str]):
        self.svc, self.names = svc, names
        self.seen: Dict[str, Dict[int, dict]] = {s: {} for s in names}
        self.last: Dict[str, int] = {}

    def poll(self):
        now = time.time()
        for s in self.names:
            snap = self.svc.snapshot(s)
            if snap.version == self.last.get(s):
                continue
            m = snap.model
            self.seen[s][snap.version] = {
                "ids": m.sv.ids, "rounds": int(m.rounds), "risk": m.risk,
                "w": m.w, "b": m.b, "final_w": m.final.w,
                "final_b": m.final.b, "seen_s": now}
            self.last[s] = snap.version


class ServiceRun:
    def __init__(self, cfg: dict, tr: dict, seed: int, rows, trace: bool):
        if tr["mode"] != "closed":
            raise ValueError(f"the service is driven closed, not "
                             f"{tr['mode']!r}")
        self.cfg, self.tr, self.seed, self.rows = cfg, tr, seed, rows
        self.trace = trace
        self.tenants = int(cfg["tenants"])
        self.names = [f"t{s:03d}" for s in range(self.tenants)]
        self.next_batch = [0] * self.tenants
        self.after_step = lambda: None      # called after every wave
        self.batch_of_uid: Dict[int, tuple] = {}
        self.due_of_uid: Dict[int, float] = {}
        self.svc = None

    # -- set-up -------------------------------------------------------------

    def setup(self):
        import jax
        import jax.numpy as jnp
        from repro.core.mapreduce_svm import MapReduceSVM
        from repro.core.sweep import fit_mapreduce_sweep, stack_params
        from repro.serving import StreamingSVMService
        from repro.sparse import rows_stack, rows_zeros_like

        cfg, tr = self.cfg, self.tr
        self.mr = mr = mr_config(cfg)
        self.L = int(cfg["partitions"])
        svc = StreamingSVMService(
            mr, num_partitions=self.L,
            max_batches_per_wave=int(cfg["max_batches_per_wave"]))
        self.svc = svc
        # Every archive is fitted in one sweep, its job axis padded with
        # empty jobs to the service's bucket width; with archives of a
        # wave's rows the sweep is the very program the waves run.
        n0 = int(tr["archive_rows"])
        parts = [program_rows(self.rows, s, -1, n0)
                 for s in range(self.tenants)]
        X = [p[0] for p in parts]
        y = [p[1] for p in parts]
        del parts
        width = next_pow2(self.tenants)
        X += [rows_zeros_like(X[0])] * (width - self.tenants)
        y += [jnp.zeros_like(y[0])] * (width - self.tenants)
        mask = jnp.stack([jnp.full((n0,), float(i < self.tenants), y[0].dtype)
                          for i in range(width)])
        X, y = rows_stack(X), jnp.stack(y)
        params = stack_params([mr.svm.params()] * width)
        res = fit_mapreduce_sweep(X, y, self.L, mr, params, mask=mask)
        del X, y, mask
        pick = lambda tree, i: jax.tree_util.tree_map(lambda a: a[i], tree)
        for i, name in enumerate(self.names):
            svc.register(name, MapReduceSVM(
                w=res.ws[i], b=res.bs[i], sv=pick(res.sv, i),
                final=pick(res.final, i), risk=res.risks[i],
                rounds=int(res.rounds[i]), history=()))
        del res
        self.archive_rows = n0
        self.tap = Tap(svc, self.names)
        self.tap.poll()
        svc.start(idle_poll_s=0.05)
        self._submit(self._wave(), due=None)
        svc.wait_idle(timeout_s=600.0, poll_s=0.001)
        self.tap.poll()
        jax.block_until_ready(jnp.zeros(()))

    def _wave(self):
        per = int(self.tr["batches_per_wave"])
        return [(s, k) for s in range(self.tenants) for k in range(per)]

    # -- load ---------------------------------------------------------------

    def _submit(self, items, due):
        """Submit batches ``(tenant, count)`` → made now, one call."""
        batch = []
        for s, _ in items:
            k = self.next_batch[s]
            self.next_batch[s] += 1
            X, y = program_rows(self.rows, s, k, int(self.tr["batch_rows"]))
            batch.append((s, k, X, y))
        uids = self.svc.submit_many(
            [(self.names[s], X, y) for s, k, X, y in batch])
        for uid, (s, k, _, _) in zip(uids, batch):
            self.batch_of_uid[uid] = (s, k)
            if due is not None:
                self.due_of_uid[uid] = due
        return uids

    def window(self, seconds: float):
        with _annotate("bench.window", self.trace):
            t0 = time.time()
            self.t_open = t0
            while time.time() - t0 < seconds:
                with _annotate("bench.submit", self.trace):
                    self._submit(self._wave(), due=time.time())
                with _annotate("bench.wait", self.trace):
                    self.svc.wait_idle(timeout_s=600.0, poll_s=0.001)
                self.tap.poll()
                self.after_step()
            self.t_close = time.time()
        self.tap.poll()

    def stop(self):
        self.svc.stop(drain=False)

    # -- records ------------------------------------------------------------

    def records(self) -> dict:
        svc = self.svc
        done = {mb.uid: mb for mb in svc.done}
        batches = []
        for uid, due in self.due_of_uid.items():
            s, k = self.batch_of_uid[uid]
            mb = done.get(uid)
            batches.append({
                "uid": uid, "tenant": s, "batch": k,
                "rows": int(self.tr["batch_rows"]), "due": due,
                "submitted": mb.submitted_s if mb else None,
                "admitted": mb.admitted_s if mb else None,
                "completed": mb.completed_s if mb else None,
                "wave": mb.wave if mb else None})
        waves = [{"wave": st.wave, "streams": st.streams,
                  "batches": st.batches, "rows": st.rows,
                  "batched": st.batched, "wall_s": st.wall_s,
                  "width": next_pow2(st.streams) if st.streams > 1 else 1}
                 for st in svc.stats]
        # wave timing from its batches
        first = {}
        for mb in svc.done:
            w = first.setdefault(mb.wave, [mb.admitted_s, mb.completed_s])
            w[0], w[1] = min(w[0], mb.admitted_s), max(w[1], mb.completed_s)
        for w in waves:
            w["admitted"], w["completed"] = first.get(w["wave"], (None, None))
        return {"batches": batches, "waves": waves,
                "open": self.t_open, "close": self.t_close,
                "versions": {s: {v: {"rounds": r["rounds"],
                                     "seen_s": r["seen_s"]}
                                 for v, r in vs.items()}
                             for s, vs in self.tap.seen.items()}}
