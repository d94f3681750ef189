"""Drives batch training through ``fit_mapreduce``, the library entry
every example calls: fits from scratch, one after another, each on one
of ``row_sets`` device-resident row sets made from the seed in set-up.
Set-up makes the sets and runs one fit, which compiles every program."""
from __future__ import annotations

import contextlib
import time
from typing import List

from bench.serve import mr_config, program_rows


class TrainRun:
    def __init__(self, cfg: dict, tr: dict, seed: int, rows, trace: bool):
        self.cfg, self.tr, self.seed, self.rows = cfg, tr, seed, rows
        self.trace = trace
        self.n = int(cfg["rows_per_device"])
        self.fits: List[dict] = []
        self.after_step = lambda: None      # called after every fit

    def setup(self):
        import jax
        self.mr = mr_config(self.cfg)
        self.L = int(self.cfg["partitions"])
        self.sets = [program_rows(self.rows, 0, j, self.n)
                     for j in range(int(self.tr["row_sets"]))]
        jax.block_until_ready(self.sets)
        self._fit(0, record=False)

    def _fit(self, j: int, record=True):
        import jax
        from repro.core import fit_mapreduce
        X, y = self.sets[j]
        t0 = time.time()
        m = fit_mapreduce(X, y, self.L, self.mr)
        jax.block_until_ready((m.final, m.w, m.b, m.sv.ids))
        t1 = time.time()
        if record:
            self.fits.append({"set": j, "start": t0, "end": t1,
                              "rounds": int(m.rounds), "ids": m.sv.ids,
                              "risk": m.risk, "w": m.w, "b": m.b,
                              "final_w": m.final.w, "final_b": m.final.b})

    def window(self, seconds: float):
        import jax
        ann = (jax.profiler.TraceAnnotation("bench.window") if self.trace
               else contextlib.nullcontext())
        with ann:
            t0 = time.time()
            self.t_open = t0
            j = 0
            while time.time() - t0 < seconds:
                with (jax.profiler.TraceAnnotation("bench.fit")
                      if self.trace else contextlib.nullcontext()):
                    self._fit(j % len(self.sets))
                self.after_step()
                j += 1
            self.t_close = time.time()

    def stop(self):
        self.sets = None

    def records(self) -> dict:
        return {"fits": [{k: v for k, v in f.items()
                          if k in ("set", "start", "end", "rounds")}
                         for f in self.fits],
                "open": self.t_open, "close": self.t_close}
