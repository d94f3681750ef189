"""A tiny copy of the benchmark for tests on the CPU: the real metric
readers, with configurations, mixes and limits at toy sizes."""
from __future__ import annotations

import json
import shutil
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]

BASE = {"num_features": 512, "sv_capacity": 64, "C": 1.0, "max_epochs": 5,
        "tol": 0.001, "sv_threshold": 1e-06, "partitions": 4,
        "max_rounds": 3, "gamma": 0.0001, "dtype": "bfloat16",
        "shuffle_impl": "ring", "rows_per_device": 256,
        "drift_per_batch": 0.4, "max_batches_per_wave": 1}
CONFIGS = {
    "tiny-dense": dict(BASE, row_format="dense", tenants=2, row_nnz=[8, 8],
                       signal_dims=16),
    "tiny-csr": dict(BASE, row_format="sparse_csr", tenants=2,
                     row_nnz=[8, 8], signal_dims=16),
}
TRAFFIC = {
    "fold": {"mode": "closed", "batch_rows": 64, "batches_per_wave": 1,
             "archive_rows": 128, "compare_folds": 2, "trace_s": 0.5},
    "train": {"mode": "train", "row_sets": 2, "compare_fits": 1},
}
# Readings at these sizes on the CPU: the program 1e-7 on dense rows (on
# CSR rows up to 4e-4, and `sv` over its limit on some seeds: the
# program sums a CSR row's squared values in bfloat16); the bfloat16
# control 2.5e-3 to 5e-2.
LIMITS = {"sv": 0.02, "risk": 1e-3, "rounds": 0, "w": 1e-3, "final": 1e-3,
          "unchecked": 0}
CELLS = [
    {"name": "d.fold", "config": "tiny-dense", "traffic": "fold",
     "chips": 1, "why": "toy"},
    {"name": "d.train", "config": "tiny-dense", "traffic": "train",
     "chips": 1, "why": "toy"},
    {"name": "c.fold", "config": "tiny-csr", "traffic": "fold",
     "chips": 1, "why": "toy"},
    {"name": "c.train", "config": "tiny-csr", "traffic": "train",
     "chips": 1, "why": "toy"},
]
RENAME = {"dense2t.fold-sat": ["d.fold", "c.fold"],
          "dense.train": ["d.train", "c.train"]}


def make(root: Path) -> Path:
    """Write the tiny benchmark under ``root``; returns its bench dir."""
    bd = root / "bench"
    shutil.copytree(BENCH / "metrics", bd / "metrics")
    for sub, items in (("configs", CONFIGS), ("traffic", TRAFFIC)):
        (bd / sub).mkdir()
        for name, body in items.items():
            (bd / sub / f"{name}.json").write_text(json.dumps(body))
    (bd / "limits").mkdir()
    for c in CELLS:
        lim = dict(LIMITS, **{"train": {"repeat": 0}}.get(c["traffic"], {}))
        (bd / "limits" / f"{c['name']}.json").write_text(json.dumps(lim))
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    bench["workloads"] = CELLS
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [t for w in m["workloads"] for t in RENAME[w]]
    (bd / "BENCHMARK.json").write_text(json.dumps(bench))
    return bd


def run(bd: Path, cell: str, seed: int, seconds: float = 2.0,
        trace: bool = False) -> dict:
    import jax
    from bench import run as run_lib, spec
    bench = json.loads((bd / "BENCHMARK.json").read_text())
    return run_lib.run_cell(bench, spec.cell(bench, cell), seed, seconds,
                            trace, bd.parent, time.time(),
                            jax.devices()[:1], bench_dir=bd)


def widen_csr_values(monkeypatch):
    """Hand the program its CSR rows' values in float32, the same
    numbers: a witness in which the program's bfloat16 sum of a CSR
    row's squared values (``repro.sparse.row_sq_norms``) no longer
    departs from the reference's float32 one."""
    import jax.numpy as jnp
    from bench import serve, train
    make = serve.program_rows

    def widened(rows_model, tenant, batch, rows):
        X, y = make(rows_model, tenant, batch, rows)
        return (X.astype(jnp.float32) if rows_model.csr else X), y
    monkeypatch.setattr(serve, "program_rows", widened)
    monkeypatch.setattr(train, "program_rows", widened)
