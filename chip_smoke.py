#!/usr/bin/env python3
"""Bring-up smoke run of the MapReduce-SVM system on a TPU.

    python chip_smoke.py               # one chip: train, then serve
    python chip_smoke.py --four-chips  # the sharded round on 4 chips

Drives the main path once through the entry points a user calls, at
the full width of the ``svm-tfidf`` configuration (d = 131072 bf16,
``sv_capacity`` 2048), all in this one process:

* train: ``repro.launch.train.train_svm`` — 8192 rows per device, the
  ring SV merge, the 1-chip data mesh, 3 rounds. Risks must be finite.
* serve: ``repro.launch.serve.serve_svm`` — two tenants, two waves of
  submit → batched wave fold → snapshot swap → predict, 8 partitions.
  Every wave must fold through the batched path, and the folded model
  must beat the stale one on the drifting stream.

``--four-chips`` runs only the cross-chip path: the sharded round on a
4-chip ``("data",)`` mesh at 8192 rows/device under the ring and the
allgather merge (their per-round risks and SV sets must agree), and the
sharded round at 1024 rows/device against the functional
``fit_mapreduce`` reference on one chip.

Earlier lines report the device, and per phase the compile time,
persistent-cache hits, wall time and ``peak_bytes_in_use``: set-up
records of a smoke run, not metrics. The last line of stdout is
``{"ok": true, "device": {...}}``. A run that finds no TPU, or in which
any phase fails, exits non-zero and prints no such line.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import math
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(REPO, "src"))

# Two tenants fold together at this many rows per wave, not the
# config's 8192. Compiled for a v5e (tests/test_tpu_compile.py), the
# two-tenant fold program alone takes 13.15 GB at 8192 rows, 10.74 GB at
# 6144 and 8.60 GB at 4096; the service also holds the wave's submitted
# rows (0.27 GB per 1024 rows per tenant) and the tenants' snapshots
# (~2.1 GB). Only 4096 leaves room in the chip's 15.75 GB.
SERVE_ROWS_PER_WAVE = 4096
SERVE_CUT_REASON = (
    "two-tenant fold at 8192 rows/wave = 13.15 GB program + 4.3 GB "
    "queued rows + ~2.1 GB snapshots, at 6144 = 10.74 + 3.2 + 2.1 GB, "
    "over the 15.75 GB HBM; at 4096 = 8.60 + 2.15 + 2.1 GB "
    "(fold programs compiled for v5e)")
FOUR_CHIP_REF_ROWS = 1024     # rows/device where the reference fits one chip
# tests/test_sharded_round.py's tolerances
RISK_TOL = dict(rtol=1e-4, atol=1e-5)
ALPHA_TOL = dict(rtol=1e-4, atol=1e-5)
ROW_TOL = dict(rtol=1e-5, atol=1e-6)


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


class PhaseClock:
    """Compile seconds and persistent-cache hits/misses, from JAX's
    monitoring events, attributed to the phase that is running."""

    def __init__(self):
        from jax import monitoring
        self.compile_s = 0.0
        self.hits = self.misses = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += duration

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    @contextlib.contextmanager
    def phase(self, name: str, devices):
        c0, h0, m0, t0 = self.compile_s, self.hits, self.misses, time.time()
        yield
        wall = time.time() - t0
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in devices]
        print(f"[{name}] wall {wall:.1f} s, compile "
              f"{self.compile_s - c0:.1f} s, persistent cache "
              f"{self.hits - h0} hit / {self.misses - m0} miss, "
              f"peak_bytes_in_use "
              + ", ".join(f"{p / 1e9:.2f} GB" for p in peaks), flush=True)


def full_config():
    from repro.configs import get_config
    cfg = get_config("svm-tfidf")
    check((cfg.num_features, cfg.dtype, cfg.sv_capacity,
           cfg.rows_per_device, cfg.shuffle_impl)
          == (131072, "bfloat16", 2048, 8192, "ring"),
          f"svm-tfidf is no longer the width this smoke run checks: {cfg}")
    return cfg


def phase_train(cluster) -> None:
    from repro.launch import train
    args = train.build_parser().parse_args(
        ["--arch", "svm-tfidf", "--rounds", "3"])
    rec = train.train_svm(full_config(), args, cluster)
    risks = [r["risk"] for r in rec["rounds"]]
    print(f"train: R_emp per round {risks}, |SV| per round "
          f"{[r['sv'] for r in rec['rounds']]}, accuracy "
          f"{rec['accuracy']}", flush=True)
    check(rec["rounds"] and all(map(math.isfinite, risks)),
          f"train risks not finite: {risks}")
    check(math.isfinite(rec["accuracy"]), "train accuracy not finite")


def phase_serve(cluster) -> None:
    from repro.launch import serve
    cfg = full_config()
    print(f"serve: stream_rows_per_wave cut {cfg.stream_rows_per_wave} → "
          f"{SERVE_ROWS_PER_WAVE}: {SERVE_CUT_REASON}", flush=True)
    args = serve.build_parser().parse_args(
        ["--arch", "svm-tfidf", "--streams", "2", "--waves", "2",
         "--rows-per-wave", str(SERVE_ROWS_PER_WAVE)])
    rec = serve.serve_svm(cfg, args, cluster)
    waves, folds = rec["waves"], rec["folds"]
    check(len(waves) == 2, f"expected 2 served waves, got {len(waves)}")
    check(folds == [True, True],
          f"every wave must fold both tenants in one batched fold; "
          f"folds batched: {folds}")
    for w in waves:
        check(w["folded"] > w["stale"],
              f"wave {w['wave']}: folded accuracy {w['folded']} does not "
              f"beat stale {w['stale']}")


def _replicated_on(sharding, n: int) -> bool:
    return sharding.is_fully_replicated and len(sharding.device_set) == n


def phase_four_chip_transports(cluster, devices) -> None:
    """ring vs allgather at full width on the 4-chip data mesh."""
    import numpy as np
    from repro.data import svm_rows_shard
    from repro.launch import train
    cfg = full_config()
    n = len(devices) * cfg.rows_per_device
    rows = svm_rows_shard(n, cfg.num_features, seed=0)
    recs = {}
    for shuffle in ("ring", "allgather"):
        args = train.build_parser().parse_args(
            ["--arch", "svm-tfidf", "--rounds", "3", "--shuffle", shuffle])
        rec = train.train_svm(cfg, args, cluster, rows=rows)
        sh = rec["shardings"]
        check(len(sh["X"].device_set) == len(devices)
              and not sh["X"].is_fully_replicated,
              f"{shuffle}: X is not sharded over the mesh: {sh['X']}")
        for k in ("sv.x", "risks", "w"):
            check(_replicated_on(sh[k], len(devices)),
                  f"{shuffle}: output {k} is not replicated on every "
                  f"chip: {sh[k]}")
        risks = [r["risk"] for r in rec["rounds"]]
        check(all(map(math.isfinite, risks)),
              f"{shuffle}: risks not finite: {risks}")
        recs[shuffle] = rec
    ring, ag = recs["ring"]["rounds"], recs["allgather"]["rounds"]
    check(len(ring) == len(ag),
          f"ring ran {len(ring)} rounds, allgather {len(ag)}")
    np.testing.assert_allclose([r["risk"] for r in ring],
                               [r["risk"] for r in ag], **RISK_TOL)
    for a, b in zip(ring, ag):
        np.testing.assert_array_equal(a["ids"], b["ids"])
    print(f"four-chip: ring ≡ allgather over {len(ring)} rounds "
          f"(risks {[r['risk'] for r in ring]})", flush=True)


def phase_four_chip_reference(devices) -> None:
    """The sharded round at 1024 rows/device vs ``fit_mapreduce`` with
    4 partitions on one chip, both at f32 matmul precision so the
    comparison holds the CPU test's tolerances."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core import MRSVMConfig, SVMConfig, fit_mapreduce
    from repro.core.mapreduce_svm import build_sharded_round, init_sv_buffer
    from repro.data import svm_rows_shard
    from repro.launch.mesh import make_host_mesh

    cfg_full = full_config()
    ndev, per, d = len(devices), FOUR_CHIP_REF_ROWS, cfg_full.num_features
    Xh, yh = svm_rows_shard(ndev * per, d, seed=1)
    bf = jnp.bfloat16
    cfg = MRSVMConfig(sv_capacity=cfg_full.sv_capacity, gamma=1e-4,
                      max_rounds=3, shuffle_impl="allgather",
                      svm=SVMConfig(C=cfg_full.C,
                                    max_epochs=cfg_full.max_epochs))
    with jax.default_matmul_precision("highest"):
        ref = fit_mapreduce(jnp.asarray(Xh, bf), jnp.asarray(yh, bf),
                            ndev, cfg)
        mesh = make_host_mesh(ndev, 1)
        rows = NamedSharding(mesh, P("data"))
        X = jax.device_put(jnp.asarray(Xh, bf), rows)
        y = jax.device_put(jnp.asarray(yh, bf), rows)
        m = jax.device_put(jnp.ones((ndev * per,), bf), rows)
        round_fn = build_sharded_round(mesh, ("data",), cfg, per)
        sv = init_sv_buffer(cfg.sv_capacity, d, bf)
        risks = []
        for _ in range(ref.rounds):
            sv, r, _, _ = round_fn(X, y, m, sv)
            risks.append(float(jnp.min(r)))
    ref_risks = [h["risk"] for h in ref.history]
    print(f"four-chip reference: sharded {risks} vs fit_mapreduce "
          f"{ref_risks}", flush=True)
    np.testing.assert_allclose(risks, ref_risks, **RISK_TOL)
    np.testing.assert_array_equal(np.asarray(sv.ids), np.asarray(ref.sv.ids))
    np.testing.assert_array_equal(np.asarray(sv.mask),
                                  np.asarray(ref.sv.mask))
    f32 = lambda a: np.asarray(a, np.float32)
    np.testing.assert_allclose(f32(sv.alpha), f32(ref.sv.alpha),
                               **ALPHA_TOL)
    np.testing.assert_allclose(f32(sv.x), f32(ref.sv.x), **ROW_TOL)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded round on a 4-chip mesh "
                         "and what it is compared with")
    args = ap.parse_args(argv)
    try:
        from repro.launch.cluster import init_cluster
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repro package is not beside this script "
              f"({e})", file=sys.stderr)
        return 2
    cache_dir = enable_compile_cache()

    import jax
    devices = jax.devices()
    platform, kind = devices[0].platform, devices[0].device_kind
    print(f"device: platform={platform} kind={kind} count={len(devices)} "
          f"jax={jax.__version__} compile cache={cache_dir}", flush=True)
    if platform != "tpu":
        print("chip_smoke: JAX found no TPU; this run needs the chip",
              file=sys.stderr)
        return 1
    want = 4 if args.four_chips else 1
    if len(devices) != want:
        print(f"chip_smoke: needs exactly {want} chip(s), JAX sees "
              f"{len(devices)}", file=sys.stderr)
        return 1

    clock = PhaseClock()
    cluster = init_cluster()
    if args.four_chips:
        with clock.phase("four-chip ring vs allgather", devices):
            phase_four_chip_transports(cluster, devices)
        with clock.phase("four-chip vs fit_mapreduce", devices):
            phase_four_chip_reference(devices)
    else:
        with clock.phase("train", devices):
            phase_train(cluster)
        with clock.phase("serve", devices):
            phase_serve(cluster)
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
