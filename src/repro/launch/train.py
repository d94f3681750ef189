"""Production training entry point.

Builds the sharded train_step for ``--arch`` on the cluster's device
mesh (one process, N CPU processes via --coordinator/--num-processes/
--process-id, or the production mesh on a real TPU slice), runs the
data pipeline, checkpoints, and logs. Without ``--smoke`` the svm
family trains at the config's full width (d = 131072 bf16, 8192 rows
per device), which is what ``chip_smoke.py`` runs on a TPU; ``--smoke``
is the reduced variant for CPU runs.

    PYTHONPATH=src python -m repro.launch.train --arch tinyllama-1.1b \
        --smoke --steps 50 --batch 8 --seq 128

Multi-process (each line its own host/process; see
examples/multihost_svm.py for a self-spawning demo):

    PYTHONPATH=src python -m repro.launch.train --arch svm-tfidf --smoke \
        --coordinator localhost:9911 --num-processes 2 --process-id 0
"""
from __future__ import annotations

import argparse
import time

import jax

from repro import compat
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import optim
from repro.ckpt import save
from repro.configs import get_config
from repro.data import DataConfig, lm_batch_at, svm_rows_shard
from repro.launch.cluster import (add_cluster_flags, cluster_config_from_args,
                                  init_cluster)
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh, simulated_hier_hosts
from repro.launch.steps import InputShape, build_train_step
from repro.models.config import smoke_variant


def train_svm(svm_cfg, args, cluster, rows=None) -> dict:
    """MapReduce-SVM training mode (``--arch svm-tfidf``): rows sharded
    over the data mesh, rounds driven on the host. ``--sweep S`` runs S
    (C, γ) hyper-parameter configs per round as one batched program —
    the vmap-over-configs sweep subsystem (repro.core.sweep).

    Process-count-agnostic (DESIGN.md §11): each process loads only its
    disjoint TF×IDF row shard (``svm_rows_shard``) and assembles the
    global arrays via ``cluster.make_global_array``; the sharded round
    itself is the SAME program at any process count. ``rows`` passes in
    an already generated ``(X, y)`` shard, so two runs can share data.

    Returns the run's record: per-round ``R_emp`` and ``|SV|``, and the
    accuracy of the selected hypothesis.
    """
    import dataclasses as dc

    from repro.core.mapreduce_svm import (MRSVMConfig, build_sharded_round,
                                          init_sv_buffer)
    from repro.core.svm import SVMConfig
    from repro.core.sweep import (build_sharded_sweep_round,
                                  run_sharded_sweep, sweep_grid)

    if args.smoke:
        svm_cfg = dc.replace(svm_cfg, num_features=256, sv_capacity=64,
                             rows_per_device=64, dtype="float32")
    say = print if cluster.is_coordinator else (lambda *a, **k: None)
    ndev = cluster.device_count
    per = args.rows_per_device or svm_cfg.rows_per_device
    n, d = ndev * per, svm_cfg.num_features
    mesh = make_host_mesh(ndev, 1, cluster=cluster)
    rounds = max(1, args.rounds)
    shuffle = args.shuffle or getattr(svm_cfg, "shuffle_impl", "allgather")
    hosts = simulated_hier_hosts(ndev) if shuffle == "hier" else None
    cfg = MRSVMConfig(sv_capacity=svm_cfg.sv_capacity,
                      gamma=1e-4, max_rounds=rounds,
                      shuffle_impl=shuffle, hier_num_hosts=hosts,
                      svm=SVMConfig(C=svm_cfg.C,
                                    max_epochs=svm_cfg.max_epochs))

    dt = jnp.dtype(svm_cfg.dtype)
    Xl, yl = rows if rows is not None else svm_rows_shard(
        n, d, seed=0, process_index=cluster.process_index,
        process_count=cluster.process_count)
    X = cluster.make_global_array(mesh, P("data"), Xl.astype(dt), (n, d))
    y = cluster.make_global_array(mesh, P("data"), yl.astype(dt), (n,))
    say(f"svm-tfidf: {n} rows × {d} features over {ndev} devices, "
        f"{cluster.process_count} process(es) "
        f"({Xl.shape[0]} rows loaded per host)")

    # Accuracy is reported on the process-local shard: the selected
    # hypothesis (w, b) is replicated, so this needs NO extra collective
    # and equals the global accuracy at one process.
    def local_acc(w_, b_):
        s = Xl.astype(np.float32) @ np.asarray(w_, np.float32).T \
            + np.asarray(b_, np.float32)
        return (np.sign(s) == (yl[:, None] if s.ndim > 1
                               else yl)).mean(axis=0)

    if args.sweep >= 1:
        params = sweep_grid(
            cfg.svm,
            C=np.logspace(-2, 1, args.sweep).astype(np.float32))
        round_fn = build_sharded_sweep_round(mesh, ("data",), cfg, per)
        t0 = time.time()
        out = run_sharded_sweep(round_fn, X, y, None, cfg, params,
                                verbose=cluster.is_coordinator)
        dt_s = time.time() - t0
        accs = local_acc(out.ws, out.bs)
        for s in range(args.sweep):
            say(f"  config C={float(params.C[s]):<8.4g} "
                f"R_emp={float(out.risks[s]):.4f} acc={accs[s]:.3f} "
                f"rounds={int(out.rounds[s])}")
        say(f"sweep selected C={float(params.C[out.best]):.4g} "
            f"({args.sweep} configs, one jit, {dt_s:.1f}s)")
        return {"risks": [float(r) for r in out.risks],
                "accuracy": [float(a) for a in accs]}

    round_fn = build_sharded_round(mesh, ("data",), cfg, per)
    sv = init_sv_buffer(cfg.sv_capacity, d, X.dtype)
    mask = cluster.make_global_array(
        mesh, P("data"), np.ones((Xl.shape[0],), Xl.dtype).astype(dt), (n,))
    prev = float("inf")
    history = []
    best = (float("inf"), None, None)    # keep the best h^t, as fit_mapreduce
    for t in range(rounds):
        sv, risks, w, b = round_fn(X, y, mask, sv)
        r = float(jnp.min(risks))
        if r < best[0]:
            best = (r, w, b)
        n_sv = int(jnp.sum(sv.mask))
        history.append({"round": t, "risk": r, "sv": n_sv,
                        "ids": np.asarray(sv.ids)})
        say(f"round {t}: R_emp={r:.4f} |SV|={n_sv}")
        if t > 0 and abs(prev - r) <= cfg.gamma:
            break
        prev = r
    acc = float(local_acc(best[1], best[2]))
    say(f"best-reducer accuracy: {acc:.3f}"
        + (" (host-local shard)" if cluster.is_distributed else ""))
    return {"rounds": history, "accuracy": acc, "shuffle": shuffle,
            "shardings": {"X": X.sharding, "sv.x": sv.x.sharding,
                          "risks": risks.sharding, "w": w.sharding}}


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced variant (CPU)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--data-par", type=int, default=1)
    ap.add_argument("--model-par", type=int, default=1)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--sweep", type=int, default=0,
                    help="svm family: run S hyper-param configs per "
                         "round as one batched sweep")
    ap.add_argument("--rounds", type=int, default=6,
                    help="svm family: MapReduce rounds")
    ap.add_argument("--rows-per-device", type=int, default=0,
                    help="svm family: override rows per device")
    from repro.core.mapreduce_svm import SHUFFLE_IMPLS
    ap.add_argument("--shuffle", default=None,
                    choices=SHUFFLE_IMPLS,
                    help="svm family: SV merge transport (default: the "
                         "arch config's shuffle_impl)")
    add_cluster_flags(ap)
    return ap


def main():
    args = build_parser().parse_args()
    enable_compile_cache()

    # BEFORE anything touches a device: the distributed client and the
    # CPU collectives wire into the backend at first init (DESIGN.md §11).
    cluster = init_cluster(cluster_config_from_args(args))
    cfg = get_config(args.arch)
    if getattr(cfg, "family", None) == "svm":
        return train_svm(cfg, args, cluster)
    if cluster.is_distributed:
        raise SystemExit(
            "multi-process launch currently covers the svm family; the "
            "LM data pipeline still materializes full global batches")
    if args.smoke:
        cfg = smoke_variant(cfg)
    mesh = make_host_mesh(args.data_par, args.model_par, cluster=cluster)
    shape = InputShape("cli", "train", args.seq, args.batch)
    bundle = build_train_step(cfg, mesh, shape, remat=False)
    model = bundle.model

    with compat.set_mesh(mesh):
        step_fn = jax.jit(
            bundle.fn,
            in_shardings=compat.to_shardings(mesh, bundle.in_shardings),
            out_shardings=compat.to_shardings(mesh, bundle.out_shardings),
            donate_argnums=bundle.donate_argnums)
        params = model.init(jax.random.PRNGKey(0))
        opt_state = optim.init(params)
        dcfg = DataConfig(batch_size=args.batch, seq_len=args.seq)
        t0 = time.time()
        for step in range(args.steps):
            batch = {k: jnp.asarray(v)
                     for k, v in lm_batch_at(dcfg, cfg, step).items()}
            params, opt_state, m = step_fn(params, opt_state, batch)
            if step % 10 == 0 or step == args.steps - 1:
                print(f"step {step:4d} loss {float(m['loss']):.4f} "
                      f"gnorm {float(m['grad_norm']):.2f} "
                      f"{(step + 1) * args.batch * args.seq / (time.time() - t0):,.0f} tok/s",
                      flush=True)
    if args.ckpt:
        save(args.ckpt, {"params": params}, step=args.steps)
        print(f"saved {args.ckpt}")


if __name__ == "__main__":
    main()
