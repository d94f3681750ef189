"""Production serving entry points.

LLM family — sharded single-token decode loop:

    PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b \
        --smoke --batch 4 --tokens 16

svm family — streaming polarization service: micro-batches of drifting
messages fold into each tenant's SV_global behind the async wave
scheduler (repro.serving.svm_stream); S streams update in one batched
device pass:

    PYTHONPATH=src python -m repro.launch.serve --arch svm-tfidf \
        --smoke --streams 4 --waves 3
"""
from __future__ import annotations

import argparse
import time

import jax

from repro import compat
import jax.numpy as jnp

from repro.configs import get_config
from repro.launch.cluster import (add_cluster_flags, cluster_config_from_args,
                                  init_cluster)
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh, simulated_hier_hosts
from repro.launch.steps import InputShape, build_serve_step
from repro.models.config import smoke_variant


def serve_svm(svm_cfg, args, cluster) -> dict:
    """Streaming polarization serve mode (``--arch svm-tfidf``).

    Each wave submits one batch per stream together (``submit_many``),
    so with two or more streams the wave folds through the batched
    sweep. Returns the run's record: per wave the stale and folded
    accuracy and whether the fold was batched, plus the service's
    throughput report.

    Multi-process topology: message admission runs on process 0 (the
    coordinator owns the queues and drives the folds) while model
    snapshots stay readable everywhere — non-coordinator processes get
    a registered service they can ``predict``/``snapshot`` against but
    not ``submit`` to (DESIGN.md §11).
    """
    import dataclasses as dc

    from repro.core import MRSVMConfig, SVMConfig, fit_mapreduce
    from repro.serving import StreamingSVMService

    if args.smoke:
        svm_cfg = dc.replace(svm_cfg, num_features=128, sv_capacity=64,
                             stream_rows_per_wave=256, dtype="float32")
    d = svm_cfg.num_features
    rows = args.rows_per_wave or svm_cfg.stream_rows_per_wave
    L = args.data_par if args.data_par > 1 else 8   # partitions (default 8)
    shuffle = args.shuffle or getattr(svm_cfg, "shuffle_impl", "allgather")
    hosts = simulated_hier_hosts(L) if shuffle == "hier" else None
    cfg = MRSVMConfig(sv_capacity=svm_cfg.sv_capacity, gamma=1e-4,
                      max_rounds=3, shuffle_impl=shuffle,
                      hier_num_hosts=hosts,
                      svm=SVMConfig(C=svm_cfg.C,
                                    max_epochs=svm_cfg.max_epochs))
    dt = jnp.dtype(svm_cfg.dtype)

    def batch(stream: int, wave: int, drift: float = 0.4):
        """Synthetic drifting message batch: stream s's true separator
        rotates steadily along a per-stream drift direction."""
        kx = jax.random.PRNGKey(1000 * stream + wave)
        w0 = jax.random.normal(jax.random.PRNGKey(stream), (d,))
        wd = jax.random.normal(jax.random.PRNGKey(500 + stream), (d,))
        w = w0 + drift * wave * wd
        X = jax.random.normal(kx, (rows, d), dt)
        y = jnp.sign((X @ w).astype(jnp.float32)).astype(dt)
        return X, y

    hardening = dict(checkpoint_keep=args.checkpoint_keep,
                     quarantine=not args.no_quarantine,
                     fold_deadline_s=args.fold_deadline,
                     heartbeat_path=args.heartbeat)
    if args.restore:
        if not args.checkpoint_dir:
            raise SystemExit("--restore requires --checkpoint-dir")
        svc = StreamingSVMService.restore(
            cfg, args.checkpoint_dir, cluster=cluster,
            checkpoint_every_waves=args.checkpoint_every, **hardening)
        print(f"svm-serve: restored {len(svc.streams())} streams from "
              f"{args.checkpoint_dir}")
    else:
        svc = StreamingSVMService(
            cfg, num_partitions=L, max_batches_per_wave=args.streams,
            cluster=cluster, checkpoint_dir=args.checkpoint_dir,
            checkpoint_every_waves=args.checkpoint_every, **hardening)
    print(f"svm-serve: {args.streams} streams × {rows} rows/wave, "
          f"{d} features, {L} partitions "
          f"(process {cluster.process_index}/{cluster.process_count})")
    for s in range(args.streams):
        if f"stream{s}" in svc.streams():
            continue                   # came back with the checkpoint
        X0, y0 = batch(s, 0)
        svc.register(f"stream{s}", fit_mapreduce(X0, y0, L, cfg))
        del X0, y0
    if not cluster.is_coordinator:
        # snapshots are served from every process; admission is not.
        acc = float(jnp.mean(svc.predict("stream0", batch(0, 0)[0])
                             == batch(0, 0)[1]))
        print(f"process {cluster.process_index}: read-only replica "
              f"(stream0 snapshot v{svc.snapshot('stream0').version}, "
              f"acc={acc:.3f}); admission runs on process 0")
        return {"waves": [], "replica_acc": acc}

    svc.start()
    # post-restore the version counters resume where the checkpoint
    # left them, so wave completion is measured against the base
    base = {s: svc.snapshot(f"stream{s}").version
            for s in range(args.streams)}
    waves = []
    for wave in range(1, args.waves + 1):
        batches = [batch(s, wave) for s in range(args.streams)]
        stale = [float(jnp.mean(svc.predict(f"stream{s}", X) == y))
                 for s, (X, y) in enumerate(batches)]
        t0 = time.time()
        svc.submit_many([(f"stream{s}", X, y)
                         for s, (X, y) in enumerate(batches)])
        deadline = time.time() + 300
        while any(svc.snapshot(f"stream{s}").version < base[s] + wave
                  for s in range(args.streams)):
            if svc.scheduler_error is not None or time.time() > deadline:
                raise RuntimeError(
                    f"wave {wave} never folded") from svc.scheduler_error
            time.sleep(0.01)
        fresh = [float(jnp.mean(svc.predict(f"stream{s}", X) == y))
                 for s, (X, y) in enumerate(batches)]
        del batches
        wall = time.time() - t0
        waves.append({"wave": wave, "stale": sum(stale) / len(stale),
                      "folded": sum(fresh) / len(fresh), "wall_s": wall})
        print(f"wave {wave}: stale acc={waves[-1]['stale']:.3f} → "
              f"folded acc={waves[-1]['folded']:.3f} ({wall:.2f}s)")
    svc.stop()
    report = svc.throughput_report()
    print(report)
    return {"waves": waves, "folds": [st.batched for st in svc.stats],
            "report": report}


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--cache-len", type=int, default=256)
    ap.add_argument("--tokens", type=int, default=16)
    ap.add_argument("--data-par", type=int, default=1)
    ap.add_argument("--model-par", type=int, default=1)
    ap.add_argument("--streams", type=int, default=4,
                    help="svm family: tenant streams served")
    ap.add_argument("--waves", type=int, default=3,
                    help="svm family: update waves to run")
    ap.add_argument("--rows-per-wave", type=int, default=0,
                    help="svm family: override the config's "
                         "stream_rows_per_wave")
    from repro.core.mapreduce_svm import SHUFFLE_IMPLS
    ap.add_argument("--shuffle", default=None,
                    choices=SHUFFLE_IMPLS,
                    help="svm family: SV merge transport of the sharded "
                         "fold programs (default: the arch config's)")
    ap.add_argument("--checkpoint-dir", default=None,
                    help="svm family: durable per-stream ModelSnapshot "
                         "checkpoints (DESIGN.md §13)")
    ap.add_argument("--checkpoint-every", type=int, default=1,
                    help="svm family: waves between checkpoints")
    ap.add_argument("--restore", action="store_true",
                    help="svm family: rebuild the service from the "
                         "latest manifest in --checkpoint-dir instead "
                         "of retraining stream models")
    ap.add_argument("--checkpoint-keep", type=int, default=3,
                    help="svm family: snapshot generations retained; "
                         "restore falls back past corrupt ones "
                         "(DESIGN.md §15)")
    ap.add_argument("--no-quarantine", action="store_true",
                    help="svm family: fold non-finite batches instead "
                         "of diverting them at submit()")
    ap.add_argument("--fold-deadline", type=float, default=None,
                    help="svm family: watchdog deadline (s) per wave "
                         "fold — a stranded collective exits the "
                         "process with code 17 instead of hanging")
    ap.add_argument("--heartbeat", default=None,
                    help="svm family: path of the watchdog's JSON "
                         "heartbeat file (operators poll it)")
    add_cluster_flags(ap)
    return ap


def main():
    args = build_parser().parse_args()
    enable_compile_cache()

    # Before first backend use — see launch/cluster.py ordering contract.
    cluster = init_cluster(cluster_config_from_args(args))
    cfg = get_config(args.arch)
    if getattr(cfg, "family", None) == "svm":
        return serve_svm(cfg, args, cluster)
    if cluster.is_distributed:
        raise SystemExit(
            "multi-process launch currently covers the svm family")
    if args.smoke:
        cfg = smoke_variant(cfg)
    mesh = make_host_mesh(args.data_par, args.model_par, cluster=cluster)
    shape = InputShape("cli", "decode", args.cache_len, args.batch)
    bundle = build_serve_step(cfg, mesh, shape)
    model = bundle.model

    with compat.set_mesh(mesh):
        step_fn = jax.jit(
            bundle.fn,
            in_shardings=compat.to_shardings(mesh, bundle.in_shardings),
            out_shardings=compat.to_shardings(mesh, bundle.out_shardings),
            donate_argnums=bundle.donate_argnums)
        params = model.init(jax.random.PRNGKey(0))
        if cfg.is_encoder_decoder:
            frames = jax.random.normal(
                jax.random.PRNGKey(1),
                (args.batch, cfg.encoder_seq, cfg.d_model), cfg.jdtype)
            state = model.init_decode_state(args.batch, args.cache_len,
                                            frames=frames, params=params)
        else:
            state = model.init_decode_state(args.batch, args.cache_len)
        tok = jnp.zeros((args.batch, 1), jnp.int32)
        t0 = time.time()
        for i in range(args.tokens):
            tok, state = step_fn(params, state, tok)
            tok = tok[:, None]
        jax.block_until_ready(tok)
        dt = time.time() - t0
    print(f"{cfg.name}: {args.tokens} tokens × {args.batch} seqs "
          f"in {dt:.2f}s → {args.tokens * args.batch / dt:,.1f} tok/s")


if __name__ == "__main__":
    main()
