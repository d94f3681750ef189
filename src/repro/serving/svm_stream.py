"""Streaming polarization service: fold live message batches into
SV_global behind an async wave scheduler (the paper's §SONUÇ future
work, productionized).

The converged global SV set is the model's sufficient statistic
(CloudSVM arXiv:1301.0082, binary MapReduce-SVM arXiv:1312.4108): a
drifted month of messages is absorbed by retraining on
(new batch ∪ carried SVs) — old non-support rows never travel, the
same bandwidth argument as the MapReduce shuffle itself.

Architecture (DESIGN.md §9):

  submit  : vectorized micro-batches queue per tenant *stream*
  admit   : the scheduler pops ≤ ``max_batches_per_wave`` batches per
            stream into one *wave*
  fold    : each admitted stream retrains on (its new rows ∪ its
            carried SVs) via ``update_mapreduce``; when several streams
            are admitted, the wave rides the sweep machinery — S
            streams become S jobs on the config/batch axis of
            :func:`~repro.core.sweep.fit_mapreduce_sweep` (per-job X /
            y / mask + stacked per-stream ``SolverParams``), so all S
            tenants update in ONE jitted device pass; a single admitted
            stream falls back to the plain round
  swap    : ``predict`` / ``decision_values`` keep serving from a
            double-buffered immutable :class:`ModelSnapshot`; the new
            model is fully materialized on device
            (``block_until_ready``) BEFORE the reference swap, so a
            reader never observes a half-updated model

Per-slot accounting mirrors the corrected decode scheduler
(:mod:`repro.serving.scheduler`): every micro-batch records submit →
admit → completion, so queue wait and fold service time are separable
and throughput reports aren't uniformly pessimistic.

Fault tolerance + elasticity (DESIGN.md §13): with ``checkpoint_dir``
set, every tenant's :class:`ModelSnapshot` persists through the
flat-npz checkpointer after each ``checkpoint_every_waves``-th wave
(the model *is* its support vectors — snapshots are tiny, restore is
instant), and :meth:`StreamingSVMService.restore` rebuilds a
queues-empty service from the latest manifest. A fold that dies
mid-wave requeues the un-swapped streams' micro-batches at the HEAD of
their queues — batches complete only *after* the snapshot swap, so
re-admission is exactly-once at the model level. Admission control
bounds the per-tenant queues (``max_queue_per_stream`` +
``shed_policy``), tracks a latency SLO (``slo_s``), and pads the
sweep's job axis to power-of-two buckets so a wave of any width reuses
a handful of compiled programs.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import json
import os
import threading
import time
from typing import Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import compat, faults
from repro import sparse as sparse_rows
from repro.analysis.retrace import RetraceError, watch_compiles
from repro.ckpt import checkpoint as ckpt
from repro.core.mapreduce_svm import (MapReduceSVM, MRSVMConfig, SVBuffer,
                                      decision_values as mr_decision_values,
                                      init_sv_buffer,
                                      predict as mr_predict,
                                      update_mapreduce)
from repro.core.svm import BinarySVM, SolverParams
from repro.core.sweep import fit_mapreduce_sweep, stack_params

_MANIFEST = "service_manifest.json"


@jax.jit
def _finite_flag(vals, y):
    """A 0-d bool on the device: every feature value and label of a
    micro-batch is finite. One reduction; the rows stay where they are."""
    return jnp.isfinite(vals).all() & jnp.isfinite(y).all()


def _all_finite(X, y, uid: int) -> bool:
    """Whether a micro-batch's features and labels are all finite —
    the quarantine gate at the submit() boundary (DESIGN.md §15): one
    NaN row folded into SV_global poisons the model for every later
    reader, so the check runs once per batch, not per fold. The test
    runs on the device and only its flag is read back: the host never
    copies the rows. ``uid`` is the batch's, for the ``svc.quarantine``
    span (DESIGN.md §17)."""
    vals = X.values if sparse_rows.is_sparse(X) else X
    with jax.profiler.TraceAnnotation(
            "svc.quarantine", uid=uid, rows=int(X.shape[0]),
            bytes=int(vals.nbytes + y.nbytes)):
        return bool(jax.device_get(_finite_flag(vals, y)))


@functools.partial(jax.jit, static_argnames=("n_max", "width"))
def _stack_jobs(new_rows, sv_rows, n_max: int, width: int):
    """The batched fold's (width, n_max, d) rows in one program: each
    job's new rows ∪ its carried SVs, zero-padded to ``n_max``, then
    all-empty padding jobs up to ``width``. Built op by op, every
    per-job concatenation would stay alive beside the stack."""
    parts = [sparse_rows.pad_rows(sparse_rows.rows_concat(x, sv, axis=0),
                                  n_max - x.shape[0] - sv.shape[0])
             for x, sv in zip(new_rows, sv_rows)]
    parts += [sparse_rows.rows_zeros_like(parts[0])] * (width - len(parts))
    return sparse_rows.rows_stack(parts)


def _snapshot_tree(snap: "ModelSnapshot") -> dict:
    """The checkpointable (array-leaf) view of one stream's snapshot.

    ``rounds``/``history``/``version`` are not array leaves — the
    manifest carries ``rounds`` and ``version``; ``history`` is a
    debugging trace and restores empty.
    """
    m = snap.model
    tree = {"model": {"w": m.w, "b": m.b, "risk": jnp.asarray(m.risk),
                      "sv": dict(m.sv._asdict()),
                      "final": dict(m.final._asdict())}}
    if snap.params is not None:
        tree["params"] = dict(snap.params._asdict())
    return tree


def _abstract_snapshot_tree(cfg: MRSVMConfig, d: int,
                            nnz_cap: Optional[int], has_params: bool,
                            dtypes: Dict[str, str]) -> dict:
    """Rebuild the ``like`` tree of :func:`_snapshot_tree` from the
    manifest's static facts: shapes from (cfg, d, nnz_cap), exact leaf
    dtypes from the recorded :func:`repro.ckpt.checkpoint.leaf_dtypes`
    map — so restore validates instead of guessing."""
    cap = cfg.sv_capacity
    f32 = jnp.float32

    def zf(*shape):
        return jnp.zeros(shape, f32)

    sv = init_sv_buffer(cap, d, f32, nnz_cap=nnz_cap)
    final = BinarySVM(alpha=zf(cap), b=zf(), w=zf(d),
                      epochs_run=jnp.zeros((), jnp.int32),
                      max_violation=zf())
    tree = {"model": {"w": zf(d), "b": zf(), "risk": zf(),
                      "sv": dict(sv._asdict()),
                      "final": dict(final._asdict())}}
    if has_params:
        tree["params"] = dict(cfg.svm.params()._asdict())
    return ckpt.with_dtypes(tree, dtypes)


@dataclasses.dataclass
class MicroBatch:
    """One vectorized message micro-batch queued for a stream."""
    uid: int
    stream: str
    X: Optional[jax.Array]      # dropped (None) once the batch folds
    y: Optional[jax.Array]
    # per-slot accounting (stamped by the service):
    submitted_s: float = 0.0
    admitted_s: float = 0.0
    completed_s: float = 0.0
    wave: int = -1

    @property
    def queue_s(self) -> float:
        """Time spent waiting for admission."""
        return max(self.admitted_s - self.submitted_s, 0.0)

    @property
    def latency_s(self) -> float:
        """Submit → the batch's model swap (NOT the whole-wave wall)."""
        return max(self.completed_s - self.submitted_s, 0.0)


class ModelSnapshot(NamedTuple):
    """Immutable served state of one stream.

    Snapshots are never mutated: a fold builds a NEW snapshot off-line
    (double buffer) and the service swaps the reference atomically.
    ``version`` increments per swap — readers can tag results with the
    exact model that produced them.
    """
    model: MapReduceSVM
    params: Optional[SolverParams]
    version: int


@dataclasses.dataclass
class StreamWaveStats:
    """One admission wave of the streaming service."""
    wave: int
    streams: int        # tenants folded this wave
    batches: int        # micro-batches admitted
    rows: int           # new message rows folded
    batched: bool       # True: one jitted sweep pass; False: plain round
    wall_s: float


class StreamingSVMService:
    """Multi-tenant streaming polarization service.

    One service hosts many tenant *streams* sharing a static
    :class:`MRSVMConfig` shell (shapes / kernel family / loop bounds);
    per-stream hyper-params ride the traced :class:`SolverParams`
    pytree, which is exactly what lets S streams update in one batched
    device pass (DESIGN.md §8/§9).
    """

    def __init__(self, cfg: MRSVMConfig, num_partitions: int = 8,
                 max_batches_per_wave: int = 4,
                 keep_history: bool = False,
                 shuffle_impl: Optional[str] = None,
                 cluster=None,
                 checkpoint_dir: Optional[str] = None,
                 checkpoint_every_waves: int = 1,
                 max_queue_per_stream: Optional[int] = None,
                 shed_policy: str = "drop_oldest",
                 max_streams_per_wave: Optional[int] = None,
                 slo_s: Optional[float] = None,
                 pad_wave_to_bucket: bool = True,
                 fail_on_retrace: bool = False,
                 checkpoint_keep: int = 3,
                 quarantine: bool = True,
                 fold_deadline_s: Optional[float] = None,
                 heartbeat_path: Optional[str] = None,
                 watchdog_handler=None):
        # ``shuffle_impl`` overrides the SV merge transport of the
        # config — any of SHUFFLE_IMPLS, including the two-level
        # "hier" schedule (DESIGN.md §10/§16). The functional folds
        # this host-local
        # service runs have no collective, but the config is the single
        # source of truth for any sharded program derived from the
        # service (launch.steps.build_svm_serve_step / dryrun
        # --shape svm_serve), so the override is applied here.
        if shuffle_impl is not None:
            cfg = dataclasses.replace(cfg, shuffle_impl=shuffle_impl)
        # ``cluster`` (repro.launch.cluster.Cluster) makes the service
        # process-count-aware (DESIGN.md §11): ADMISSION — submit,
        # run_wave, the background scheduler — runs on process 0 only
        # (the coordinator owns the queues and drives the folds), while
        # SNAPSHOTS stay readable everywhere (register/predict/
        # decision_values/snapshot are process-local). None → the
        # historical single-process behaviour, every method enabled.
        # Fault tolerance (DESIGN.md §13): ``checkpoint_dir`` turns on
        # durable snapshots — every registered stream persists on
        # register and after each ``checkpoint_every_waves``-th wave;
        # ``restore`` rebuilds the service from the latest manifest.
        # Admission control: ``max_queue_per_stream`` caps each tenant's
        # backlog (``shed_policy``: 'drop_oldest' sheds the stalest
        # batch, 'reject' refuses the submit), ``max_streams_per_wave``
        # bounds the fold's job-axis width (oldest-waiting streams
        # first), ``slo_s`` counts latency-SLO violations, and
        # ``pad_wave_to_bucket`` pads the job axis to the next power of
        # two so any tenant count reuses log2 compiled sweep programs.
        # ``fail_on_retrace`` arms the invariant linter's retrace
        # detector (DESIGN.md §14): a STEADY-STATE fold — one whose
        # exact input signature (bucket width, row padding, formats)
        # already compiled in this service's lifetime — must hit the
        # jit cache; any compile inside it raises ``RetraceError``
        # naming the recompiled program. First-time signatures warm the
        # cache freely.
        # Degraded-mode survival (DESIGN.md §15): ``checkpoint_keep``
        # retains the last N snapshot *generations* (manifest format 2)
        # so restore can fall back past a corrupt newest one;
        # ``quarantine`` diverts non-finite batches at submit() instead
        # of folding NaN into SV_global; ``fold_deadline_s`` arms a
        # CollectiveWatchdog around each wave's folds (heartbeat at
        # ``heartbeat_path``) — ``watchdog_handler`` overrides the
        # default exit-the-process timeout handler for tests/harnesses.
        if shed_policy not in ("drop_oldest", "reject"):
            raise ValueError(f"unknown shed_policy {shed_policy!r} "
                             "(expected 'drop_oldest' or 'reject')")
        self.cluster = cluster
        self.cfg = cfg
        self.L = num_partitions
        self.max_batches_per_wave = max_batches_per_wave
        self.keep_history = keep_history
        self.checkpoint_dir = checkpoint_dir
        self.checkpoint_every_waves = checkpoint_every_waves
        self.max_queue_per_stream = max_queue_per_stream
        self.shed_policy = shed_policy
        self.max_streams_per_wave = max_streams_per_wave
        self.slo_s = slo_s
        self.pad_wave_to_bucket = pad_wave_to_bucket
        self.fail_on_retrace = fail_on_retrace
        self.checkpoint_keep = checkpoint_keep
        self.quarantine = quarantine
        self.fold_deadline_s = fold_deadline_s
        self.heartbeat_path = heartbeat_path
        self.watchdog_handler = watchdog_handler
        self._fold_signatures: set = set()
        self._retraces = 0
        self.shed: List[MicroBatch] = []
        self.quarantined: List[MicroBatch] = []
        self.restore_fallbacks = 0
        self._retries = 0
        self._watchdog_fires = 0
        self._requeued = 0
        self._slo_violations = 0
        self._waves_since_ckpt = 0
        self._stream_slot: Dict[str, int] = {}
        self._snapshots: Dict[str, ModelSnapshot] = {}
        self._queues: Dict[str, List[MicroBatch]] = {}
        self._history: Dict[str, Dict[int, ModelSnapshot]] = {}
        self._lock = threading.Lock()          # queues + snapshot refs
        self._cv = threading.Condition(self._lock)
        self._wave_lock = threading.Lock()     # serializes folds
        self._ckpt_lock = threading.Lock()     # serializes checkpoints
        self._uid = 0
        self._wave = 0
        # Generation counter resumes past an existing manifest so a new
        # checkpoint NEVER reuses a file name a kept generation record
        # still references (that would corrupt restorable history).
        self._generation = 0
        self._gen_records: List[dict] = []
        if checkpoint_dir is not None:
            man = self._read_manifest(checkpoint_dir)
            if man is not None and man.get("format", 1) >= 2:
                self._generation = int(man.get("generation", -1)) + 1
                self._gen_records = list(man.get("generations", []))
        self.done: List[MicroBatch] = []
        self.stats: List[StreamWaveStats] = []
        self._thread: Optional[threading.Thread] = None
        self._stop_evt = threading.Event()
        self._scheduler_error: Optional[BaseException] = None

    # -- stream lifecycle --------------------------------------------------

    def register(self, stream: str, model: MapReduceSVM,
                 params: Optional[SolverParams] = None) -> ModelSnapshot:
        """Install a stream's initial model (its version-0 snapshot).

        ``params`` must be the :class:`SolverParams` the model was
        trained with (sweep-selected streams), else the config defaults
        are assumed — the same contract as :func:`update_mapreduce`.
        """
        snap = ModelSnapshot(model=model, params=params, version=0)
        with self._lock:
            if stream in self._snapshots:
                raise ValueError(f"stream {stream!r} already registered")
            self._snapshots[stream] = snap
            self._queues[stream] = []
            self._stream_slot[stream] = len(self._stream_slot)
            if self.keep_history:
                self._history[stream] = {0: snap}
        if self.checkpoint_dir is not None and self._admits:
            # a stream is durable from the moment it exists — a crash
            # between register and the first wave must not lose it
            self.checkpoint()
        return snap

    @classmethod
    def restore(cls, cfg: MRSVMConfig, checkpoint_dir: str,
                **kwargs) -> "StreamingSVMService":
        """Rebuild a queues-empty service from the latest manifest.

        Every stream's snapshot restores at its checkpointed version
        (SV buffer, SolverParams, w/b/final/risk); wave and uid
        counters resume from the manifest so post-restore versions and
        uids keep ascending. Queued-but-unfolded batches are NOT
        durable — clients re-submit anything they never saw complete
        (the exactly-once guarantee is at the model level: a fold is in
        the checkpoint iff its swap happened before the save).

        ``cfg`` must match the checkpointed service's shapes
        (``sv_capacity`` is validated here; per-leaf shape/dtype drift
        fails in :func:`repro.ckpt.checkpoint.restore`). Remaining
        kwargs forward to ``__init__`` — ``num_partitions`` and
        ``max_batches_per_wave`` default to their manifest values.
        """
        path = os.path.join(checkpoint_dir, _MANIFEST)
        if not os.path.exists(path):
            raise FileNotFoundError(
                f"no service manifest under {checkpoint_dir!r} — the "
                "service checkpoints on register and every "
                "checkpoint_every_waves-th wave")
        with open(path) as f:
            man = json.load(f)
        if man.get("sv_capacity") != cfg.sv_capacity:
            raise ValueError(
                f"checkpoint was taken at sv_capacity="
                f"{man.get('sv_capacity')} but cfg has {cfg.sv_capacity} "
                "— restore with the training-time config")
        kwargs.setdefault("num_partitions", man["num_partitions"])
        kwargs.setdefault("max_batches_per_wave",
                          man["max_batches_per_wave"])
        svc = cls(cfg, checkpoint_dir=checkpoint_dir, **kwargs)
        if man.get("format", 1) >= 2:
            gens = list(man.get("generations", []))
        else:                          # format-1: one implicit generation
            gens = [{"generation": 0, "wave": man["wave"],
                     "uid": man["uid"], "streams": man["streams"]}]
        errors: List[str] = []
        restored = None
        for rec in reversed(gens):
            try:
                loaded = {}
                for stream in sorted(rec["streams"]):
                    meta = rec["streams"][stream]
                    fpath = os.path.join(checkpoint_dir, meta["file"])
                    want = meta.get("file_crc32")
                    if want is not None and ckpt.file_crc32(fpath) != want:
                        raise ckpt.CorruptCheckpointError(
                            f"{meta['file']}: medium does not match its "
                            f"recorded crc32")
                    like = _abstract_snapshot_tree(
                        cfg, meta["d"], meta["nnz_cap"],
                        meta["has_params"], meta["dtypes"])
                    tree = ckpt.restore(fpath, like,
                                        checksums=meta.get("checksums"))
                    model = MapReduceSVM(
                        w=tree["model"]["w"], b=tree["model"]["b"],
                        sv=SVBuffer(**tree["model"]["sv"]),
                        final=BinarySVM(**tree["model"]["final"]),
                        risk=tree["model"]["risk"], rounds=meta["rounds"],
                        history=())
                    params = (SolverParams(**tree["params"])
                              if meta["has_params"] else None)
                    loaded[stream] = (
                        ModelSnapshot(model=model, params=params,
                                      version=meta["version"]),
                        meta["slot"])
                restored = (rec, loaded)
                break
            except Exception as e:     # this generation is corrupt/missing
                errors.append(f"generation {rec.get('generation')}: {e}")
                faults.count("ckpt_fallbacks")
                svc.restore_fallbacks += 1
        if restored is None:
            raise faults.FaultDetected(
                "ckpt",
                f"no intact snapshot generation under {checkpoint_dir!r}"
                f" ({'; '.join(errors) or 'no generations recorded'})",
                action="restore from an older backup or re-register the "
                       "streams from their training pipelines")
        rec, loaded = restored
        if svc.restore_fallbacks:
            print(f"[svm_stream] newest snapshot generation(s) failed "
                  f"verification — restored generation "
                  f"{rec.get('generation')} instead "
                  f"({svc.restore_fallbacks} skipped)", flush=True)
        with svc._lock:
            for stream, (snap, slot) in loaded.items():
                svc._snapshots[stream] = snap
                svc._queues[stream] = []
                svc._stream_slot[stream] = slot
                if svc.keep_history:
                    svc._history[stream] = {snap.version: snap}
            svc._wave = rec["wave"]
            svc._uid = rec["uid"]
        return svc

    @staticmethod
    def _read_manifest(checkpoint_dir: str) -> Optional[dict]:
        try:
            with open(os.path.join(checkpoint_dir, _MANIFEST)) as f:
                return json.load(f)
        except (OSError, json.JSONDecodeError, ValueError):
            return None

    def checkpoint(self) -> str:
        """Durably snapshot every stream + the service counters;
        returns the manifest path.

        Layout under ``checkpoint_dir``: one flat-npz per stream per
        *generation* (``gen000007_stream0.npz``; atomic tmp→rename,
        :func:`repro.ckpt.checkpoint.save`) plus an atomically-replaced
        JSON manifest (format 2) recording the last
        ``checkpoint_keep`` generations — per-stream per-leaf crc32s
        and the file crc32 ride along, so :meth:`restore` verifies each
        payload and falls BACK past a corrupt newest generation instead
        of restoring silently wrong state. A crash at ANY point leaves
        the previous complete checkpoint installed, never a torn one;
        media of pruned generations are GC'd.
        """
        if self.checkpoint_dir is None:
            raise RuntimeError(
                "service was built without checkpoint_dir")
        os.makedirs(self.checkpoint_dir, exist_ok=True)
        with self._ckpt_lock:
            gen = self._generation
            self._generation += 1
            with self._lock:
                snaps = dict(self._snapshots)
                slots = dict(self._stream_slot)
                wave, uid = self._wave, self._uid
            streams_meta = {}
            for stream, snap in snaps.items():
                fname = f"gen{gen:06d}_stream{slots[stream]}.npz"
                tree = _snapshot_tree(snap)
                crc = ckpt.save(
                    os.path.join(self.checkpoint_dir, fname), tree,
                    on_retry=self._note_retry)
                x = snap.model.sv.x
                sp = sparse_rows.is_sparse(x)
                streams_meta[stream] = {
                    "file": fname, "slot": slots[stream],
                    "version": snap.version,
                    "rounds": int(snap.model.rounds),
                    "d": int(x.shape[1]),
                    "nnz_cap": int(x.nnz_cap) if sp else None,
                    "has_params": snap.params is not None,
                    "dtypes": ckpt.leaf_dtypes(tree),
                    "checksums": ckpt.leaf_checksums(tree),
                    "file_crc32": crc,
                }
            rec = {"generation": gen, "wave": wave, "uid": uid,
                   "streams": streams_meta}
            records = [r for r in self._gen_records
                       if r.get("generation") != gen] + [rec]
            keep = max(int(self.checkpoint_keep), 1)
            dropped, records = records[:-keep], records[-keep:]
            self._gen_records = records
            # Top-level wave/uid/streams mirror the newest generation so
            # format-1 readers (benchmarks, older tooling) keep working.
            ckpt.atomic_write_json(
                os.path.join(self.checkpoint_dir, _MANIFEST),
                {"format": 2, "wave": wave, "uid": uid,
                 "sv_capacity": self.cfg.sv_capacity,
                 "num_partitions": self.L,
                 "max_batches_per_wave": self.max_batches_per_wave,
                 "generation": gen, "generations": records,
                 "streams": streams_meta},
                on_retry=self._note_retry)
            kept = {m["file"] for r in records
                    for m in r["streams"].values()}
            for r in dropped:
                for m in r["streams"].values():
                    if m["file"] not in kept:
                        try:
                            os.remove(os.path.join(self.checkpoint_dir,
                                                   m["file"]))
                        except OSError:
                            pass
            self._waves_since_ckpt = 0
            return os.path.join(self.checkpoint_dir, _MANIFEST)

    def _note_retry(self, attempt: int, exc: BaseException) -> None:
        self._retries += 1

    def streams(self) -> List[str]:
        with self._lock:
            return list(self._snapshots)

    def snapshot(self, stream: str) -> ModelSnapshot:
        """The stream's current served snapshot (atomic reference read)."""
        with self._lock:
            return self._snapshots[stream]

    def history(self, stream: str) -> Dict[int, ModelSnapshot]:
        """version → snapshot (only populated with ``keep_history``)."""
        with self._lock:
            return dict(self._history.get(stream, {}))

    # -- ingest ------------------------------------------------------------

    @property
    def _admits(self) -> bool:
        """Whether THIS process runs admission (process 0, or local)."""
        return self.cluster is None or self.cluster.is_coordinator

    def submit(self, stream: str, X: jax.Array, y: jax.Array) -> int:
        """Queue one vectorized micro-batch; returns its uid. ``X`` is
        dense ``(n, d)`` or blocked-CSR :class:`repro.sparse.SparseRows`
        — whichever format the stream's model serves."""
        return self.submit_many([(stream, X, y)])[0]

    def submit_many(self, batches) -> List[int]:
        """Queue ``(stream, X, y)`` micro-batches under one hold of the
        queue lock, so the scheduler admits them in the same wave (and
        several streams fold together). Returns the uids.

        Admission is coordinator-only on a multi-process cluster: a
        submit on any other process is a routing bug (its queue would
        silently never fold), so it raises instead of enqueueing. A
        dead scheduler raises too — enqueueing behind one grows queues
        that can never fold while readers pin the stale snapshot.
        """
        if self._scheduler_error is not None:
            raise RuntimeError(
                "streaming scheduler died — restart the service (or "
                "StreamingSVMService.restore from its checkpoint) before "
                "submitting more work") from self._scheduler_error
        if not self._admits:
            raise RuntimeError(
                f"stream admission runs on process 0; this is process "
                f"{self.cluster.process_index} of "
                f"{self.cluster.process_count} (snapshots stay readable "
                "here — route submissions to the coordinator)")
        batches = list(batches)
        with jax.profiler.TraceAnnotation("svc.submit",
                                          batches=len(batches)), self._cv:
            uids = [self._enqueue(stream, X, y) for stream, X, y in batches]
            self._cv.notify_all()
        return uids

    def _enqueue(self, stream: str, X, y) -> int:
        """Validate and queue one micro-batch; the caller holds ``_cv``."""
        # featurizer seam: an armed poison_rows fault lands NaN/Inf in
        # the batch exactly where a buggy upstream vectorizer would
        spec = faults.fire("serving.submit", kinds=("poison_rows",))
        if spec is not None:
            X, y = faults.poison_batch(X, y, spec)
        if not sparse_rows.is_sparse(X):
            X = jnp.asarray(X)
        y = jnp.asarray(y)
        if X.ndim != 2 or y.shape[0] != X.shape[0]:
            raise ValueError(f"micro-batch must be (n, d) rows with (n,) "
                             f"labels; got X{X.shape} y{y.shape}")
        if stream not in self._snapshots:
            raise KeyError(f"unregistered stream {stream!r}")
        sv_x = self._snapshots[stream].model.sv.x
        d_model = sv_x.shape[1]
        if X.shape[1] != d_model:
            raise ValueError(
                f"stream {stream!r} serves {d_model}-dim features but "
                f"the batch has {X.shape[1]} — vectorize with the same "
                "featurizer as training")
        sp_model = sparse_rows.is_sparse(sv_x)
        sp_batch = sparse_rows.is_sparse(X)
        if sp_model != sp_batch:
            raise ValueError(
                f"stream {stream!r} serves "
                f"{'sparse' if sp_model else 'dense'} rows but the "
                f"batch is {'sparse' if sp_batch else 'dense'} — "
                "submit the model's row format")
        if sp_batch and X.nnz_cap != sv_x.nnz_cap:
            raise ValueError(
                f"stream {stream!r} serves nnz_cap={sv_x.nnz_cap} "
                f"rows but the batch has nnz_cap={X.nnz_cap} — "
                "re-block with the model's cap")
        if self.quarantine and not _all_finite(X, y, self._uid + 1):
            # NaN/Inf never reaches a fold: one poisoned row in
            # SV_global would corrupt every later wave's model.
            # The batch is acknowledged (uid) but diverted —
            # counted in throughput_report for the operator.
            faults.count("quarantined")
            self._uid += 1
            mb = MicroBatch(uid=self._uid, stream=stream,
                            X=None, y=None,
                            submitted_s=time.time())
            self.quarantined.append(mb)
            return mb.uid
        q = self._queues[stream]
        if (self.max_queue_per_stream is not None
                and len(q) >= self.max_queue_per_stream):
            if self.shed_policy == "reject":
                raise RuntimeError(
                    f"stream {stream!r} queue is at its cap "
                    f"({self.max_queue_per_stream}) — admission "
                    "control rejected the batch (shed_policy="
                    "'reject')")
            # drop_oldest: the stalest queued batch is the least
            # valuable under drift — shed it, keep the fresh one
            old = q.pop(0)
            old.X = old.y = None
            self.shed.append(old)
        self._uid += 1
        mb = MicroBatch(uid=self._uid, stream=stream, X=X, y=y,
                        submitted_s=time.time())
        self._queues[stream].append(mb)
        return mb.uid

    def pending(self) -> int:
        with self._lock:
            return sum(len(q) for q in self._queues.values())

    # -- serve -------------------------------------------------------------

    def decision_values(self, stream: str, X: jax.Array) -> jax.Array:
        """Scores from the stream's CURRENT snapshot. The snapshot
        reference is read once, so an update swapping mid-call can never
        yield a half-updated model (snapshots are immutable)."""
        snap = self.snapshot(stream)
        return mr_decision_values(snap.model, X, self.cfg, params=snap.params)

    def predict(self, stream: str, X: jax.Array,
                with_version: bool = False):
        """±1 polarization labels from the current snapshot."""
        snap = self.snapshot(stream)
        pred = mr_predict(snap.model, X, self.cfg, params=snap.params)
        return (pred, snap.version) if with_version else pred

    # -- wave admission + fold --------------------------------------------

    def _admit(self) -> Dict[str, Tuple[ModelSnapshot, List[MicroBatch]]]:
        """Pop ≤ max_batches_per_wave batches per stream, pairing each
        admitted stream with the snapshot whose SVs the fold carries.
        With ``max_streams_per_wave`` the wave is width-bounded: the
        streams whose HEAD batch has waited longest go first, so a
        narrow fold never starves a tenant."""
        now = time.time()
        admitted: Dict[str, Tuple[ModelSnapshot, List[MicroBatch]]] = {}
        with self._lock:
            ready = sorted((q[0].submitted_s, stream)
                           for stream, q in self._queues.items() if q)
            if self.max_streams_per_wave is not None:
                ready = ready[:self.max_streams_per_wave]
            for _, stream in ready:
                q = self._queues[stream]
                take, self._queues[stream] = (q[:self.max_batches_per_wave],
                                              q[self.max_batches_per_wave:])
                for mb in take:
                    mb.admitted_s = now
                    mb.wave = self._wave
                admitted[stream] = (self._snapshots[stream], take)
        return admitted

    def _swap(self, stream: str, model: MapReduceSVM,
              params: Optional[SolverParams]) -> ModelSnapshot:
        """Atomically publish a fully-materialized new snapshot."""
        # folds are serialised by ``_wave_lock``: this is the version
        # the swap publishes
        with jax.profiler.TraceAnnotation(
                "svc.swap", stream=stream,
                version=self._snapshots[stream].version + 1):
            jax.block_until_ready((model.sv, model.final, model.w, model.b))
            with self._lock:
                old = self._snapshots[stream]
                snap = ModelSnapshot(model=model, params=params,
                                     version=old.version + 1)
                self._snapshots[stream] = snap
                if self.keep_history:
                    self._history[stream][snap.version] = snap
        return snap

    def run_wave(self) -> Optional[StreamWaveStats]:
        """Admit one wave and fold it. Returns its stats, or ``None``
        when every queue was empty. Thread-safe; folds are serialized.
        No-op (``None``) off the coordinator — nothing can be queued
        there (see :meth:`submit`)."""
        if not self._admits:
            return None
        with self._wave_lock, jax.profiler.TraceAnnotation(
                "svc.wave", wave=self._wave):
            t0 = time.time()
            with jax.profiler.TraceAnnotation("svc.admit",
                                              wave=self._wave) as span:
                admitted = self._admit()
                # a comma would split the span's ``name#k=v,k=v#`` form
                span.set_metadata(uids=" ".join(
                    str(mb.uid) for _, take in admitted.values()
                    for mb in take))
            if not admitted:
                return None
            wave_id = self._wave
            self._wave += 1

            names = sorted(admitted)
            joined = {}
            for s in names:
                snap, batches = admitted[s]
                Xn = sparse_rows.rows_concat_all(
                    [mb.X for mb in batches], axis=0)
                yn = jnp.concatenate([mb.y.astype(Xn.dtype)
                                      for mb in batches], axis=0)
                joined[s] = (snap, batches, Xn, yn)

            swapped: List[str] = []
            any_batched = False
            try:
                # scheduler seam: an armed scheduler_kill dies here, so
                # _recover_wave requeues every admitted batch (HEAD of
                # queue) before the error surfaces.
                faults.maybe_raise("serving.wave",
                                   kinds=("scheduler_kill",),
                                   when=wave_id)
                wd_ctx = (faults.CollectiveWatchdog(
                              self.fold_deadline_s,
                              heartbeat_path=self.heartbeat_path,
                              layer="serving",
                              cause=f"wave {wave_id} fold",
                              action="kill the process and restore the "
                                     "service from its last checkpoint "
                                     "generation",
                              on_timeout=self._on_watchdog_timeout)
                          if self.fold_deadline_s is not None
                          else contextlib.nullcontext())
                with wd_ctx as wd:
                    # stall seam: a fold that stops making progress —
                    # bounded sleep past the deadline, so the watchdog
                    # (not the harness's patience) ends it
                    if faults.fire("serving.stall", ("stall",),
                                   when=wave_id) is not None:
                        time.sleep((self.fold_deadline_s or 0.5) * 1.5)
                    for group in self._fold_groups(names, joined):
                        if len(group) == 1:
                            # single tenant: the plain incremental round
                            s = group[0]
                            snap, batches, Xn, yn = joined[s]
                            sig = self._fold_signature(
                                "single", Xn, yn, snap.model.sv)
                            with self._retrace_guard(
                                    sig,
                                    f"run_wave single-tenant fold {s}"):
                                model = update_mapreduce(
                                    snap.model, Xn, yn, self.L,
                                    self.cfg, params=snap.params)
                            self._swap(s, model, snap.params)
                            swapped.append(s)
                        else:
                            any_batched = True
                            self._fold_batched(joined, group, swapped)
                        if wd is not None:
                            wd.beat()
                if wd is not None:
                    wd.check()
            except BaseException:
                self._recover_wave(joined, names, swapped)
                raise

            now = time.time()
            n_batches = n_rows = 0
            for s in names:
                _, batches, Xn, _ = joined[s]
                n_batches += len(batches)
                n_rows += int(Xn.shape[0])
                for mb in batches:
                    mb.completed_s = now
                    if self.slo_s is not None and mb.latency_s > self.slo_s:
                        self._slo_violations += 1
                    # Folded rows live on in SV_global (or were
                    # discarded as non-support); keeping every
                    # historical batch pinned in ``done`` would grow
                    # memory without bound in a long-running service —
                    # only the accounting fields survive.
                    mb.X = mb.y = None
                    self.done.append(mb)
            st = StreamWaveStats(wave=wave_id, streams=len(names),
                                 batches=n_batches, rows=n_rows,
                                 batched=any_batched,
                                 wall_s=now - t0)
            self.stats.append(st)
            if (self.checkpoint_dir is not None
                    and self.checkpoint_every_waves > 0):
                self._waves_since_ckpt += 1
                if self._waves_since_ckpt >= self.checkpoint_every_waves:
                    self.checkpoint()
            return st

    @contextlib.contextmanager
    def _retrace_guard(self, signature: tuple, label: str):
        """Steady-state jit-cache tripwire around one fold
        (DESIGN.md §14). The signature — every folded leaf's
        (shape, dtype) plus the driver width — identifies a compiled
        program family; the first fold of a signature warms the cache,
        any later fold of the SAME signature that still compiles is a
        retrace bug and raises :class:`RetraceError`."""
        if not self.fail_on_retrace:
            self._fold_signatures.add(signature)
            yield
            return
        first = signature not in self._fold_signatures
        with watch_compiles() as stats:
            yield
        self._fold_signatures.add(signature)
        if not first and stats.count:
            self._retraces += stats.count
            raise RetraceError(label, stats.events)

    @staticmethod
    def _fold_signature(kind: str, *trees) -> tuple:
        leaves = jax.tree_util.tree_leaves(trees)
        return (kind,) + tuple((tuple(a.shape), str(a.dtype))
                               for a in leaves)

    def _fold_groups(self, names, joined) -> List[List[str]]:
        """Partition admitted streams into stackable fold groups.

        The batched fold stacks per-job rows on the sweep axis, so jobs
        must agree on (format, d, nnz_cap); a mixed wave — PR 6 sparse
        tenants next to dense ones, or tenants on different hash spaces
        — folds as one sweep pass per group instead of failing."""
        groups: Dict[tuple, List[str]] = {}
        for s in names:
            x = joined[s][0].model.sv.x
            sp = sparse_rows.is_sparse(x)
            key = (sp, int(x.shape[1]), int(x.nnz_cap) if sp else -1)
            groups.setdefault(key, []).append(s)
        return [groups[k] for k in sorted(groups)]

    def _bucket_width(self, n: int) -> int:
        """Job-axis width the fold compiles at: the next power of two
        (elastic waves of 3, 5-8, … tenants share log2 programs
        instead of retracing per width)."""
        if not self.pad_wave_to_bucket or n <= 1:
            return n
        width = 1
        while width < n:
            width *= 2
        return width

    def _recover_wave(self, joined, names, swapped) -> None:
        """Mid-wave failure (worker loss, preemption, OOM): exactly-once
        at the model level.

        Streams whose snapshot already swapped have their batches
        completed — the published model contains them. Every other
        admitted batch goes BACK to the HEAD of its queue with its rows
        still pinned (X/y drop only on completion), so the next wave —
        on whatever mesh survived, or after a checkpoint restart —
        re-admits and re-folds it exactly once."""
        now = time.time()
        done_set = set(swapped)
        with self._lock:
            for s in names:
                _, batches, _, _ = joined[s]
                if s in done_set:
                    for mb in batches:
                        mb.completed_s = now
                        mb.X = mb.y = None
                        self.done.append(mb)
                else:
                    self._queues[s][:0] = batches
                    self._requeued += len(batches)

    def _fold_batched(self, joined, names, swapped) -> None:
        """S admitted streams = S jobs on the sweep's config/batch axis:
        per-job (X, y, mask) + stacked per-stream SolverParams, one
        jitted device pass (DESIGN.md §9). Rows route through the
        format-generic sparse helpers, so blocked-CSR tenants batch the
        same way dense ones do. Each stream appends to ``swapped`` the
        moment its snapshot publishes (recovery bookkeeping)."""
        cap = self.cfg.sv_capacity
        d = joined[names[0]][0].model.sv.x.shape[1]
        n_max = max(int(joined[s][2].shape[0]) for s in names) + cap

        width = self._bucket_width(len(names))
        with jax.profiler.TraceAnnotation("svc.stack", width=width,
                                          rows=n_max):
            Xb = _stack_jobs(tuple(joined[s][2] for s in names),
                             tuple(joined[s][0].model.sv.x for s in names),
                             n_max=n_max, width=width)   # (S', n_max, d)
            ys, ms, ps = [], [], []
            for s in names:
                snap, _, Xn, yn = joined[s]
                sv = snap.model.sv
                n_new = int(Xn.shape[0])
                pad = n_max - n_new - cap
                dt = yn.dtype
                ys.append(jnp.concatenate(
                    [yn, sv.y.astype(dt), jnp.zeros((pad,), dt)], axis=0))
                ms.append(jnp.concatenate(
                    [jnp.ones((n_new,), dt), sv.mask.astype(dt),
                     jnp.zeros((pad,), dt)], axis=0))
                ps.append(snap.params if snap.params is not None
                          else self.cfg.svm.params())
            # Elastic job axis: pad to the bucket width with all-masked
            # zero jobs (their results are discarded below) so a wave of
            # any tenant count reuses the bucket's compiled program.
            for _ in range(width - len(names)):
                ys.append(jnp.zeros_like(ys[0]))
                ms.append(jnp.zeros_like(ms[0]))
                ps.append(ps[0])
            yb = jnp.stack(ys)                       # (S', n_max)
            mb_ = jnp.stack(ms)                      # (S', n_max)
            params_b = stack_params(ps)

        sig = self._fold_signature("batched", Xb, yb, mb_, params_b)
        with self._retrace_guard(
                sig, f"run_wave batched fold ({len(names)} streams)"):
            res = fit_mapreduce_sweep(Xb, yb, self.L, self.cfg, params_b,
                                      mask=mb_)
        for i, s in enumerate(names):            # padding jobs dropped
            snap = joined[s][0]
            model = MapReduceSVM(
                w=res.ws[i], b=res.bs[i],
                sv=compat.tree_map(lambda a: a[i], res.sv),
                final=compat.tree_map(lambda a: a[i], res.final),
                risk=res.risks[i], rounds=int(res.rounds[i]), history=())
            self._swap(s, model, snap.params)
            swapped.append(s)

    def drain(self) -> int:
        """Run waves until every queue is empty; returns waves run."""
        waves = 0
        while self.run_wave() is not None:
            waves += 1
        return waves

    # -- async scheduler ---------------------------------------------------

    def start(self, idle_poll_s: float = 0.05) -> None:
        """Start the background wave scheduler: batches submitted after
        this fold in continuously without blocking the submitter.
        No-op off the coordinator, so symmetric SPMD launch code can
        call it unconditionally."""
        if not self._admits:
            return
        with self._lock:
            if self._thread is not None:
                return
            self._stop_evt.clear()
            self._scheduler_error = None
            self._thread = threading.Thread(
                target=self._scheduler_loop, args=(idle_poll_s,),
                name="svm-stream-scheduler", daemon=True)
            self._thread.start()

    @property
    def scheduler_error(self) -> Optional[BaseException]:
        """The exception that killed the background scheduler, if any."""
        return self._scheduler_error

    def _scheduler_loop(self, idle_poll_s: float) -> None:
        while not self._stop_evt.is_set():
            with self._cv:
                while (not self._stop_evt.is_set()
                       and not any(self._queues.values())):
                    self._cv.wait(timeout=idle_poll_s)
                if self._stop_evt.is_set():
                    return
            try:
                self.run_wave()
            except BaseException as e:
                # A silently dead daemon thread would leave queues
                # growing and readers on the stale snapshot forever —
                # record the error (wait_idle/stop re-raise it) and
                # shut the loop down loudly.
                self._scheduler_error = e
                self._stop_evt.set()
                import traceback
                traceback.print_exc()
                return

    def _on_watchdog_timeout(self, info: dict) -> None:
        self._watchdog_fires += 1
        handler = self.watchdog_handler
        if handler is not None:
            handler(info)
        else:
            faults.exit_handler(info)

    def wait_idle(self, timeout_s: float = 120.0,
                  poll_s: float = 0.01) -> bool:
        """Block until every queue is empty AND no wave is in flight.

        A doomed wait surfaces IMMEDIATELY instead of burning the full
        timeout: a recorded scheduler error re-raises, a scheduler
        thread that died WITHOUT recording one (killed interpreter-side,
        a bug in the loop itself) raises, and queued work with no
        scheduler running at all raises — in every one of those states
        no amount of waiting can drain the queues. Returns ``False``
        only for a genuine timeout (slow folds still in flight)."""
        deadline = time.time() + timeout_s
        while True:
            if self._scheduler_error is not None:
                raise RuntimeError(
                    "streaming scheduler died") from self._scheduler_error
            thread = self._thread
            if (thread is not None and not thread.is_alive()
                    and not self._stop_evt.is_set()):
                raise RuntimeError(
                    "scheduler thread died without recording an error — "
                    "restart the service (restore from its checkpoint "
                    "if one was configured)")
            if thread is None and self.pending() > 0:
                raise RuntimeError(
                    "no scheduler is running but work is queued — call "
                    "start() (or drain() synchronously) first")
            if self.pending() == 0 and not self._wave_lock.locked():
                return True
            if time.time() >= deadline:
                return False
            time.sleep(poll_s)

    def stop(self, drain: bool = True, timeout_s: float = 60.0) -> None:
        """Stop the scheduler thread; optionally fold what's queued.
        Re-raises the error that killed the scheduler, if any. A thread
        that refuses to die within ``timeout_s`` — stranded in a fold
        collective — raises a typed :class:`~repro.faults.FaultDetected`
        instead of silently leaking the daemon."""
        thread = self._thread
        if thread is None:
            return
        self._stop_evt.set()
        with self._cv:
            self._cv.notify_all()
        thread.join(timeout=timeout_s)
        if thread.is_alive():
            raise faults.FaultDetected(
                "serving",
                f"scheduler thread refused to die within {timeout_s:.0f}s"
                " (likely stranded in a fold collective)",
                action="kill the process and restart from the last "
                       "checkpoint generation")
        self._thread = None
        if self._scheduler_error is not None:
            raise RuntimeError(
                "streaming scheduler died") from self._scheduler_error
        if drain:
            self.drain()

    # -- reporting ---------------------------------------------------------

    def throughput_report(self) -> Dict[str, float]:
        """The service's counts and rates so far. ``wall_s`` is the
        summed wall time of the folds (waves) alone; ``rows_per_s``
        divides the rows folded by the time from the first completed
        batch's submission to the last one's completion, so the time
        between waves (submits, queueing, idle) counts against it."""
        lats = [mb.latency_s for mb in self.done]
        queues = [mb.queue_s for mb in self.done]
        rows = sum(s.rows for s in self.stats)
        wall = sum(s.wall_s for s in self.stats)
        span = (max(mb.completed_s for mb in self.done)
                - min(mb.submitted_s for mb in self.done)
                if self.done else 0.0)
        return {
            "batches": len(self.done),
            "rows": rows,
            "waves": len(self.stats),
            "wall_s": round(wall, 3),
            "rows_per_s": round(rows / max(span, 1e-9), 1),
            "mean_latency_s": round(float(np.mean(lats)), 4) if lats else 0.0,
            "p95_latency_s": (round(float(np.percentile(lats, 95)), 4)
                              if lats else 0.0),
            "mean_queue_s": (round(float(np.mean(queues)), 4)
                             if queues else 0.0),
            "shed": len(self.shed),
            "requeued": self._requeued,
            "slo_violations": self._slo_violations,
            "fold_programs": len(self._fold_signatures),
            "retraces": self._retraces,
            "quarantined": len(self.quarantined),
            "retries": self._retries,
            "watchdog_fires": self._watchdog_fires,
        }
