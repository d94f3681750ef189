"""Cluster runtime: the process-count-agnostic multi-host substrate
(DESIGN.md §11).

The paper's premise is that ONE machine cannot hold the quadratic SVM
training problem — training is distributed across nodes and only
support vectors travel (Çatak 2014; CloudSVM arXiv:1301.0082). Every
layer above this module is written against the *global* topology this
module reports, so the same program runs unchanged on one process
(laptop / CI), N CPU processes (``examples/multihost_svm.py``,
``make test-dist-mp``), or a real multi-host TPU slice:

  init_cluster()      — wraps ``jax.distributed.initialize`` (explicit
                        --coordinator/--num-processes/--process-id
                        flags, env auto-detect, 1-process fast path
                        that never opens a coordinator);
  Cluster             — topology handle: process index/count, local vs
                        global devices, coordinator gating;
  make_global_array() — assembles each process's local numpy shard
                        into a globally-sharded ``jax.Array``
                        (``jax.make_array_from_process_local_data``
                        with a ``from_single_device_arrays`` fallback
                        behind :mod:`repro.compat`).

Ordering contract: ``init_cluster`` MUST run before the first use of
the jax backend in the process (``jax.devices()``, any op). The
distributed client and the CPU gloo collectives are wired into the
backend at its first initialization, so the entry points in
``launch/{train,serve}.py`` parse flags and call this before anything
else touches a device.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Optional, Sequence

from repro import compat, faults

# One process-wide runtime: jax.distributed can only initialize once,
# so repeated init_cluster() calls return the same handle.
_CLUSTER: Optional["Cluster"] = None


@dataclasses.dataclass(frozen=True)
class ClusterConfig:
    """How to join (or not join) a multi-process cluster.

    All ``None`` → single process, unless the ``REPRO_COORDINATOR`` /
    ``REPRO_NUM_PROCESSES`` / ``REPRO_PROCESS_ID`` environment
    variables (or their ``JAX_``-prefixed spellings) supply the triple
    — the env auto-detect path for launchers that template per-process
    env instead of argv.
    """
    coordinator: Optional[str] = None      # "host:port" of process 0
    num_processes: Optional[int] = None
    process_id: Optional[int] = None
    # Faked host devices for multi-process CPU runs; set BEFORE backend
    # init (XLA locks the per-process device count at first use).
    local_device_count: Optional[int] = None
    cpu_collectives: str = "gloo"
    initialization_timeout: int = 120      # s; bounds a dead-peer hang
    # Coordinator handshake retry (DESIGN.md §15): a restarted process
    # often races the coordinator coming back up; a bounded
    # retry-with-backoff turns that window into a survived transient
    # instead of a launch failure.
    handshake_retries: int = 3
    handshake_backoff_s: float = 0.5

    def resolved(self) -> "ClusterConfig":
        """Fill unset fields from the environment (explicit args win)."""
        def env(*names):
            for n in names:
                v = os.environ.get(n)
                if v:
                    return v
            return None

        coord = self.coordinator or env("REPRO_COORDINATOR",
                                        "JAX_COORDINATOR_ADDRESS")
        num = self.num_processes
        if num is None:
            v = env("REPRO_NUM_PROCESSES", "JAX_NUM_PROCESSES")
            num = int(v) if v else None
        pid = self.process_id
        if pid is None:
            v = env("REPRO_PROCESS_ID", "JAX_PROCESS_ID")
            pid = int(v) if v else None
        return dataclasses.replace(self, coordinator=coord,
                                   num_processes=num, process_id=pid)

    @property
    def is_multiprocess(self) -> bool:
        return (self.num_processes or 1) > 1 or self.coordinator is not None


@dataclasses.dataclass(frozen=True)
class Cluster:
    """Topology of the running job, as every layer above sees it."""
    process_index: int
    process_count: int
    coordinator: Optional[str] = None

    @property
    def is_distributed(self) -> bool:
        return self.process_count > 1

    @property
    def is_coordinator(self) -> bool:
        """Process 0: the one host that ingests/admits/reports."""
        return self.process_index == 0

    # -- devices (queried live: backend state, not config) ----------------

    def devices(self) -> list:
        """GLOBAL devices, in process-major order (jax device-id order
        groups each process's local devices contiguously — the layout
        the per-host row loaders assume)."""
        import jax
        return jax.devices()

    def local_devices(self) -> list:
        import jax
        return jax.local_devices()

    @property
    def device_count(self) -> int:
        return len(self.devices())

    @property
    def local_device_count(self) -> int:
        return len(self.local_devices())

    def describe(self) -> dict:
        """Topology report (JSON-able) for logs and dry-run artifacts."""
        import jax
        return {
            "process_index": self.process_index,
            "process_count": self.process_count,
            "coordinator": self.coordinator,
            "platform": jax.devices()[0].platform,
            "local_devices": self.local_device_count,
            "global_devices": self.device_count,
        }

    # -- per-host shard assembly -------------------------------------------

    def make_global_array(self, mesh, spec, local_data,
                          global_shape: Optional[Sequence[int]] = None):
        """Globally-sharded ``jax.Array`` from THIS process's shard.

        ``local_data`` is the process-local block of the global array:
        the concatenation, along the dimension ``spec`` shards, of the
        shards this process's devices hold (for a 1-process cluster
        that is simply the whole array — the result then equals
        ``jax.device_put(local_data, NamedSharding(mesh, spec))``).
        """
        from jax.sharding import NamedSharding, PartitionSpec
        sharding = (NamedSharding(mesh, spec)
                    if isinstance(spec, PartitionSpec) else spec)
        if global_shape is not None:
            global_shape = tuple(int(s) for s in global_shape)
        return compat.make_array_from_process_local_data(
            sharding, local_data, global_shape)


def local_cluster() -> Cluster:
    """The 1-process topology (no coordinator, backend as-is)."""
    return Cluster(process_index=0, process_count=1)


def init_cluster(cfg: Optional[ClusterConfig] = None) -> Cluster:
    """Join the cluster described by ``cfg`` (+ env) and report topology.

    Single-process fast path: with no coordinator configured anywhere
    this performs NO distributed handshake at all — no coordinator
    socket, no timeout, no backend side effects — and just returns the
    1-process :class:`Cluster`. Multi-process: enables cross-process
    CPU collectives (gloo) where the backend is CPU, sets the faked
    local device count if requested, and calls
    ``jax.distributed.initialize`` via :mod:`repro.compat`.

    Idempotent: the first call wins; later calls return the same
    handle (jax.distributed can only initialize once per process).
    """
    global _CLUSTER
    if _CLUSTER is not None:
        return _CLUSTER
    cfg = (cfg or ClusterConfig()).resolved()

    if not cfg.is_multiprocess:
        _CLUSTER = local_cluster()
        return _CLUSTER

    # Validate the FULL triple before any side effect: past this point
    # gloo gets wired into the backend config, which a process without
    # a distributed client cannot survive (see enable_cpu_collectives).
    if cfg.coordinator is None or cfg.num_processes is None \
            or cfg.process_id is None:
        raise ValueError(
            "multi-process launch needs the full triple: coordinator "
            f"address, num_processes and process_id (got {cfg})")
    if cfg.local_device_count:
        flag = (f"--xla_force_host_platform_device_count="
                f"{cfg.local_device_count}")
        os.environ["XLA_FLAGS"] = \
            (os.environ.get("XLA_FLAGS", "") + " " + flag).strip()
    platform = os.environ.get("JAX_PLATFORMS", "").split(",")[0]
    if platform in ("", "cpu"):
        compat.enable_cpu_collectives(cfg.cpu_collectives)

    def handshake():
        faults.maybe_raise("cluster.handshake", kinds=("handshake_flake",))
        compat.distributed_initialize(
            coordinator_address=cfg.coordinator,
            num_processes=cfg.num_processes,
            process_id=cfg.process_id,
            initialization_timeout=cfg.initialization_timeout)

    faults.retry_with_backoff(
        handshake, attempts=cfg.handshake_retries,
        base_s=cfg.handshake_backoff_s, layer="cluster",
        cause=f"coordinator handshake with {cfg.coordinator}",
        action="check that process 0 is reachable at the coordinator "
               "address, then relaunch this process (the restarted "
               "process rejoins from the last checkpoint)")
    _CLUSTER = Cluster(process_index=compat.process_index(),
                       process_count=compat.process_count(),
                       coordinator=cfg.coordinator)
    return _CLUSTER


# ---------------------------------------------------------------------------
# Entry-point wiring (launch/{train,serve}.py, examples).
# ---------------------------------------------------------------------------

def add_cluster_flags(parser) -> None:
    """The launch flags every entry point shares."""
    parser.add_argument("--coordinator", default=None,
                        help="process 0 address host:port "
                             "(multi-process launch)")
    parser.add_argument("--num-processes", type=int, default=None)
    parser.add_argument("--process-id", type=int, default=None)
    parser.add_argument("--local-devices", type=int, default=None,
                        help="faked host devices per process "
                             "(multi-process CPU)")
    parser.add_argument("--cluster-timeout", type=int, default=120,
                        help="jax.distributed initialization timeout (s) "
                             "— bounds how long a restarted process "
                             "waits for dead peers to rejoin")


def cluster_config_from_args(args) -> ClusterConfig:
    return ClusterConfig(coordinator=args.coordinator,
                         num_processes=args.num_processes,
                         process_id=args.process_id,
                         local_device_count=args.local_devices,
                         initialization_timeout=args.cluster_timeout)


def simulated_topology(num_processes: int, device_count: int) -> dict:
    """Per-host split of a ``device_count``-chip job over
    ``num_processes`` hosts — the dry-run's view of a topology it is
    not actually running (``dryrun --processes N``)."""
    if device_count % num_processes != 0:
        raise ValueError(f"{device_count} devices do not split over "
                         f"{num_processes} processes")
    return {"process_count": num_processes,
            "devices_per_process": device_count // num_processes}
