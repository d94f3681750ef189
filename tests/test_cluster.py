"""Cluster-runtime regressions (ISSUE 5 satellites): the 1-process
fast path must stay a no-op (no coordinator handshake), and
make_global_array must round-trip against plain ``jax.device_put`` on
a single host.
The real multi-process behaviour is tests/test_multihost.py."""
import argparse

import jax
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import compat
from repro.launch import cluster as cluster_lib
from repro.launch.cluster import (ClusterConfig, add_cluster_flags,
                                  cluster_config_from_args, init_cluster,
                                  local_cluster, simulated_topology)
from repro.launch.mesh import make_cluster_mesh, make_host_mesh


@pytest.fixture(autouse=True)
def _fresh_runtime(monkeypatch):
    """init_cluster is process-global (jax.distributed initializes
    once); isolate each test's view of it."""
    monkeypatch.setattr(cluster_lib, "_CLUSTER", None)


def test_init_cluster_single_process_is_noop_fast_path(monkeypatch):
    """No coordinator configured anywhere → NO distributed handshake:
    jax.distributed.initialize must never be called (a 1-process
    launch needs no open port, no timeout, no gloo)."""
    def boom(*a, **k):
        raise AssertionError("distributed handshake on the 1-process path")
    monkeypatch.setattr(jax.distributed, "initialize", boom)
    monkeypatch.setattr(compat, "enable_cpu_collectives", boom)
    for var in ("REPRO_COORDINATOR", "JAX_COORDINATOR_ADDRESS",
                "REPRO_NUM_PROCESSES", "JAX_NUM_PROCESSES"):
        monkeypatch.delenv(var, raising=False)
    c = init_cluster()
    assert c.process_count == 1 and c.process_index == 0
    assert not c.is_distributed and c.is_coordinator
    assert c.device_count == len(jax.devices())
    assert c.local_device_count == len(jax.local_devices())
    # idempotent: the second call returns the same handle
    assert init_cluster() is c


def test_cluster_config_env_autodetect(monkeypatch):
    monkeypatch.setenv("REPRO_COORDINATOR", "somehost:1234")
    monkeypatch.setenv("REPRO_NUM_PROCESSES", "4")
    monkeypatch.setenv("REPRO_PROCESS_ID", "2")
    cfg = ClusterConfig().resolved()
    assert cfg.coordinator == "somehost:1234"
    assert cfg.num_processes == 4 and cfg.process_id == 2
    assert cfg.is_multiprocess
    # explicit args beat the environment
    cfg = ClusterConfig(process_id=0).resolved()
    assert cfg.process_id == 0


def test_cluster_flags_roundtrip():
    ap = argparse.ArgumentParser()
    add_cluster_flags(ap)
    cfg = cluster_config_from_args(ap.parse_args(
        ["--coordinator", "localhost:9911", "--num-processes", "2",
         "--process-id", "1", "--local-devices", "4"]))
    assert cfg == ClusterConfig(coordinator="localhost:9911",
                                num_processes=2, process_id=1,
                                local_device_count=4)
    # no flags → the single-process config
    assert not cluster_config_from_args(ap.parse_args([])).is_multiprocess


def test_incomplete_multiprocess_config_raises(monkeypatch):
    monkeypatch.setattr(cluster_lib, "_CLUSTER", None)
    with pytest.raises(ValueError, match="triple"):
        init_cluster(ClusterConfig(coordinator="localhost:1"))


def _roundtrip(spec, local, global_shape):
    c = local_cluster()
    n = len(jax.devices())
    mesh = make_host_mesh(n, 1)
    arr = c.make_global_array(mesh, spec, local, global_shape)
    ref = jax.device_put(local, NamedSharding(mesh, spec))
    np.testing.assert_array_equal(np.asarray(arr), np.asarray(ref))
    assert arr.sharding.is_equivalent_to(ref.sharding, local.ndim)
    return arr


def test_make_global_array_roundtrips_against_device_put():
    """On one host the process-local shard IS the whole array, so
    make_global_array must agree with jax.device_put exactly —
    sharded rows and fully-replicated buffers alike."""
    n = len(jax.devices())
    rows = np.arange(4 * n * 3, dtype=np.float32).reshape(4 * n, 3)
    _roundtrip(P("data"), rows, rows.shape)
    _roundtrip(P(), rows, rows.shape)                      # replicated
    _roundtrip(P("data"), np.arange(2 * n, dtype=np.int32), (2 * n,))


def test_make_cluster_mesh_process_order():
    c = local_cluster()
    mesh = make_cluster_mesh(c)
    assert mesh.axis_names == ("data", "model")
    assert mesh.shape["data"] == len(jax.devices())
    assert list(mesh.devices.flat) == list(jax.devices())


def test_simulated_topology():
    assert simulated_topology(4, 256) == {"process_count": 4,
                                          "devices_per_process": 64}
    with pytest.raises(ValueError):
        simulated_topology(3, 256)


def test_streaming_service_admission_is_coordinator_only():
    """svm_stream on a non-coordinator process: snapshots readable,
    admission refused (submit raises; start/run_wave no-op)."""
    from repro.core import MRSVMConfig, SVMConfig, fit_mapreduce
    from repro.serving import StreamingSVMService

    rng = np.random.default_rng(0)
    X = rng.normal(0, 1, (64, 8)).astype(np.float32)
    y = np.sign(X @ rng.normal(0, 1, 8).astype(np.float32) + 1e-3)
    cfg = MRSVMConfig(sv_capacity=16, max_rounds=2,
                      svm=SVMConfig(C=1.0, max_epochs=8))
    model = fit_mapreduce(X, y, 4, cfg)

    replica = cluster_lib.Cluster(process_index=1, process_count=2)
    svc = StreamingSVMService(cfg, num_partitions=4, cluster=replica)
    svc.register("s0", model)
    assert svc.predict("s0", X).shape == (64,)      # snapshot readable
    assert svc.snapshot("s0").version == 0
    with pytest.raises(RuntimeError, match="process 0"):
        svc.submit("s0", X, y)
    svc.start()                                     # symmetric-SPMD no-op
    assert svc._thread is None
    assert svc.run_wave() is None

    coord = cluster_lib.Cluster(process_index=0, process_count=2)
    svc0 = StreamingSVMService(cfg, num_partitions=4, cluster=coord)
    svc0.register("s0", model)
    svc0.submit("s0", X, y)                         # coordinator admits
    assert svc0.run_wave() is not None
    assert svc0.snapshot("s0").version == 1
