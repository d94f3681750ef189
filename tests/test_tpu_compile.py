"""Compiles of the main path for a described TPU v5e, at real widths.

Nothing runs here: each test lowers and compiles a program for chip 0
of a described ``v5e:2x2`` topology, so what the chip's compiler would
refuse (a kernel that does not lower, more VMEM than a kernel may use,
a program that does not fit HBM) fails here at no chip time. The
topology is described inside a fixture, never at import, and the
persistent compilation cache is off around these compiles (what they
would write cannot be read back without a chip).
"""
import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

REPO = Path(__file__).resolve().parents[1]
D = 131072                 # svm-tfidf num_features
CAP = 2048                 # svm-tfidf sv_capacity
HBM = 15.75e9              # what the v5e compiler allows one program
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = bool(jax.config.jax_enable_compilation_cache)
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _program_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


def _svm_cfg(shuffle="ring"):
    from repro.configs import get_config
    from repro.core import MRSVMConfig, SVMConfig
    c = get_config("svm-tfidf")
    assert (c.num_features, c.sv_capacity) == (D, CAP)
    return MRSVMConfig(sv_capacity=c.sv_capacity, gamma=1e-4, max_rounds=3,
                       shuffle_impl=shuffle,
                       svm=SVMConfig(C=c.C, max_epochs=c.max_epochs))


@pytest.mark.parametrize("kind", ["linear", "rbf"])
def test_dense_gram_compiles(one_chip, kind):
    from repro.kernels.gram import gram
    x = jax.ShapeDtypeStruct((512, D), BF16, sharding=one_chip)
    compiled = jax.jit(
        lambda a, b: gram(a, b, 0.5, 0.0, kind=kind, interpret=False)
    ).lower(x, x).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("kind", ["linear", "rbf"])
def test_sparse_gram_compiles(one_chip, kind):
    from repro.kernels.gram import sparse_gram
    from repro.sparse import SparseRows

    def rows(n):
        return SparseRows(
            jax.ShapeDtypeStruct((n, 256), jnp.int32, sharding=one_chip),
            jax.ShapeDtypeStruct((n, 256), BF16, sharding=one_chip), D)

    compiled = jax.jit(
        lambda a, b: sparse_gram(a, b, 0.5, 0.0, kind=kind,
                                 interpret=False)
    ).lower(rows(512), rows(384)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_sharded_round_compiles_one_chip(topo):
    """The train phase's round: 1-chip data mesh, 8192 rows, ring."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.core.mapreduce_svm import SVBuffer, build_sharded_round

    mesh = Mesh(np.asarray(topo.devices[:1]), ("data",))
    rows, rep = NamedSharding(mesh, P("data")), NamedSharding(mesh, P())
    per = 8192
    s = lambda shape, dt, sh: jax.ShapeDtypeStruct(shape, dt, sharding=sh)
    sv = SVBuffer(x=s((CAP, D), BF16, rep), y=s((CAP,), BF16, rep),
                  alpha=s((CAP,), BF16, rep),
                  ids=s((CAP,), jnp.int32, rep), mask=s((CAP,), BF16, rep))
    fn = build_sharded_round(mesh, ("data",), _svm_cfg(), per)
    compiled = fn.lower(s((per, D), BF16, rows), s((per,), BF16, rows),
                        s((per,), BF16, rows), sv).compile()
    assert _program_bytes(compiled) < HBM


def _smoke_rows_per_wave() -> int:
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", REPO / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.SERVE_ROWS_PER_WAVE


def test_serve_fold_compiles_at_smoke_shape(one_chip):
    """The two-tenant wave fold exactly as chip_smoke.py runs it. The
    budget is the fold's measured size (8.60 GB) plus 5%: a change that
    grows it past that must first show the smoke run still fits."""
    from repro.core import sweep
    from repro.core.mapreduce_svm import SVBuffer
    from repro.core.svm import SolverParams

    S, L = 2, 8
    n = _smoke_rows_per_wave() + CAP      # new rows ∪ carried SVs per job
    s = lambda shape, dt=BF16: jax.ShapeDtypeStruct(shape, dt,
                                                    sharding=one_chip)
    per = -(-n // L)
    svb = SVBuffer(x=s((S, CAP, D)), y=s((S, CAP)), alpha=s((S, CAP)),
                   ids=s((S, CAP), jnp.int32), mask=s((S, CAP)))
    eff = SolverParams(*[s((S,), jnp.float32) for _ in range(6)])
    compiled = sweep._sweep_round_jit.lower(
        s((S, n, D)), s((S, L, per)), s((S, L, per)), svb, eff,
        cfg=_svm_cfg(), x_ax=0, m_ax=0, L=L).compile()
    assert _program_bytes(compiled) <= 1.05 * 8.60e9
    # the (L, per + cap, d) union is never materialized: before the
    # solver read the SV rows in place it cost 2 × 5.4 GB of temp per
    # tenant at this width
    assert compiled.memory_analysis().temp_size_in_bytes < 4e9
