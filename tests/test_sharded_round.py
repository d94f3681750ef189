"""Sharded-vs-functional MapReduce round equivalence (ISSUE 1 tentpole).

The distributed mode (shard_map over the ``data`` mesh axis, via
repro.compat) must reproduce the functional mode (vmap over a leading
partition axis) bit-for-bit in structure: same per-reducer risks, same
merged global SV buffer.

Runs in-process when ≥8 devices exist (e.g. under
``XLA_FLAGS=--xla_force_host_platform_device_count=8``, see
``make test-dist``); otherwise re-executes itself in a subprocess with
the flag set, since XLA fixes the device count at first backend init.
"""
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

REPO = Path(__file__).resolve().parents[1]
NDEV = 8


def _problem(n=512, d=12):
    X = jax.random.normal(jax.random.PRNGKey(0), (n, d))
    w = jax.random.normal(jax.random.PRNGKey(1), (d,))
    y = jnp.sign(X @ w)
    return X, y, jnp.ones((n,))


def _functional_reference(X, y, mask, cfg, rounds):
    from repro.core.mapreduce_svm import init_sv_buffer, mapreduce_round
    n, d = X.shape
    per = n // NDEV
    Xp = X.reshape(NDEV, per, d)
    yp = y.reshape(NDEV, per)
    mp = mask.reshape(NDEV, per)
    sv = init_sv_buffer(cfg.sv_capacity, d)
    risks = None
    for _ in range(rounds):
        out = mapreduce_round(Xp, yp, mp, sv, cfg)
        sv, risks = out.sv, out.risks
    return sv, risks


def _assert_round_equivalence(mesh_shape, mesh_axes, rounds=3,
                              shuffle_impl="allgather",
                              hier_num_hosts=None):
    from repro import compat
    from repro.core import MRSVMConfig, SVMConfig
    from repro.core.mapreduce_svm import build_sharded_round, init_sv_buffer

    X, y, mask = _problem()
    n, d = X.shape
    # ring/hier: wire dtype = data dtype so the transport is bit-exact
    # and the functional reference stays the strict oracle (the bf16
    # wire is exercised separately with bf16-representable data)
    cfg = MRSVMConfig(sv_capacity=64, svm=SVMConfig(C=1.0, max_epochs=15),
                      shuffle_impl=shuffle_impl,
                      shuffle_wire_dtype="float32",
                      hier_num_hosts=hier_num_hosts)

    mesh = compat.make_mesh(mesh_shape, mesh_axes)
    data_axes = tuple(a for a in mesh_axes if a != "model")
    fn = build_sharded_round(mesh, data_axes, cfg, n // NDEV)
    sv_s = init_sv_buffer(cfg.sv_capacity, d)
    risks_s = None
    for _ in range(rounds):
        sv_s, risks_s, w_s, b_s = fn(X, y, mask, sv_s)

    sv_f, risks_f = _functional_reference(X, y, mask, cfg, rounds)

    # same per-reducer risks (device order == partition order: rows are
    # sharded contiguously over the flattened data axes)
    np.testing.assert_allclose(np.asarray(risks_s), np.asarray(risks_f),
                               rtol=1e-4, atol=1e-5)
    # same merged SV buffer: ids, live count, evidence, feature rows
    np.testing.assert_array_equal(np.asarray(sv_s.ids), np.asarray(sv_f.ids))
    np.testing.assert_array_equal(np.asarray(sv_s.mask), np.asarray(sv_f.mask))
    np.testing.assert_allclose(np.asarray(sv_s.alpha), np.asarray(sv_f.alpha),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(sv_s.x), np.asarray(sv_f.x),
                               rtol=1e-5, atol=1e-6)
    # the selected hypothesis is one of the reducers', replicated
    assert np.asarray(w_s).shape == (d,)
    assert np.asarray(b_s).shape == ()


def _in_subprocess(check_name: str):
    """Re-run one check with 8 faked host devices (own process, since
    the device count is locked at first backend init)."""
    code = (f"import sys; sys.path.insert(0, {str(REPO / 'tests')!r}); "
            f"import test_sharded_round as t; t.{check_name}(); "
            "print('SHARDED_ROUND_OK')")
    from conftest import subprocess_env
    env = subprocess_env(
        XLA_FLAGS="--xla_force_host_platform_device_count=8",
        PYTHONPATH=str(REPO / "src"))
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600, env=env)
    assert "SHARDED_ROUND_OK" in r.stdout, r.stdout + r.stderr


def _assert_gram_round_equivalence(gram_impl: str, rounds=2):
    """use_gram=True through build_sharded_round ≡ the functional round
    (ISSUE 2 satellite / ROADMAP: the Gram path — including the Pallas
    kernel — must be exercised under the sharded mode, not only the
    functional one)."""
    from repro import compat
    from repro.core import MRSVMConfig, SVMConfig
    from repro.core.mapreduce_svm import (build_sharded_round,
                                          init_sv_buffer, mapreduce_round)

    X, y, mask = _problem(n=256, d=8)
    n, d = X.shape
    cfg = MRSVMConfig(sv_capacity=32, svm=SVMConfig(
        C=1.0, max_epochs=10, use_gram=True, gram_impl=gram_impl))

    mesh = compat.make_mesh((NDEV,), ("data",))
    fn = build_sharded_round(mesh, ("data",), cfg, n // NDEV)
    sv_s = init_sv_buffer(cfg.sv_capacity, d)
    for _ in range(rounds):
        sv_s, risks_s, w_s, b_s = fn(X, y, mask, sv_s)

    per = n // NDEV
    Xp = X.reshape(NDEV, per, d)
    yp = y.reshape(NDEV, per)
    mp = mask.reshape(NDEV, per)
    sv_f = init_sv_buffer(cfg.sv_capacity, d)
    for _ in range(rounds):
        out = mapreduce_round(Xp, yp, mp, sv_f, cfg)
        sv_f, risks_f = out.sv, out.risks

    np.testing.assert_allclose(np.asarray(risks_s), np.asarray(risks_f),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(sv_s.ids), np.asarray(sv_f.ids))
    np.testing.assert_allclose(np.asarray(sv_s.alpha), np.asarray(sv_f.alpha),
                               rtol=1e-4, atol=1e-5)


def _check_1d():
    _assert_round_equivalence((NDEV,), ("data",))


def _check_pod_2d():
    # multi-axis data sharding: exercises compat.axis_index over a tuple
    _assert_round_equivalence((2, NDEV // 2), ("pod", "data"))


def _check_ring_1d():
    # ISSUE 4 tentpole: the ring-pipelined merge must reproduce the
    # functional round exactly (f32 wire ≡ no quantization)
    _assert_round_equivalence((NDEV,), ("data",), shuffle_impl="ring")


def _check_ring_pod_2d():
    # ring over the flattened ("pod", "data") index — multi-axis ppermute
    _assert_round_equivalence((2, NDEV // 2), ("pod", "data"),
                              shuffle_impl="ring")


def _check_ring_bf16_wire(rounds=3, shuffle_impl="ring",
                          hier_num_hosts=None):
    """The production wire dtype: with bf16-representable rows the wire
    round-trip is lossless, so the packed transport ≡ allgather stays
    strict."""
    import dataclasses as dc

    import jax.numpy as jnp
    from repro import compat
    from repro.core import MRSVMConfig, SVMConfig
    from repro.core.mapreduce_svm import build_sharded_round, init_sv_buffer

    X, y, mask = _problem()
    X = X.astype(jnp.bfloat16).astype(jnp.float32)
    y = jnp.sign(X @ jax.random.normal(jax.random.PRNGKey(1), (X.shape[1],)))
    n, d = X.shape
    cfg_a = MRSVMConfig(sv_capacity=64, svm=SVMConfig(C=1.0, max_epochs=15))
    cfg_r = dc.replace(cfg_a, shuffle_impl=shuffle_impl,   # bf16 wire default
                       hier_num_hosts=hier_num_hosts)
    mesh = compat.make_mesh((NDEV,), ("data",))
    fa = build_sharded_round(mesh, ("data",), cfg_a, n // NDEV)
    fr = build_sharded_round(mesh, ("data",), cfg_r, n // NDEV)
    sv_a = init_sv_buffer(cfg_a.sv_capacity, d)
    sv_r = sv_a._replace(x=sv_a.x.astype(jnp.bfloat16))
    for _ in range(rounds):
        sv_a, risks_a, w_a, b_a = fa(X, y, mask, sv_a)
        sv_r, risks_r, w_r, b_r = fr(X, y, mask, sv_r)
    np.testing.assert_allclose(np.asarray(risks_a), np.asarray(risks_r),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(sv_a.ids), np.asarray(sv_r.ids))
    np.testing.assert_allclose(np.asarray(sv_a.alpha),
                               np.asarray(sv_r.alpha), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(sv_a.x),
                               np.asarray(sv_r.x).astype(np.float32),
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(w_a), np.asarray(w_r),
                               rtol=1e-5, atol=1e-6)


def _assert_sparse_round_equivalence(shuffle_impl: str, rounds=3,
                                     n=512, d=64, nnz=8, cap=16,
                                     hier_num_hosts=None):
    """ISSUE 6 tentpole invariant: the blocked-CSR sharded round — SV
    buffer, shuffle wire and all — must reproduce the DENSE functional
    reference at matched data (sparse rows densified for the oracle).
    An f32 wire keeps the transport bit-exact; indices ship bitcast and
    are exact under any wire dtype."""
    import dataclasses as dc

    from repro import compat, sparse
    from repro.core import MRSVMConfig, SVMConfig
    from repro.core.mapreduce_svm import build_sharded_round, init_sv_buffer
    from repro.data import svm_rows

    Xd, y = svm_rows(n, d, seed=3, nnz=nnz)
    Xd, y = jnp.asarray(Xd), jnp.asarray(y)
    mask = jnp.ones((n,))
    Xs = sparse.from_dense(Xd, cap)          # lossless: nnz < cap
    np.testing.assert_array_equal(np.asarray(sparse.to_dense(Xs)),
                                  np.asarray(Xd))

    cfg_d = MRSVMConfig(sv_capacity=64, svm=SVMConfig(C=1.0, max_epochs=15),
                        shuffle_impl=shuffle_impl,
                        shuffle_wire_dtype="float32",
                        hier_num_hosts=hier_num_hosts)
    cfg_s = dc.replace(cfg_d, svm=dc.replace(
        cfg_d.svm, row_format="sparse_csr", nnz_cap=cap))

    mesh = compat.make_mesh((NDEV,), ("data",))
    fn = build_sharded_round(mesh, ("data",), cfg_s, n // NDEV)
    sv_s = init_sv_buffer(cfg_s.sv_capacity, d, nnz_cap=cap)
    risks_s = None
    for _ in range(rounds):
        sv_s, risks_s, w_s, b_s = fn(Xs, y, mask, sv_s)

    sv_f, risks_f = _functional_reference(Xd, y, mask, cfg_d, rounds)

    np.testing.assert_allclose(np.asarray(risks_s), np.asarray(risks_f),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(sv_s.ids), np.asarray(sv_f.ids))
    np.testing.assert_array_equal(np.asarray(sv_s.mask),
                                  np.asarray(sv_f.mask))
    np.testing.assert_allclose(np.asarray(sv_s.alpha), np.asarray(sv_f.alpha),
                               rtol=1e-4, atol=1e-5)
    # the merged buffer stays blocked-CSR end to end; densified it is
    # the dense run's buffer (f32 wire, distinct-index rows)
    assert sparse.is_sparse(sv_s.x) and sv_s.x.nnz_cap == cap
    np.testing.assert_allclose(np.asarray(sparse.to_dense(sv_s.x)),
                               np.asarray(sv_f.x), rtol=1e-5, atol=1e-6)
    assert np.asarray(w_s).shape == (d,)     # hypothesis stays dense


def _assert_sparse_gram_round_equivalence(rounds=2, n=256, d=32,
                                          nnz=4, cap=8):
    """pallas_sparse Gram under the sharded round ≡ the dense xla Gram
    functional reference at matched data."""
    import dataclasses as dc

    from repro import compat, sparse
    from repro.core import MRSVMConfig, SVMConfig
    from repro.core.mapreduce_svm import build_sharded_round, init_sv_buffer
    from repro.data import svm_rows

    Xd, y = svm_rows(n, d, seed=5, nnz=nnz)
    Xd, y = jnp.asarray(Xd), jnp.asarray(y)
    mask = jnp.ones((n,))
    Xs = sparse.from_dense(Xd, cap)

    cfg_d = MRSVMConfig(sv_capacity=32, svm=SVMConfig(
        C=1.0, max_epochs=10, use_gram=True, gram_impl="xla"),
        shuffle_wire_dtype="float32")
    cfg_s = dc.replace(cfg_d, svm=dc.replace(
        cfg_d.svm, gram_impl="pallas_sparse", row_format="sparse_csr",
        nnz_cap=cap))

    mesh = compat.make_mesh((NDEV,), ("data",))
    fn = build_sharded_round(mesh, ("data",), cfg_s, n // NDEV)
    sv_s = init_sv_buffer(cfg_s.sv_capacity, d, nnz_cap=cap)
    risks_s = None
    for _ in range(rounds):
        sv_s, risks_s, w_s, b_s = fn(Xs, y, mask, sv_s)

    sv_f, risks_f = _functional_reference(Xd, y, mask, cfg_d, rounds)

    np.testing.assert_allclose(np.asarray(risks_s), np.asarray(risks_f),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(sv_s.ids), np.asarray(sv_f.ids))
    np.testing.assert_allclose(np.asarray(sv_s.alpha), np.asarray(sv_f.alpha),
                               rtol=1e-4, atol=1e-5)


def _check_hier_1d():
    # ISSUE 10 tentpole: the two-level hier merge (2 simulated hosts ×
    # 4 locals) must reproduce the functional round exactly (f32 wire)
    _assert_round_equivalence((NDEV,), ("data",), shuffle_impl="hier",
                              hier_num_hosts=2)


def _check_hier_pod_2d():
    # hier over the flattened ("pod", "data") index — multi-axis
    # grouped all_gather + slice-exchange ppermute
    _assert_round_equivalence((2, NDEV // 2), ("pod", "data"),
                              shuffle_impl="hier", hier_num_hosts=2)


def _check_hier_bf16_wire():
    _check_ring_bf16_wire(shuffle_impl="hier", hier_num_hosts=2)


def _check_tree_converge():
    """converge_impl="tree" (recursive-doubling readback) ≡ the flat
    psum readback, transport-independent, on 8 devices. Summation
    order differs (log-depth pairwise vs backend reduce) so risks get
    a float tolerance; everything downstream of the argmin-selected
    hypothesis must agree exactly."""
    import dataclasses as dc

    from repro import compat
    from repro.core import MRSVMConfig, SVMConfig
    from repro.core.mapreduce_svm import build_sharded_round, init_sv_buffer

    X, y, mask = _problem()
    n, d = X.shape
    cfg_p = MRSVMConfig(sv_capacity=64, svm=SVMConfig(C=1.0, max_epochs=15),
                        shuffle_impl="hier", hier_num_hosts=2,
                        shuffle_wire_dtype="float32")
    cfg_t = dc.replace(cfg_p, converge_impl="tree")
    mesh = compat.make_mesh((NDEV,), ("data",))
    fp = build_sharded_round(mesh, ("data",), cfg_p, n // NDEV)
    ft = build_sharded_round(mesh, ("data",), cfg_t, n // NDEV)
    sv_p = init_sv_buffer(cfg_p.sv_capacity, d)
    sv_t = init_sv_buffer(cfg_t.sv_capacity, d)
    for _ in range(3):
        sv_p, risks_p, w_p, b_p = fp(X, y, mask, sv_p)
        sv_t, risks_t, w_t, b_t = ft(X, y, mask, sv_t)
    np.testing.assert_allclose(np.asarray(risks_p), np.asarray(risks_t),
                               rtol=1e-6, atol=1e-7)
    np.testing.assert_array_equal(np.asarray(sv_p.ids), np.asarray(sv_t.ids))
    np.testing.assert_array_equal(np.asarray(sv_p.x), np.asarray(sv_t.x))
    np.testing.assert_array_equal(np.asarray(w_p), np.asarray(w_t))


def _check_gram_xla():
    _assert_gram_round_equivalence("xla")


def _check_gram_pallas():
    _assert_gram_round_equivalence("pallas")


def _check_sparse_1d():
    _assert_sparse_round_equivalence("allgather")


def _check_sparse_ring_1d():
    _assert_sparse_round_equivalence("ring")


def _check_sparse_hier_1d():
    _assert_sparse_round_equivalence("hier", hier_num_hosts=2)


def _check_sparse_gram_pallas():
    _assert_sparse_gram_round_equivalence()


def test_sharded_round_matches_functional():
    if len(jax.devices()) >= NDEV:
        _check_1d()
    else:
        _in_subprocess("_check_1d")


def test_sharded_round_matches_functional_pod_mesh():
    if len(jax.devices()) >= NDEV:
        _check_pod_2d()
    else:
        _in_subprocess("_check_pod_2d")


def test_sharded_round_gram_path():
    if len(jax.devices()) >= NDEV:
        _check_gram_xla()
    else:
        _in_subprocess("_check_gram_xla")


def test_sharded_round_pallas_gram_path():
    if len(jax.devices()) >= NDEV:
        _check_gram_pallas()
    else:
        _in_subprocess("_check_gram_pallas")


def test_ring_round_matches_functional():
    if len(jax.devices()) >= NDEV:
        _check_ring_1d()
    else:
        _in_subprocess("_check_ring_1d")


def test_ring_round_matches_functional_pod_mesh():
    if len(jax.devices()) >= NDEV:
        _check_ring_pod_2d()
    else:
        _in_subprocess("_check_ring_pod_2d")


def test_ring_round_bf16_wire_matches_allgather():
    if len(jax.devices()) >= NDEV:
        _check_ring_bf16_wire()
    else:
        _in_subprocess("_check_ring_bf16_wire")


def test_hier_round_matches_functional():
    if len(jax.devices()) >= NDEV:
        _check_hier_1d()
    else:
        _in_subprocess("_check_hier_1d")


def test_hier_round_matches_functional_pod_mesh():
    if len(jax.devices()) >= NDEV:
        _check_hier_pod_2d()
    else:
        _in_subprocess("_check_hier_pod_2d")


def test_hier_round_bf16_wire_matches_allgather():
    if len(jax.devices()) >= NDEV:
        _check_hier_bf16_wire()
    else:
        _in_subprocess("_check_hier_bf16_wire")


def test_tree_converge_matches_psum():
    if len(jax.devices()) >= NDEV:
        _check_tree_converge()
    else:
        _in_subprocess("_check_tree_converge")


def test_sparse_hier_round_matches_dense_functional():
    if len(jax.devices()) >= NDEV:
        _check_sparse_hier_1d()
    else:
        _in_subprocess("_check_sparse_hier_1d")


def test_sparse_round_matches_dense_functional():
    if len(jax.devices()) >= NDEV:
        _check_sparse_1d()
    else:
        _in_subprocess("_check_sparse_1d")


def test_sparse_ring_round_matches_dense_functional():
    if len(jax.devices()) >= NDEV:
        _check_sparse_ring_1d()
    else:
        _in_subprocess("_check_sparse_ring_1d")


def test_sparse_pallas_gram_round_matches_dense_functional():
    if len(jax.devices()) >= NDEV:
        _check_sparse_gram_pallas()
    else:
        _in_subprocess("_check_sparse_gram_pallas")
