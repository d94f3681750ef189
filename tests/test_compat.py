"""Unit tests for the JAX substrate (repro.compat)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro import compat


def test_jax_version_tuple():
    v = compat.jax_version()
    assert isinstance(v, tuple) and len(v) == 3
    assert all(isinstance(p, int) for p in v)
    assert v >= (0, 4, 0)


def test_tree_map():
    out = compat.tree_map(lambda a, b: a + b, {"x": 1, "y": (2, 3)},
                          {"x": 10, "y": (20, 30)})
    assert out == {"x": 11, "y": (22, 33)}


# ---------------------------------------------------------------------------
# pvary: a no-op on values, inside shard_map or outside it.
# ---------------------------------------------------------------------------

def test_pvary_empty_axes_is_identity():
    x = jnp.arange(3.0)
    assert compat.pvary(x, ()) is x


def test_pvary_nonempty_axes_never_raises():
    tree = (jnp.zeros((4,)), jnp.asarray(1.0))
    out = compat.pvary(tree, ("data",))     # outside shard_map
    for a, b in zip(jax.tree_util.tree_leaves(tree),
                    jax.tree_util.tree_leaves(out)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_svm_pvary_shim_and_vma_axes_path():
    """fit_binary with non-empty vma_axes (the sharded reducer call
    signature) must run outside shard_map too."""
    from repro.core.svm import SVMConfig, _pvary, fit_binary
    x = {"a": jnp.ones((2, 2))}
    out = _pvary(x, ("data",))
    np.testing.assert_array_equal(np.asarray(out["a"]), np.asarray(x["a"]))

    X = jax.random.normal(jax.random.PRNGKey(0), (32, 4))
    y = jnp.sign(X[:, 0] + 1e-3)
    model = fit_binary(X, y, cfg=SVMConfig(C=1.0, max_epochs=10),
                       vma_axes=("data",))
    assert float(jnp.max(model.alpha)) >= 0.0


# ---------------------------------------------------------------------------
# Mesh construction.
# ---------------------------------------------------------------------------

def test_make_abstract_mesh():
    mesh = compat.make_abstract_mesh((16, 16), ("data", "model"))
    assert mesh.shape["data"] == 16 and mesh.shape["model"] == 16
    assert tuple(mesh.axis_names) == ("data", "model")


def test_make_abstract_mesh_3d():
    mesh = compat.make_abstract_mesh((2, 16, 16), ("pod", "data", "model"))
    assert mesh.shape["pod"] == 2
    assert tuple(mesh.axis_names) == ("pod", "data", "model")


def test_make_mesh_local_devices():
    mesh = compat.make_mesh((len(jax.devices()),), ("data",))
    assert mesh.shape["data"] == len(jax.devices())


# ---------------------------------------------------------------------------
# shard_map wrapper: check_vma passthrough + collectives.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("check_vma", [None, False])
def test_shard_map_psum(check_vma):
    mesh = compat.make_mesh((1,), ("data",))
    fn = compat.shard_map(lambda x: compat.psum(jnp.sum(x), ("data",)),
                          mesh=mesh, in_specs=(P("data"),), out_specs=P(),
                          check_vma=check_vma)
    assert float(jax.jit(fn)(jnp.arange(4.0))) == 6.0


def test_axis_index_multi_axis():
    mesh = compat.make_mesh((1, 1), ("a", "b"))
    fn = compat.shard_map(
        lambda x: x + compat.axis_index(("a", "b")).astype(x.dtype),
        mesh=mesh, in_specs=(P(),), out_specs=P(), check_vma=False)
    assert float(jax.jit(fn)(jnp.asarray(1.0))) == 1.0


def test_all_gather_and_pmax():
    mesh = compat.make_mesh((1,), ("data",))
    def body(x):
        g = compat.all_gather(x, ("data",), tiled=True)
        return g, compat.pmax(jnp.max(x), ("data",))
    fn = compat.shard_map(body, mesh=mesh, in_specs=(P("data"),),
                          out_specs=(P(), P()), check_vma=False)
    g, m = jax.jit(fn)(jnp.arange(4.0))
    assert g.shape == (4,) and float(m) == 3.0


# ---------------------------------------------------------------------------
# Ring-pipelined shuffle primitives (ISSUE 4 tentpole).
# ---------------------------------------------------------------------------

def test_ring_shift_single_device_identity():
    """A 1-device ring is the identity — and ring_shift must map over a
    whole pytree (the SV chunk + packed sideband of the ring merge)."""
    mesh = compat.make_mesh((1,), ("data",))
    fn = compat.shard_map(
        lambda x: compat.ring_shift((x, x * 2.0), ("data",)),
        mesh=mesh, in_specs=(P("data"),), out_specs=(P("data"), P("data")),
        check_vma=False)
    a, b = jax.jit(fn)(jnp.arange(4.0))
    np.testing.assert_array_equal(np.asarray(a), np.arange(4.0))
    np.testing.assert_array_equal(np.asarray(b), 2.0 * np.arange(4.0))


def test_ppermute_single_axis():
    mesh = compat.make_mesh((1,), ("data",))
    fn = compat.shard_map(
        lambda x: compat.ppermute(x, ("data",), [(0, 0)]),
        mesh=mesh, in_specs=(P("data"),), out_specs=P("data"),
        check_vma=False)
    np.testing.assert_array_equal(np.asarray(jax.jit(fn)(jnp.arange(2.0))),
                                  np.arange(2.0))
