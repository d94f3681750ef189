"""The JAX substrate every other module imports (DESIGN.md §7).

The repo targets the installed JAX, 0.9.0. The sharding and collective
names it uses live here, under one spelling each, so a later JAX that
moves them is absorbed in this file alone:

* ``shard_map`` with the ``check_vma`` replication checker;
* ``pvary`` (``jax.lax.pcast(..., to="varying")``) for loop carries
  built from constants inside ``shard_map``;
* meshes: ``make_mesh`` gives every axis the ``Auto`` type, so bare
  ``PartitionSpec`` constraints and ``shard_map`` keep the meaning the
  model code was written for (``jax.make_mesh`` defaults to
  ``Explicit`` axes);
* collectives over a tuple of mesh axes (flattened row-major index);
* the multi-process runtime (``jax.distributed.initialize``, gloo CPU
  collectives, ``jax.make_array_from_process_local_data``).
"""
from __future__ import annotations

from typing import Any, Callable, Optional, Sequence, Tuple

import jax


def jax_version() -> Tuple[int, ...]:
    """Installed JAX version as a comparable int tuple, e.g. (0, 9, 0)."""
    parts = []
    for p in jax.__version__.split(".")[:3]:
        digits = "".join(c for c in p if c.isdigit())
        parts.append(int(digits or 0))
    return tuple(parts)


tree_map = jax.tree.map


# ---------------------------------------------------------------------------
# shard_map and varying-manual-axes (vma) marking.
# ---------------------------------------------------------------------------

def shard_map(f: Callable, *, mesh, in_specs, out_specs,
              check_vma: Optional[bool] = None, **kwargs) -> Callable:
    """``jax.shard_map``; ``check_vma=None`` keeps JAX's default."""
    if check_vma is not None:
        kwargs["check_vma"] = check_vma
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kwargs)


def pvary(tree: Any, axes: Sequence[str]) -> Any:
    """Mark a pytree as device-varying over shard_map manual ``axes``.

    while_loop carries built from constants type as axis-invariant,
    while loop-body outputs are varying; this casts the carry up front.
    Outside ``shard_map`` it is the identity.
    """
    axes = tuple(axes)
    if not axes:
        return tree
    return tree_map(lambda x: jax.lax.pcast(x, axes, to="varying"), tree)


# ---------------------------------------------------------------------------
# Mesh construction.
# ---------------------------------------------------------------------------

def make_abstract_mesh(axis_sizes: Sequence[int],
                       axis_names: Sequence[str]):
    """Device-free ``AbstractMesh`` (dry-run sharding rules)."""
    from jax.sharding import AbstractMesh
    return AbstractMesh(tuple(axis_sizes), tuple(axis_names))


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str]):
    """``jax.make_mesh`` over the local devices, every axis ``Auto``."""
    from jax.sharding import AxisType
    names = tuple(axis_names)
    return jax.make_mesh(tuple(axis_shapes), names,
                         axis_types=(AxisType.Auto,) * len(names))


def to_shardings(mesh, specs):
    """PartitionSpec pytree → NamedSharding pytree bound to ``mesh``."""
    from jax.sharding import NamedSharding, PartitionSpec
    is_spec = lambda s: isinstance(s, PartitionSpec)
    return tree_map(lambda s: NamedSharding(mesh, s) if is_spec(s) else s,
                    specs, is_leaf=is_spec)


def cost_analysis(compiled) -> dict:
    """Cost dict of a compiled executable (empty when XLA gives none)."""
    return compiled.cost_analysis() or {}


def set_mesh(mesh):
    """Context manager activating ``mesh`` for bare-PartitionSpec
    sharding constraints."""
    return jax.set_mesh(mesh)


# ---------------------------------------------------------------------------
# Collectives over one mesh axis name or a tuple of them.
# ---------------------------------------------------------------------------

def _axis_name(axis_names):
    return axis_names if isinstance(axis_names, str) else tuple(axis_names)


def axis_index(axis_names) -> jax.Array:
    """Flattened (row-major) device index over one or several axes."""
    return jax.lax.axis_index(_axis_name(axis_names))


def psum(x, axis_names):
    return jax.lax.psum(x, _axis_name(axis_names))


def pmax(x, axis_names):
    return jax.lax.pmax(x, _axis_name(axis_names))


def all_gather(x, axis_names, *, axis: int = 0, tiled: bool = False):
    return jax.lax.all_gather(x, _axis_name(axis_names), axis=axis,
                              tiled=tiled)


def all_gather_groups(x, axis_names, groups, *, axis: int = 0,
                      tiled: bool = False):
    """Grouped ``all_gather``: each device gathers only within its row
    of ``groups`` — lists of row-major FLATTENED indices over
    ``axis_names`` (matching :func:`axis_index`) that must partition
    the devices. The intra-host leg of the two-level hier shuffle
    (DESIGN.md §16): group = the devices of one host, so the gather
    rides the fast local interconnect and never crosses the network.
    """
    return jax.lax.all_gather(x, _axis_name(axis_names), axis=axis,
                              tiled=tiled,
                              axis_index_groups=[list(g) for g in groups])


def axis_size(axis_names) -> int:
    """Product of the named manual-axis sizes (trace-time constant)."""
    if isinstance(axis_names, str):
        axis_names = (axis_names,)
    n = 1
    for a in axis_names:
        n *= jax.lax.psum(1, a)
    return n


def ppermute(x, axis_names, perm):
    """``jax.lax.ppermute``; ``perm`` is over the flattened index of
    ``axis_names`` (matching :func:`axis_index`)."""
    names = _axis_name(axis_names)
    if not isinstance(names, str) and len(names) == 1:
        names = names[0]
    return jax.lax.ppermute(x, names, perm)


def ring_shift(tree: Any, axis_names) -> Any:
    """Send each device's pytree to its flattened-ring successor:
    device ``i`` receives the value of device ``i-1 mod N`` — one stage
    of the ring-pipelined SV shuffle."""
    n = axis_size(axis_names)
    perm = [(i, (i + 1) % n) for i in range(n)]
    return tree_map(lambda x: ppermute(x, axis_names, perm), tree)


# ---------------------------------------------------------------------------
# Multi-process runtime (repro.launch.cluster rides on these).
# ---------------------------------------------------------------------------

def distributed_initialize(coordinator_address: Optional[str] = None,
                           num_processes: Optional[int] = None,
                           process_id: Optional[int] = None,
                           **kwargs) -> None:
    """``jax.distributed.initialize`` with an explicit triple, so JAX
    looks nothing up (no cluster auto-detection, no metadata server)."""
    jax.distributed.initialize(coordinator_address=coordinator_address,
                               num_processes=num_processes,
                               process_id=process_id, **kwargs)


def enable_cpu_collectives(impl: str = "gloo") -> None:
    """Turn on cross-process CPU collectives (needed for any
    multi-process run on the CPU backend; TPU/GPU ignore it).

    Call ONLY on the distributed path, between
    :func:`distributed_initialize` being decided and the first backend
    use: gloo collectives are constructed at CPU-client init from the
    distributed runtime client, so enabling them in a single-process
    program breaks backend creation outright (``distributed_client:
    NoneType``) — which is exactly why ``init_cluster``'s 1-process
    fast path never touches this."""
    jax.config.update("jax_cpu_collectives_implementation", impl)


def make_array_from_process_local_data(sharding, local_data,
                                       global_shape: Optional[Tuple[int, ...]]
                                       = None):
    """Assemble a global ``jax.Array`` from THIS process's shard:
    ``local_data`` is the concatenation (along the sharded dimension)
    of the shards this process's addressable devices hold."""
    return jax.make_array_from_process_local_data(sharding, local_data,
                                                  global_shape)


def process_index() -> int:
    return int(jax.process_index())


def process_count() -> int:
    return int(jax.process_count())
