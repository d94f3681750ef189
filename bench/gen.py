"""Inputs from ``--seed``: hashed TF×IDF-like message rows, made on the
device.

Every row is identified by ``(tenant, batch, row)``; the batch is made
from the seed by one jitted call, so the same seed gives the same rows
in any order of generation, and the reference can make a batch again
after the window. The row model copies the repository's synthetic
TF×IDF rows (non-negative, L2-normalised, a planted linear separator,
labels ``sign(x·w + 1e-3)``) and the serve launcher's per-tenant drift
(the separator turns along a per-tenant direction, one step per batch).

Columns are drawn one per stratum of width ``d // nnz_max``, so the
indices of a row are distinct, and ``nnz`` of a row is uniform in
``[nnz_min, nnz_max]``. Rows come in the configuration's
``row_format``: ``dense`` rows are ``(rows, d)`` arrays; ``sparse_csr``
rows are the pair ``(cols, vals)`` of ``(rows, nnz_cap)`` column ids
(int32) and values, ``nnz_cap`` the largest ``row_nnz``, with a row's
dead slots ``(0, 0.0)``: the program's blocked-CSR layout, which the
drivers wrap as its row type. Both come from the same draws, so a CSR
batch scattered into a dense one is the dense batch, bit for bit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_MASK32 = 0xFFFFFFFF


def base_key(seed: int) -> jax.Array:
    """A key from a seed of any size up to 64 bits."""
    if seed < 0 or seed >= 1 << 64:
        raise ValueError(f"seed {seed} is outside [0, 2**64)")
    k = jax.random.key(0)
    k = jax.random.fold_in(k, (seed >> 32) & _MASK32)
    return jax.random.fold_in(k, seed & _MASK32)


def stream_key(seed: int, tenant: int, batch: int) -> jax.Array:
    """Key of one batch. ``batch`` -1 is the tenant's archive (its
    initial rows), -3 its separator."""
    k = jax.random.fold_in(base_key(seed), tenant)
    return jax.random.fold_in(k, batch + 3)


@functools.partial(jax.jit, static_argnames=("rows", "d", "nnz_min",
                                             "nnz_max", "signal_dims",
                                             "dtype", "csr"))
def _batch(key, tkey, step, drift, *, rows, d, nnz_min, nnz_max,
           signal_dims, dtype, csr=False):
    kc, kv, kn = jax.random.split(key, 3)
    stride = d // nnz_max
    offs = jax.random.randint(kc, (rows, nnz_max), 0, stride)
    cols = jnp.arange(nnz_max, dtype=jnp.int32)[None, :] * stride + offs
    nnz = jax.random.randint(kn, (rows, 1), nnz_min, nnz_max + 1)
    live = jnp.arange(nnz_max)[None, :] < nnz
    vals = jax.random.uniform(kv, (rows, nnz_max), jnp.float32, 0.05, 1.0)
    vals = jnp.where(live, vals, 0.0)
    vals = vals / jnp.linalg.norm(vals, axis=1, keepdims=True)
    vals = vals.astype(dtype)
    cols = jnp.where(live, cols, 0)
    # the tenant's separator at this step of its drift
    ks, k0, kd = jax.random.split(tkey, 3)
    sig = jax.random.choice(ks, d, (signal_dims,), replace=False)
    w = (jnp.zeros((d,), jnp.float32).at[sig].set(
        jax.random.normal(k0, (signal_dims,))
        + drift * step * jax.random.normal(kd, (signal_dims,))))
    score = jnp.sum(jnp.take(w, cols) * vals.astype(jnp.float32), axis=1)
    y = jnp.where(score + 1e-3 >= 0, 1.0, -1.0).astype(dtype)
    if csr:
        return (cols, vals), y
    r = jnp.broadcast_to(jnp.arange(rows)[:, None], cols.shape)
    X = jnp.zeros((rows, d), dtype).at[r, cols].add(vals)
    return X, y


def nnz_cap(cfg: dict) -> int:
    """Slots of a blocked-CSR row: the largest ``row_nnz``."""
    return int(cfg["row_nnz"][1])


class RowModel:
    """The rows of one configuration. ``make(tenant, batch, rows)``
    returns ``(X, y)`` on the device: ``X`` dense ``(rows, d)``, or
    ``(cols, vals)`` where ``csr``."""

    def __init__(self, cfg: dict, seed: int):
        self.seed = seed
        self.d = int(cfg["num_features"])
        if cfg["row_format"] not in ("dense", "sparse_csr"):
            raise ValueError(f"no rows of format {cfg['row_format']!r}")
        self.csr = cfg["row_format"] == "sparse_csr"
        self.dtype = jnp.dtype(cfg["dtype"])
        self.nnz_min, self.nnz_max = (int(v) for v in cfg["row_nnz"])
        self.signal_dims = int(cfg["signal_dims"])
        self.drift = float(cfg["drift_per_batch"])
        if self.d % self.nnz_max:
            raise ValueError("the largest row_nnz must divide d")

    def make(self, tenant: int, batch: int, rows: int, dtype=None):
        step = max(batch, 0)
        X, y = _batch(stream_key(self.seed, tenant, batch),
                      stream_key(self.seed, tenant, -3),
                      jnp.float32(step), jnp.float32(self.drift),
                      rows=rows, d=self.d, nnz_min=self.nnz_min,
                      nnz_max=self.nnz_max, signal_dims=self.signal_dims,
                      dtype=self.dtype if dtype is None else dtype,
                      csr=self.csr)
        return X, y
