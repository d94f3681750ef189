"""Pallas TPU kernel: tiled Gram matrix K = k(X, Zᵀ).

The kernel-SVM reducer's dominant cost is the (n × n) Gram matrix
(paper: O(m²) space is *why* MapReduce partitioning exists). On TPU we
tile it for the MXU: grid over (n/bm, m/bn, d/bk) with (bm, bk)×(bk, bn)
VMEM blocks accumulating into a float32 (bm, bn) output block; the
kernel transform (rbf/poly) is fused into the last k-step so K never
round-trips to HBM in raw dot-product form.

``gamma``/``coef0`` are TRACED scalar operands, not trace-time
constants: they ride in as (1, 1) blocks (the SMEM scalar-input
pattern), so a :class:`~repro.core.svm.SolverParams` sweep over kernel
scales reuses ONE compiled kernel — and the sweep subsystem's
vmap-over-configs batches straight through the pallas_call. Only the
operator choice stays static: ``kind`` picks the fused transform and
``degree`` must be an integer exponent (a traced float ``pow`` would
NaN on negative bases).

Block shapes default to 256×256×512 — MXU-aligned (multiples of 128)
and ≤ ~1.3 MB/input block, comfortably inside the ~16 MB/core VMEM
budget with double buffering.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _gram_kernel(gamma_ref, coef0_ref, x_ref, z_ref, rownorm_ref,
                 colnorm_ref, o_ref, *, kind: str, degree: int,
                 k_steps: int):
    """One (bm, bn) output tile; grid dim 2 walks the shared d axis."""
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...].astype(jnp.float32)          # (bm, bk)
    z = z_ref[...].astype(jnp.float32)          # (bn, bk)
    o_ref[...] += jax.lax.dot_general(
        x, z, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _finalize():
        acc = o_ref[...]
        gamma = gamma_ref[0, 0]
        coef0 = coef0_ref[0, 0]
        if kind == "poly":
            o_ref[...] = (gamma * acc + coef0) ** degree
        elif kind == "rbf":
            sq = rownorm_ref[...].T + colnorm_ref[...] - 2.0 * acc
            o_ref[...] = jnp.exp(-gamma * jnp.maximum(sq, 0.0))
        # linear: accumulator already is K


@functools.partial(jax.jit, static_argnames=("kind", "degree", "bm", "bn",
                                             "bk", "interpret"))
def gram(X: jax.Array, Z: jax.Array, gamma=1.0, coef0=0.0, *,
         kind: str = "linear", degree: int = 3,
         bm: int = 256, bn: int = 256, bk: int = 512,
         interpret: bool = True) -> jax.Array:
    """K (n, m) = k(X (n, d), Z (m, d)). Pads to block multiples.

    ``gamma``/``coef0`` may be Python floats or traced scalars — they
    are operands of the compiled kernel either way.
    """
    n, d = X.shape
    m = Z.shape[0]
    bm_, bn_, bk_ = min(bm, _ceil(n)), min(bn, _ceil(m)), min(bk, _ceil(d))
    n_p, m_p, d_p = _pad_to(n, bm_), _pad_to(m, bn_), _pad_to(d, bk_)
    Xp = jnp.pad(X, ((0, n_p - n), (0, d_p - d)))
    Zp = jnp.pad(Z, ((0, m_p - m), (0, d_p - d)))
    rown = jnp.sum(Xp.astype(jnp.float32) ** 2, axis=1, keepdims=True)  # (n,1)
    coln = jnp.sum(Zp.astype(jnp.float32) ** 2, axis=1, keepdims=True).T
    g = jnp.asarray(gamma, jnp.float32).reshape(1, 1)
    c0 = jnp.asarray(coef0, jnp.float32).reshape(1, 1)

    k_steps = d_p // bk_
    scalar = pl.BlockSpec((1, 1), lambda i, j, k: (0, 0))
    out = pl.pallas_call(
        functools.partial(_gram_kernel, kind=kind, degree=degree,
                          k_steps=k_steps),
        grid=(n_p // bm_, m_p // bn_, k_steps),
        in_specs=[
            scalar,
            scalar,
            pl.BlockSpec((bm_, bk_), lambda i, j, k: (i, k)),
            pl.BlockSpec((bn_, bk_), lambda i, j, k: (j, k)),
            pl.BlockSpec((1, bm_), lambda i, j, k: (0, i)),
            pl.BlockSpec((1, bn_), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm_, bn_), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n_p, m_p), jnp.float32),
        interpret=interpret,
    )(g, c0, Xp, Zp, rown.T, coln)
    return out[:n, :m]


def _ceil(x: int, to: int = 128) -> int:
    return max(to, (x + to - 1) // to * to)


def _pad_to(x: int, block: int) -> int:
    return (x + block - 1) // block * block


# ---------------------------------------------------------------------------
# Sparse Gram: blocked-CSR rows (ISSUE 6, gram_impl="pallas_sparse").
# ---------------------------------------------------------------------------

_SLOT_CHUNK = 8   # x-side slots per grid step (one sublane tile)


def _sparse_gram_kernel(gamma_ref, coef0_ref, xi_ref, xv_ref, zi_ref,
                        zv_ref, rownorm_ref, colnorm_ref, o_ref, *,
                        kind: str, degree: int, z_slots: int,
                        k_steps: int):
    """One (bm, bn) tile from index/value blocks (no dense (·, d) tile
    ever exists). The contraction is an index-match accumulate: the
    x-side slots whose column id equals z-side slot q's contribute
    ``xv · zv[q]``. Padding slots are (index 0, value 0) on BOTH sides,
    so every spurious 0==0 match multiplies a zero value.
    O(bm·bn·px·pz) compare-work replaces O(bm·bn·d) dense MACs: a win
    whenever nnz_cap² ≪ d (the >99%-zero TF×IDF regime this kernel
    exists for).

    Both sides arrive slot-major — x as (_SLOT_CHUNK, bm) chunks walked
    by grid dim 2, z as (pz, bn) — so the loop over z slots indexes the
    sublane axis (the lane axis cannot be sliced dynamically) and each
    step's compare tile stays (bm, bn), inside scoped VMEM at any
    ``nnz_cap``."""
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    xi = jnp.transpose(xi_ref[...])               # (bm, chunk) int32
    xv = jnp.transpose(xv_ref[...])               # (bm, chunk) f32

    def match_step(q, acc):
        zq = zi_ref[pl.ds(q, 1), :]               # (1, bn)
        vq = zv_ref[pl.ds(q, 1), :]
        part = jnp.zeros(acc.shape, jnp.float32)
        for c in range(_SLOT_CHUNK):
            part = part + jnp.where(xi[:, c:c + 1] == zq,
                                    xv[:, c:c + 1], 0.0)
        return acc + part * vq

    o_ref[...] += jax.lax.fori_loop(
        0, z_slots, match_step, jnp.zeros(o_ref.shape, jnp.float32))

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _finalize():
        acc = o_ref[...]
        gamma = gamma_ref[0, 0]
        coef0 = coef0_ref[0, 0]
        if kind == "poly":
            o_ref[...] = (gamma * acc + coef0) ** degree
        elif kind == "rbf":
            sq = rownorm_ref[...].T + colnorm_ref[...] - 2.0 * acc
            o_ref[...] = jnp.exp(-gamma * jnp.maximum(sq, 0.0))


def _pad_sparse(sp, n_p: int, slots: int):
    """(rows, slots) padding of blocked-CSR rows, returned slot-major
    (slots, rows) with f32 values."""
    pad_r = n_p - sp.values.shape[0]
    pad_s = slots - sp.values.shape[1]
    idx = jnp.pad(sp.indices, ((0, pad_r), (0, pad_s)))
    val = jnp.pad(sp.values, ((0, pad_r), (0, pad_s))).astype(jnp.float32)
    return idx.T, val.T


@functools.partial(jax.jit, static_argnames=("kind", "degree", "bm", "bn",
                                             "interpret"))
def sparse_gram(X, Z, gamma=1.0, coef0=0.0, *, kind: str = "linear",
                degree: int = 3, bm: int = 128, bn: int = 128,
                interpret: bool = True) -> jax.Array:
    """K (n, m) = k(X, Z) over blocked-CSR rows (``SparseRows``).

    Both-sparse runs the Pallas index-match kernel tiled (n/bm, m/bn,
    nnz_cap/8): the z side's (index, value) slots are resident per
    tile, the x side's arrive 8 at a time; ``gamma``/``coef0`` ride in
    as traced (1, 1) scalar blocks exactly like the dense kernel, so
    SolverParams sweeps share one compiled kernel. Mixed dense×sparse
    (the serve-side decision path: dense query rows against the sparse
    SV buffer) routes through the XLA gather contraction of
    :mod:`repro.sparse` with the same fused transforms — there is no
    dense (·, d) tile a Pallas block could hold at 100k+ features.
    """
    from repro import sparse as sparse_rows

    if not (sparse_rows.is_sparse(X) and sparse_rows.is_sparse(Z)):
        dots = sparse_rows.cross_dots(X, Z).astype(jnp.float32)
        g = jnp.asarray(gamma, jnp.float32)
        c0 = jnp.asarray(coef0, jnp.float32)
        if kind == "poly":
            return (g * dots + c0) ** degree
        if kind == "rbf":
            xx = sparse_rows.row_sq_norms(X).astype(jnp.float32)[:, None]
            zz = sparse_rows.row_sq_norms(Z).astype(jnp.float32)[None, :]
            return jnp.exp(-g * jnp.maximum(xx + zz - 2.0 * dots, 0.0))
        return dots
    n, m = X.values.shape[0], Z.values.shape[0]
    bm_, bn_ = min(bm, _ceil(n)), min(bn, _ceil(m))
    n_p, m_p = _pad_to(n, bm_), _pad_to(m, bn_)
    px = _pad_to(X.values.shape[1], _SLOT_CHUNK)
    xi, xv = _pad_sparse(X, n_p, px)              # (px, n_p)
    zi, zv = _pad_sparse(Z, m_p, Z.values.shape[1])   # (pz, m_p)
    rown = jnp.sum(xv ** 2, axis=0, keepdims=True)    # (1, n_p)
    coln = jnp.sum(zv ** 2, axis=0, keepdims=True)    # (1, m_p)
    g = jnp.asarray(gamma, jnp.float32).reshape(1, 1)
    c0 = jnp.asarray(coef0, jnp.float32).reshape(1, 1)
    pz = zi.shape[0]
    k_steps = px // _SLOT_CHUNK

    scalar = pl.BlockSpec((1, 1), lambda i, j, k: (0, 0))
    out = pl.pallas_call(
        functools.partial(_sparse_gram_kernel, kind=kind, degree=degree,
                          z_slots=pz, k_steps=k_steps),
        grid=(n_p // bm_, m_p // bn_, k_steps),
        in_specs=[
            scalar,
            scalar,
            pl.BlockSpec((_SLOT_CHUNK, bm_), lambda i, j, k: (k, i)),
            pl.BlockSpec((_SLOT_CHUNK, bm_), lambda i, j, k: (k, i)),
            pl.BlockSpec((pz, bn_), lambda i, j, k: (0, j)),
            pl.BlockSpec((pz, bn_), lambda i, j, k: (0, j)),
            pl.BlockSpec((1, bm_), lambda i, j, k: (0, i)),
            pl.BlockSpec((1, bn_), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm_, bn_), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n_p, m_p), jnp.float32),
        interpret=interpret,
    )(g, c0, xi, xv, zi, zv, rown, coln)
    return out[:n, :m]
