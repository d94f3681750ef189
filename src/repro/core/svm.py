"""Soft-margin binary SVM trained in the dual (paper eq. 1-2).

The paper's reducers each train a full binary soft-margin SVM on their
augmented partition. We implement the reducer's solver as dual
coordinate ascent (Hsieh et al. 2008 style, L1-loss), written entirely
in ``jax.lax`` control flow so it can be jit'ed, vmap'ed over
partitions (the functional MapReduce mode) and shard_map'ed over the
``data`` mesh axis (the distributed mode).

Two execution paths:

* **linear** (``fit_binary_linear``): maintains the primal vector
  ``w = Σ α_i y_i x_i`` directly — O(n·d) per epoch, no Gram matrix.
  This is the production path for TF×IDF text features.
* **kernel** (``fit_binary_kernel``): precomputes the Gram matrix
  (optionally via the Pallas kernel in :mod:`repro.kernels.gram`) and
  runs Gram-based dual CD — O(n²) per epoch.

The bias is handled LIBLINEAR-style by augmenting with a constant
feature (regularized bias): ``K ← K + 1`` / ``Q_ii ← Q_ii + 1`` and
``b = Σ α_i y_i``. Padded rows are masked: their updates are multiplied
by 0 so α stays exactly 0.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro import compat
from repro import sparse as sparse_rows
from repro.core.kernel_fns import KernelConfig, apply_kernel


def _pvary(tree, axes):
    """Mark a pytree as varying over shard_map manual axes (vma).

    No-op when ``axes`` is empty or outside shard_map. Needed because
    our while_loop carries start from constants, which JAX types as
    axis-invariant, while the loop body outputs are device-varying.
    """
    return compat.pvary(tree, axes)


class SolverParams(NamedTuple):
    """Traced (vmappable) solver hyper-parameters.

    The static/traced split (DESIGN.md §8): anything that changes the
    *program* — shapes, loop bounds, kernel family, execution path —
    stays in the frozen :class:`SVMConfig` shell; anything that only
    changes *values* lives here as a jnp scalar, so a batch of S
    configs is just a ``SolverParams`` with a leading (S,) axis fed
    through ``vmap`` (the sweep subsystem in :mod:`repro.core.sweep`).
    ``KernelConfig.degree`` stays static: a traced integer exponent
    would lower to a float ``pow`` whose negative-base branch NaNs.

    ``max_epochs`` is the traced *cutoff*: the dual-CD while_loop stops
    at ``min(cfg.max_epochs, params.max_epochs)``. The static shell
    keeps the program's loop bound; the traced value lets a sweep give
    each config its own epoch budget — and lets the sweep driver freeze
    a converged config at a cutoff of 0 (zero epochs) instead of
    spinning it to the shared bound. Kept float32 so the pytree stays
    leaf-uniform under ``stack_params``/``sweep_grid``.
    """
    C: jax.Array             # () box constraint (eq. 2)
    tol: jax.Array           # () max projected-gradient violation to stop
    sv_threshold: jax.Array  # () α above this counts as a support vector
    gamma: jax.Array         # () rbf / poly scale
    coef0: jax.Array         # () poly offset
    max_epochs: jax.Array    # () traced epoch cutoff ≤ the static bound


@dataclasses.dataclass(frozen=True)
class SVMConfig:
    """Reducer-level solver configuration (paper eq. 2 hyper-params).

    Static shell: fields here are compile-time constants. The float
    hyper-parameters double as *defaults* for :meth:`params`, which
    lifts them into a traced :class:`SolverParams` pytree.
    """
    C: float = 1.0
    max_epochs: int = 30
    tol: float = 1e-3            # max projected-gradient violation to stop
    kernel: KernelConfig = KernelConfig()
    sv_threshold: float = 1e-6   # α above this counts as a support vector
    use_gram: bool = False       # force the Gram path even for linear
    gram_impl: str = "xla"       # 'xla' | 'pallas' | 'pallas_sparse'
    row_format: str = "dense"    # 'dense' | 'sparse_csr' (blocked CSR/ELL)
    nnz_cap: int = 0             # slots per sparse row; required if sparse

    def __post_init__(self):
        if self.row_format not in ("dense", "sparse_csr"):
            raise ValueError(
                f"row_format must be 'dense' or 'sparse_csr', "
                f"got {self.row_format!r}")
        if self.gram_impl not in ("xla", "pallas", "pallas_sparse"):
            raise ValueError(
                f"gram_impl must be 'xla' | 'pallas' | 'pallas_sparse', "
                f"got {self.gram_impl!r}")
        if self.row_format == "sparse_csr" and self.nnz_cap < 1:
            raise ValueError(
                "row_format='sparse_csr' requires nnz_cap >= 1 (the "
                "static slot count of the blocked-CSR rows)")
        if self.gram_impl == "pallas_sparse" and self.row_format != \
                "sparse_csr":
            raise ValueError(
                "gram_impl='pallas_sparse' requires row_format="
                "'sparse_csr' (it consumes index/value blocks)")
        if self.gram_impl == "pallas" and self.row_format == "sparse_csr":
            raise ValueError(
                "the dense Pallas Gram kernel cannot consume sparse_csr "
                "rows; use gram_impl='pallas_sparse' or 'xla'")

    def params(self, dtype=jnp.float32) -> SolverParams:
        """Lift the value-like hyper-params into a traced pytree."""
        return SolverParams(
            C=jnp.asarray(self.C, dtype),
            tol=jnp.asarray(self.tol, dtype),
            sv_threshold=jnp.asarray(self.sv_threshold, dtype),
            gamma=jnp.asarray(self.kernel.gamma, dtype),
            coef0=jnp.asarray(self.kernel.coef0, dtype),
            max_epochs=jnp.asarray(float(self.max_epochs), dtype),
        )


class BinarySVM(NamedTuple):
    """Trained reducer output: dual coefs + primal view when linear."""
    alpha: jax.Array          # (n,) dual variables in [0, C]
    b: jax.Array              # () bias (regularized-bias convention)
    w: jax.Array              # (d,) primal weights; zeros on the kernel path
    epochs_run: jax.Array     # () actual epochs before tol hit
    max_violation: jax.Array  # () final max projected-gradient violation


def support_mask(alpha: jax.Array, threshold: float = 1e-6) -> jax.Array:
    """Boolean mask of support vectors (α > 0 up to threshold)."""
    return alpha > threshold


# ---------------------------------------------------------------------------
# Linear path: dual CD maintaining w directly.
# ---------------------------------------------------------------------------

def fit_binary_linear(X: jax.Array, y: jax.Array,
                      mask: Optional[jax.Array],
                      cfg: SVMConfig,
                      params: Optional[SolverParams] = None,
                      vma_axes: tuple = (),
                      tail=None) -> BinarySVM:
    """``tail`` optionally appends a second row block (the reducer's
    SV_global copy): rows ``[0, len(X))`` come from ``X`` and the rest
    from ``tail``. Each epoch walks the two blocks in place, in the same
    order as over their concatenation, so the union is never copied."""
    blocks = (X,) if tail is None else (X, tail)
    d = X.shape[1]
    sizes = [int(B.shape[0]) for B in blocks]
    n = sum(sizes)
    is_sp = sparse_rows.is_sparse(X)
    p = cfg.params() if params is None else params
    # Feature rows may be bf16 (halves the dominant HBM stream, §Perf
    # iteration 5); the solver state (w, α, b) stays f32.
    ct = jnp.promote_types(X.dtype, jnp.float32)
    y = y.astype(ct)
    m = jnp.ones((n,), ct) if mask is None else mask.astype(ct)

    # Q_ii = ||x_i||^2 + 1 (bias augmentation). Masked rows get 1 to avoid
    # 0-div. einsum keeps bf16 X un-materialized (no f32 copy of X).
    if is_sp:
        qdiag = jnp.concatenate([sparse_rows.row_sq_norms(B).astype(ct)
                                 for B in blocks]) + 1.0
    else:
        qdiag = jnp.concatenate([
            jnp.einsum("nd,nd->n", B, B, preferred_element_type=ct)
            for B in blocks]) + 1.0
    qdiag = jnp.where(m > 0, qdiag, 1.0)
    C = p.C.astype(ct)
    tol = p.tol.astype(ct)
    # Static bound × traced cutoff (DESIGN.md §8): the program's loop
    # bound stays cfg.max_epochs; a per-config traced budget can only
    # tighten it.
    ecap = jnp.minimum(jnp.asarray(cfg.max_epochs, ct), p.max_epochs.astype(ct))

    def block_body(B, off):
        def body_i(r, carry):
            alpha, w, b, viol = carry
            i = r + off                                # union row index
            if is_sp:
                # sparse row: gather w at its column ids, scatter-add the
                # update back — O(nnz) per inner step instead of O(d)
                ii = jax.lax.dynamic_index_in_dim(B.indices, r,
                                                  keepdims=False)
                vv = jax.lax.dynamic_index_in_dim(
                    B.values, r, keepdims=False).astype(ct)
                wx = jnp.dot(jnp.take(w, ii), vv)
            else:
                xi = jax.lax.dynamic_index_in_dim(
                    B, r, keepdims=False).astype(ct)
                wx = jnp.dot(w, xi)
            yi = y[i]
            g = yi * (wx + b) - 1.0                    # ∂/∂α_i of dual obj
            a_old = alpha[i]
            # projected gradient for the box [0, C]
            pg = jnp.where(a_old <= 0.0, jnp.minimum(g, 0.0),
                           jnp.where(a_old >= C, jnp.maximum(g, 0.0), g))
            a_new = jnp.clip(a_old - g / qdiag[i], 0.0, C)
            delta = (a_new - a_old) * m[i]
            alpha = alpha.at[i].set(a_old + delta)
            if is_sp:
                w = w.at[ii].add(delta * yi * vv)
            else:
                w = w + delta * yi * xi
            b = b + delta * yi
            viol = jnp.maximum(viol, jnp.abs(pg) * m[i])
            return alpha, w, b, viol
        return body_i

    offsets = [sum(sizes[:j]) for j in range(len(blocks))]
    zero = _pvary(jnp.asarray(0.0, ct), vma_axes)

    def epoch(carry):
        alpha, w, b, _, t = carry
        state = (alpha, w, b, zero)
        for B, off, size in zip(blocks, offsets, sizes):
            state = jax.lax.fori_loop(0, size, block_body(B, off), state)
        alpha, w, b, viol = state
        return alpha, w, b, viol, t + 1

    def cond(carry):
        _, _, _, viol, t = carry
        return jnp.logical_and(t < ecap,
                               jnp.logical_or(t == 0, viol > tol))

    init = _pvary((jnp.zeros((n,), ct), jnp.zeros((d,), ct),
                   jnp.asarray(0.0, ct), jnp.asarray(jnp.inf, ct),
                   jnp.asarray(0, jnp.int32)), vma_axes)
    alpha, w, b, viol, t = jax.lax.while_loop(cond, epoch, init)
    return BinarySVM(alpha=alpha, b=b, w=w, epochs_run=t, max_violation=viol)


# ---------------------------------------------------------------------------
# Kernel path: Gram-based dual CD.
# ---------------------------------------------------------------------------

GramFn = Callable[[jax.Array, jax.Array], jax.Array]


def _pallas_gram_fn(cfg: SVMConfig, p: SolverParams) -> GramFn:
    """Route the reducer's Gram build through the Pallas TPU kernel
    (:mod:`repro.kernels.gram`). ``gamma``/``coef0`` are *traced* scalar
    operands of the kernel (SMEM-style scalar inputs), so rbf/poly
    sweeps over :class:`SolverParams` run on the Pallas path — and every
    config shares ONE compiled kernel instead of re-specializing per
    value. Only the operator choice (``kernel.name``/``degree``) stays
    baked in at trace time. The :mod:`repro.kernels.ops` wrappers pick
    interpret mode from the backend, so a TPU never runs it
    interpreted."""
    from repro.kernels import ops
    kc = cfg.kernel
    build = (ops.sparse_gram_matrix if cfg.gram_impl == "pallas_sparse"
             else ops.gram_matrix)

    def fn(X, Z):
        K = build(X, Z, gamma=p.gamma, coef0=p.coef0, kind=kc.name,
                  degree=kc.degree)
        return K.astype(X.dtype)
    return fn


def fit_binary_kernel(X: jax.Array, y: jax.Array,
                      mask: Optional[jax.Array],
                      cfg: SVMConfig,
                      gram_fn: Optional[GramFn] = None,
                      params: Optional[SolverParams] = None,
                      vma_axes: tuple = ()) -> BinarySVM:
    n, d = X.shape
    p = cfg.params() if params is None else params
    y = y.astype(X.dtype)
    m = jnp.ones((n,), X.dtype) if mask is None else mask.astype(X.dtype)

    if gram_fn is None and cfg.gram_impl in ("pallas", "pallas_sparse"):
        gram_fn = _pallas_gram_fn(cfg, p)
    if gram_fn is None:
        K = apply_kernel(X, X, cfg=cfg.kernel, gamma=p.gamma, coef0=p.coef0)
    else:
        K = gram_fn(X, X)
    K = K + 1.0                                   # regularized bias augment
    Q = (y[:, None] * y[None, :]) * K
    # Mask padded rows/cols out of Q so their updates are inert.
    Q = Q * (m[:, None] * m[None, :])
    qdiag = jnp.where(m > 0, jnp.diagonal(Q), 1.0)
    C = p.C.astype(X.dtype)
    tol = p.tol.astype(X.dtype)
    ecap = jnp.minimum(jnp.asarray(cfg.max_epochs, jnp.float32),
                       p.max_epochs.astype(jnp.float32))

    def body_i(i, carry):
        alpha, g, viol = carry
        gi = g[i]
        a_old = alpha[i]
        pg = jnp.where(a_old <= 0.0, jnp.minimum(gi, 0.0),
                       jnp.where(a_old >= C, jnp.maximum(gi, 0.0), gi))
        a_new = jnp.clip(a_old - gi / qdiag[i], 0.0, C)
        delta = (a_new - a_old) * m[i]
        alpha = alpha.at[i].set(a_old + delta)
        g = g + delta * Q[:, i]                   # rank-1 gradient refresh
        viol = jnp.maximum(viol, jnp.abs(pg) * m[i])
        return alpha, g, viol

    zero = _pvary(jnp.asarray(0.0, X.dtype), vma_axes)

    def epoch(carry):
        alpha, g, _, t = carry
        alpha, g, viol = jax.lax.fori_loop(
            0, n, body_i, (alpha, g, zero))
        return alpha, g, viol, t + 1

    def cond(carry):
        _, _, viol, t = carry
        return jnp.logical_and(t < ecap,
                               jnp.logical_or(t == 0, viol > tol))

    init = _pvary((jnp.zeros((n,), X.dtype), -jnp.ones((n,), X.dtype) * m,
                   jnp.asarray(jnp.inf, X.dtype), jnp.asarray(0, jnp.int32)),
                  vma_axes)
    alpha, g, viol, t = jax.lax.while_loop(cond, epoch, init)

    coef = alpha * y * m
    w = (sparse_rows.weighted_row_sum(X, coef).astype(X.dtype)
         if cfg.kernel.name == "linear" else jnp.zeros((d,), X.dtype))
    b = jnp.sum(coef)                             # bias-augment convention
    return BinarySVM(alpha=alpha, b=b, w=w, epochs_run=t, max_violation=viol)


def fit_binary(X: jax.Array, y: jax.Array, mask: Optional[jax.Array] = None,
               cfg: SVMConfig = SVMConfig(),
               gram_fn: Optional[GramFn] = None,
               params: Optional[SolverParams] = None,
               vma_axes: tuple = (), tail=None) -> BinarySVM:
    """Train one reducer's soft-margin binary SVM. y ∈ {-1, +1}.

    ``params`` overrides the value-like hyper-params of ``cfg`` with a
    traced :class:`SolverParams` pytree (vmappable for sweeps); when
    ``None`` the static defaults of ``cfg`` are lifted. ``tail`` rows
    follow ``X`` (the training set is their union; ``y``/``mask`` cover
    both): the linear path reads them in place, the Gram path needs
    the union as one matrix and concatenates.
    """
    # Every dual-CD path runs under this scope: the device ops of the
    # local and final solves carry it in their ``op_name`` metadata
    # (DESIGN.md §17).
    with jax.named_scope("svm.solve"):
        if cfg.kernel.name == "linear" and not cfg.use_gram:
            return fit_binary_linear(X, y, mask, cfg, params=params,
                                     vma_axes=vma_axes, tail=tail)
        if tail is not None:
            X = sparse_rows.rows_concat(X, tail, axis=0)
        return fit_binary_kernel(X, y, mask, cfg, gram_fn=gram_fn,
                                 params=params, vma_axes=vma_axes)


# ---------------------------------------------------------------------------
# Decision functions.
# ---------------------------------------------------------------------------

def decision_linear(w: jax.Array, b: jax.Array, X: jax.Array) -> jax.Array:
    return X @ w + b


def decision_kernel(sv_x: jax.Array, sv_coef: jax.Array, b: jax.Array,
                    X: jax.Array, kcfg: KernelConfig,
                    gamma: Optional[jax.Array] = None,
                    coef0: Optional[jax.Array] = None) -> jax.Array:
    """f(x) = Σ_i coef_i K(x, sv_i) + b, coef = α·y (masked).

    ``gamma``/``coef0`` override the static kernel params with traced
    values (must match the values the model was trained with).
    """
    K = apply_kernel(X, sv_x, cfg=kcfg, gamma=gamma, coef0=coef0)
    return K @ sv_coef + b


def predict_sign(scores: jax.Array) -> jax.Array:
    """±1 labels; ties (score==0) resolve to +1 like the paper's tables."""
    return jnp.where(scores >= 0.0, 1.0, -1.0)
