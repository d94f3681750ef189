"""Public jit'd wrappers for the Pallas kernels.

The one place that picks the kernels' ``interpret`` mode: compiled
(``interpret=False``) when JAX's default backend is a TPU, interpreted
in Python everywhere else (the CPU test runs). Callers on the solver
path (``core/svm.py``) go through these wrappers, so a kernel never
runs interpreted on a TPU.
"""
from __future__ import annotations

import jax

from repro.kernels.gram import gram, sparse_gram
from repro.kernels.hinge_score import hinge_scores
from repro.kernels.decode_attention import flash_decode
from repro.kernels.svm_step import cd_epoch


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def gram_matrix(X, Z, kind="linear", **kw):
    """Tiled Gram matrix; drop-in ``gram_fn`` for core.svm.fit_binary."""
    kw.setdefault("interpret", not on_tpu())
    return gram(X, Z, kind=kind, **kw)


def sparse_gram_matrix(X, Z, kind="linear", **kw):
    """Blocked-CSR Gram matrix (gram_impl="pallas_sparse")."""
    kw.setdefault("interpret", not on_tpu())
    return sparse_gram(X, Z, kind=kind, **kw)


def risk_eval(X, W, b, y, mask, **kw):
    """Fused hinge risk of L hypotheses; → (losses (L,), count ())."""
    kw.setdefault("interpret", not on_tpu())
    return hinge_scores(X, W, b, y, mask, **kw)


def decode_attention(q, k, v, valid_len, **kw):
    """Flash-decode attention for the serving path."""
    kw.setdefault("interpret", not on_tpu())
    return flash_decode(q, k, v, valid_len, **kw)


def svm_cd_epoch(X, y, alpha, w, b, mask, C=1.0, **kw):
    """VMEM-resident dual-CD epoch (the paper's reducer hot loop)."""
    kw.setdefault("interpret", not on_tpu())
    return cd_epoch(X, y, alpha, w, b, mask, C=C, **kw)
