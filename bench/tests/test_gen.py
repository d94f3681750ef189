"""Inputs are made from the seed alone, in either row format."""
import hashlib
import json
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from bench import gen
from bench.tests.tiny import CONFIGS

ROOT = Path(__file__).resolve().parents[2]
RCV1 = json.loads((ROOT / "bench/configs/rcv1-dense.json").read_text())


def densify(X, d):
    """Rows as a dense array in their own dtype: a CSR pair scattered
    as the program's ``sparse.to_dense`` does."""
    if not isinstance(X, tuple):
        return X
    cols, vals = X
    r = jnp.broadcast_to(jnp.arange(cols.shape[0])[:, None], cols.shape)
    return jnp.zeros((cols.shape[0], d), vals.dtype).at[r, cols].add(vals)


def _make(cfg, seed, tenant=1, batch=3, rows=16):
    X, y = gen.RowModel(cfg, seed).make(tenant, batch, rows)
    X = densify(X, int(cfg["num_features"]))
    return np.asarray(X, np.float32), np.asarray(y, np.float32)


def test_same_seed_same_rows():
    for cfg in CONFIGS.values():
        a, b = _make(cfg, 2**40 + 5), _make(cfg, 2**40 + 5)
        np.testing.assert_array_equal(a[1], b[1])
        np.testing.assert_array_equal(a[0], b[0])


def test_other_seed_other_rows():
    cfg = CONFIGS["tiny-dense"]
    a, b = _make(cfg, 3000000001), _make(cfg, 3000000002)
    assert not np.array_equal(a[0], b[0])
    c = _make(cfg, 3000000001, batch=4)
    assert not np.array_equal(a[0], c[0])


def test_rows_are_tfidf_like():
    for cfg in CONFIGS.values():
        dense, y = _make(cfg, 77, rows=64)
        assert ((dense != 0).sum(1) == cfg["row_nnz"][0]).all()
        assert (dense >= 0).all()
        np.testing.assert_allclose(np.linalg.norm(dense, axis=1), 1.0,
                                   atol=1e-2)
        assert set(np.unique(y)) <= {-1.0, 1.0}


def test_committed_rows_have_the_source_shape():
    """The committed configuration's rows: non-zeros a row in its range
    with the source's mean, and classes about even."""
    root = Path(__file__).resolve().parents[2]
    bench = json.loads((root / "BENCHMARK.json").read_text())
    for c in bench["configs"]:
        cfg = json.loads((root / c["file"]).read_text())
        X, y = _make(cfg, 2**33 + 9, rows=512)
        nnz = (X != 0).sum(1)
        lo, hi = cfg["row_nnz"]
        assert nnz.min() >= lo and nnz.max() <= hi
        assert abs(nnz.mean() - (lo + hi) / 2) < 0.05 * hi
        assert 0.35 < (y > 0).mean() < 0.65


# sha256 of the dense rows' bytes, then the labels', made by the row
# model of the tree before the CSR format came back (the same draws)
PARENT_DIGESTS = {
    ("tiny", 2**40 + 5, 1, 3):
        "e0cadab4f2bb9b443e30757dfae7d2230543ce5b6dd4313f5348af590cef5101",
    ("tiny", 2**40 + 5, 0, -1):
        "1f046736a7e572018f07bc9dc7c1ae5a15b97a139c52b5de043ade7c7e4db2b4",
    ("tiny", 3000000001, 1, 3):
        "43e1a50a1d7d99a2dd0c85791eb37f66e4313611e27889251470d8830dd2dd39",
    ("tiny", 3000000001, 0, -1):
        "d25bb651cb393ed7296e8bf1bfc18af075604d6b149116546db3bb0e7b257c52",
    ("rcv1", 2**40 + 5, 1, 3):
        "0537066584a6a92a2b837f1ab790e0502eb14650198c2e979529ea9f938f5419",
    ("rcv1", 2**40 + 5, 0, -1):
        "379cf1f60e161b2e7b5f9532514a58630a618db38e81905b99b457ada7246618",
    ("rcv1", 3000000001, 1, 3):
        "2365368401ab6b6364aadeb9dec99443ae17bc52728c8f42d3dfafc39d6d934f",
    ("rcv1", 3000000001, 0, -1):
        "8177f45d567b4aa4da65bd20a56cf4156a4af871739bb4a63f0045d6659be1ef",
}
SIZES = {"tiny": (CONFIGS["tiny-dense"], 16), "rcv1": (RCV1, 64)}


@pytest.mark.parametrize("key", list(PARENT_DIGESTS), ids=str)
def test_dense_rows_are_the_parents(key):
    """Dense rows and labels stay bit for bit what they were, at the
    tiny size and at the committed configuration's width."""
    name, seed, tenant, batch = key
    cfg, rows = SIZES[name]
    X, y = gen.RowModel(cfg, seed).make(tenant, batch, rows)
    assert X.dtype == jnp.bfloat16 and X.shape == (rows, cfg["num_features"])
    h = hashlib.sha256(np.asarray(X).tobytes())
    h.update(np.asarray(y).tobytes())
    assert h.hexdigest() == PARENT_DIGESTS[key]


@pytest.mark.parametrize("name,seed,tenant,batch",
                         [("tiny", 2**40 + 5, 1, 3), ("tiny", 3000000001, 0, -1),
                          ("rcv1", 2**40 + 5, 0, -1), ("rcv1", 3000000001, 1, 3)])
def test_csr_rows_are_the_dense_rows(name, seed, tenant, batch):
    """A CSR batch is the dense batch of the same ``(seed, tenant,
    batch)``: ``(rows, nnz_cap)`` int32 columns, distinct in a row's
    live slots, dead slots ``(0, 0.0)``; scattered, the dense rows bit
    for bit, and the same labels."""
    cfg, rows = SIZES[name]
    d, cap = int(cfg["num_features"]), gen.nnz_cap(cfg)
    Xd, yd = gen.RowModel(cfg, seed).make(tenant, batch, rows)
    (cols, vals), yc = gen.RowModel(dict(cfg, row_format="sparse_csr"),
                                    seed).make(tenant, batch, rows)
    assert cols.dtype == jnp.int32 and cols.shape == (rows, cap)
    assert vals.dtype == Xd.dtype and vals.shape == (rows, cap)
    c, v = np.asarray(cols), np.asarray(vals, np.float32)
    live = v != 0
    assert (c[~live] == 0).all() and ((c >= 0) & (c < d)).all()
    for r in range(rows):
        assert len(set(c[r][live[r]])) == live[r].sum()
    dense = densify((cols, vals), d)
    assert np.asarray(dense).tobytes() == np.asarray(Xd).tobytes()
    assert np.asarray(yc).tobytes() == np.asarray(yd).tobytes()
