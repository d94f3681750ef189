"""Inputs are made from the seed alone."""
import numpy as np

import json
from pathlib import Path

from bench import gen
from bench.tests.tiny import CONFIGS


def _make(cfg, seed, tenant=1, batch=3, rows=16):
    X, y = gen.RowModel(cfg, seed).make(tenant, batch, rows)
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
