"""The reference takes blocked-CSR rows as it takes dense ones: the same
answers on the same jobs, and no array with a row axis and the feature
axis together, so a job of news20.binary's width fits beside its
hypotheses alone."""
import math

import jax
from jax.extend.core import ClosedJaxpr, Jaxpr
import jax.numpy as jnp
import numpy as np
import pytest

from bench import compare, gen
from bench import reference as ref
from bench.chain import Chain, job_rows
from bench.tests.tiny import CONFIGS, TRAFFIC

DENSE, CSR = CONFIGS["tiny-dense"], CONFIGS["tiny-csr"]
SEED = 2**40 + 17


def _train_job(cfg):
    n = int(cfg["rows_per_device"])
    X, y = gen.RowModel(cfg, SEED).make(0, 1, n)
    return (ref.rows_from(X, jnp.float32), y.astype(jnp.float32),
            jnp.ones((n,), jnp.float32), n)


def _fold_job(cfg):
    """A second fold of tenant 1: one new batch, then the archive rows
    its last snapshot carried (some slots empty), then empty rows up to
    a longer job."""
    tr, cap = TRAFFIC["fold"], int(cfg["sv_capacity"])
    rng = np.random.default_rng(3)
    ids0 = rng.choice(tr["archive_rows"], cap, replace=False)
    ids0[rng.random(cap) < 0.3] = -1
    n_job = tr["batch_rows"] + cap + 16
    chain = Chain(tr["archive_rows"], tr["batch_rows"], cap,
                  {1: ([0], n_job)}, {0: ids0})
    return job_rows(gen.RowModel(cfg, SEED), 1, chain, 1, jnp.float32)


def _close(a, r, tol=1e-5):
    assert compare.rel_gap(a, r) <= tol, compare.rel_gap(a, r)


@pytest.mark.parametrize("job", [_train_job, _fold_job],
                         ids=["train", "fold"])
def test_csr_reference_is_the_dense_reference(job):
    """SV rows equal, and risks, hypotheses and the final solve within
    1e-5: only the order of float32 sums differs."""
    rows_d, y, mask, n = job(DENSE)
    rows_c, y_c, mask_c, n_c = job(CSR)
    assert isinstance(rows_c, ref.Csr) and not isinstance(rows_d, ref.Csr)
    assert n == n_c
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y_c))
    np.testing.assert_array_equal(np.asarray(mask), np.asarray(mask_c))
    fd = ref.fit(rows_d, y, mask, n, DENSE)
    fc = ref.fit(rows_c, y, mask, n, CSR)
    assert fd.rounds == fc.rounds
    assert (fd.best_round, fd.best_part) == (fc.best_round, fc.best_part)
    for t in range(fd.rounds):
        np.testing.assert_array_equal(fd.sv_ids[t], fc.sv_ids[t])
        _close(fc.risks[t], fd.risks[t])
        _close(fc.ws[t], fd.ws[t])
        _close(fc.bs[t], fd.bs[t])
    ids = fd.sv_ids[-1]
    assert (ids >= 0).sum() > 0
    wd, bd, _ = ref.final(rows_d, y, n, ids, DENSE)
    wc, bc, _ = ref.final(rows_c, y, n, ids, CSR)
    _close(np.append(wc, bc), np.append(wd, bd))


def _avals(jaxpr):
    """Every array the jaxpr and the jaxprs inside it name."""
    for v in list(jaxpr.invars) + list(jaxpr.constvars):
        yield v.aval
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            yield v.aval
        for p in eqn.params.values():
            for sub in (p if isinstance(p, (tuple, list)) else (p,)):
                if isinstance(sub, ClosedJaxpr):
                    yield from _avals(sub.jaxpr)
                elif isinstance(sub, Jaxpr):
                    yield from _avals(sub)


def test_csr_reference_holds_no_row_by_feature_array():
    """The programs of the CSR reference's fit (its round and its final
    solve) at news20.binary's width, 4,096 rows: every array with the
    feature axis holds at most the L hypotheses beside it."""
    d, n, L, cap, nnz = 1_355_191, 4096, 8, 2048, 512
    per = n // L
    rows = ref.Csr(jax.ShapeDtypeStruct((n + 1, nnz), jnp.int32),
                   jax.ShapeDtypeStruct((n + 1, nnz), jnp.float32))
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)
    ids = jax.ShapeDtypeStruct((cap,), jnp.int32)
    params = (f32(), f32(), f32())
    round_ = jax.make_jaxpr(lambda *a: ref.fit_round(
        *a, L=L, per=per, cap=cap, d=d, max_epochs=10, acc=jnp.float32))(
        rows, f32(L, per), f32(L, per), ids, f32(cap), params)
    final = jax.make_jaxpr(lambda *a: ref.fit_final(
        *a, d=d, max_epochs=10, acc=jnp.float32))(rows, f32(n), ids, params)
    seen = 0
    for jaxpr in (round_, final):
        for aval in _avals(jaxpr.jaxpr):
            shape = tuple(getattr(aval, "shape", ()))
            if d in shape:
                seen += 1
                rest = math.prod(shape) // d
                assert rest <= L, shape
    assert seen
