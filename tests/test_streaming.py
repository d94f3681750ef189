"""Streaming polarization service (ISSUE 3): wave folding, snapshot
atomicity, multi-tenant batched updates, and the incremental-update
correctness bugfixes."""
import subprocess
import sys
import threading
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (MRSVMConfig, SVMConfig, decision_values,
                        fit_mapreduce, fit_mapreduce_sweep, predict,
                        stack_params, sweep_grid, update_mapreduce)
from repro.core.risk import empirical_risk, zero_one_loss
from repro.serving import StreamingSVMService

REPO = Path(__file__).resolve().parents[1]


def _sep_data(seed, n, d=16, w_key=9):
    w = jax.random.normal(jax.random.PRNGKey(w_key), (d,))
    X = jax.random.normal(jax.random.PRNGKey(seed), (n, d))
    return X, jnp.sign(X @ w)


@pytest.fixture(scope="module")
def stream_cfg():
    return MRSVMConfig(sv_capacity=64, gamma=1e-4, max_rounds=3,
                       svm=SVMConfig(C=1.0, max_epochs=15))


# ---------------------------------------------------------------------------
# (a) sequential folds ≡ one-shot union update
# ---------------------------------------------------------------------------

def test_sequential_folds_match_union_update(stream_cfg):
    """Folding k micro-batches one wave at a time must land on the same
    decision function (tolerance-level: the intermediate SV truncations
    perturb, not redirect) and the same bounded SV capacity as one
    update_mapreduce on the union."""
    cfg = stream_cfg
    X0, y0 = _sep_data(0, 256)
    m0 = fit_mapreduce(X0, y0, 4, cfg)
    batches = [_sep_data(i + 1, 96) for i in range(3)]

    m_seq = m0
    for Xb, yb in batches:
        m_seq = update_mapreduce(m_seq, Xb, yb, 4, cfg)
    Xu = jnp.concatenate([b[0] for b in batches])
    yu = jnp.concatenate([b[1] for b in batches])
    m_one = update_mapreduce(m0, Xu, yu, 4, cfg)

    assert m_seq.sv.x.shape == m_one.sv.x.shape == (cfg.sv_capacity, 16)
    Xt, yt = _sep_data(50, 400)
    dv_seq = np.asarray(decision_values(m_seq, Xt, cfg))
    dv_one = np.asarray(decision_values(m_one, Xt, cfg))
    assert np.corrcoef(dv_seq, dv_one)[0, 1] > 0.97
    assert (np.sign(dv_seq) == np.sign(dv_one)).mean() > 0.93
    acc_seq = float(jnp.mean(predict(m_seq, Xt, cfg) == yt))
    acc_one = float(jnp.mean(predict(m_one, Xt, cfg) == yt))
    assert acc_seq > 0.9 and abs(acc_seq - acc_one) < 0.05


# ---------------------------------------------------------------------------
# (b) drift scenario: stale < folded
# ---------------------------------------------------------------------------

def test_drift_fold_beats_stale_model(stream_cfg):
    cfg = stream_cfg
    X1, y1 = _sep_data(1, 320, w_key=7)
    svc = StreamingSVMService(cfg, num_partitions=4)
    svc.register("tenant", fit_mapreduce(X1, y1, 4, cfg))

    # drifted separator: the old one plus a sizeable rotation (content
    # drifts month-over-month; it doesn't reset)
    w_old = jax.random.normal(jax.random.PRNGKey(7), (16,))
    w_new = w_old + 0.8 * jax.random.normal(jax.random.PRNGKey(8), (16,))
    X2 = jax.random.normal(jax.random.PRNGKey(2), (320, 16))
    y2 = jnp.sign(X2 @ w_new)
    stale = float(jnp.mean(svc.predict("tenant", X2) == y2))
    svc.submit("tenant", X2[:160], y2[:160])
    svc.submit("tenant", X2[160:], y2[160:])
    st = svc.run_wave()
    assert st is not None and st.batches == 2 and st.rows == 320
    folded = float(jnp.mean(svc.predict("tenant", X2) == y2))
    assert folded > 0.8                  # accuracy floor on the new month
    assert folded > stale + 0.05         # folding genuinely adapted
    assert svc.snapshot("tenant").version == 1


# ---------------------------------------------------------------------------
# multi-tenant wave: S streams = S jobs on the sweep's config axis
# ---------------------------------------------------------------------------

def test_batched_wave_matches_per_stream_updates(stream_cfg):
    """A 2-stream wave folds through ONE fit_mapreduce_sweep pass and
    must match each stream's sequential update_mapreduce."""
    cfg = stream_cfg
    svc = StreamingSVMService(cfg, num_partitions=4,
                              max_batches_per_wave=2)
    models = {}
    for s, wk in (("a", 3), ("b", 4)):
        X0, y0 = _sep_data(10 + ord(s), 192, w_key=wk)
        models[s] = fit_mapreduce(X0, y0, 4, cfg)
        svc.register(s, models[s])

    new = {s: _sep_data(20 + ord(s), 128, w_key=wk)
           for s, wk in (("a", 3), ("b", 4))}
    for s, (Xn, yn) in new.items():
        svc.submit(s, Xn, yn)
    st = svc.run_wave()
    assert st.batched and st.streams == 2

    Xt, _ = _sep_data(60, 256)
    for s, (Xn, yn) in new.items():
        ref = update_mapreduce(models[s], Xn, yn, 4, cfg)
        np.testing.assert_allclose(
            np.asarray(svc.decision_values(s, Xt)),
            np.asarray(decision_values(ref, Xt, cfg)),
            rtol=1e-4, atol=1e-4)


def test_throughput_counts_the_time_between_waves(stream_cfg):
    """``rows_per_s`` is rows over first submission → last completion,
    not over the summed fold walls (``wall_s``), which leave out the
    time between waves."""
    import time
    cfg = stream_cfg
    svc = StreamingSVMService(cfg, num_partitions=4)
    X0, y0 = _sep_data(5, 128)
    svc.register("a", fit_mapreduce(X0, y0, 4, cfg))
    for k in range(2):
        svc.submit("a", *_sep_data(30 + k, 64))
        svc.run_wave()
        time.sleep(0.3)
    rep = svc.throughput_report()
    span = (max(mb.completed_s for mb in svc.done)
            - min(mb.submitted_s for mb in svc.done))
    assert rep["rows"] == 128 and rep["waves"] == 2
    assert rep["wall_s"] == round(sum(st.wall_s for st in svc.stats), 3)
    assert rep["rows_per_s"] == round(128 / span, 1)
    assert span >= sum(st.wall_s for st in svc.stats) + 0.3


def test_sweep_per_job_data_matches_sequential(stream_cfg):
    """The substrate itself: fit_mapreduce_sweep with per-job (X, y,
    mask) must equal per-job fit_mapreduce runs."""
    cfg = stream_cfg
    S, n, d = 3, 128, 12
    Xs, ys, ms = [], [], []
    for s in range(S):
        X, y = _sep_data(30 + s, n, d=d, w_key=s)
        Xs.append(X)
        ys.append(y)
        ms.append(jnp.where(jnp.arange(n) < n - 8 * s, 1.0, 0.0))
    Xb, yb, mb = jnp.stack(Xs), jnp.stack(ys), jnp.stack(ms)
    params = stack_params([cfg.svm.params()] * S)
    res = fit_mapreduce_sweep(Xb, yb, 4, cfg, params, mask=mb)
    for s in range(S):
        ref = fit_mapreduce(Xs[s], ys[s], 4, cfg, mask=ms[s])
        np.testing.assert_allclose(np.asarray(res.risks[s]),
                                   np.asarray(ref.risk),
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(res.final.alpha[s]),
                                   np.asarray(ref.final.alpha),
                                   rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# (c) snapshot swap atomicity under interleaved predicts
# ---------------------------------------------------------------------------

def test_snapshot_swap_atomic_under_interleaved_predicts(stream_cfg):
    """Readers racing the async folder must always see predictions
    consistent with EXACTLY one published snapshot version — never a
    half-updated model."""
    cfg = stream_cfg
    X0, y0 = _sep_data(5, 192)
    svc = StreamingSVMService(cfg, num_partitions=4, max_batches_per_wave=1,
                              keep_history=True)
    svc.register("t", fit_mapreduce(X0, y0, 4, cfg))
    Xq, _ = _sep_data(77, 64)

    seen = []
    errors = []
    stop = threading.Event()

    def reader():
        try:
            while not stop.is_set():
                pred, ver = svc.predict("t", Xq, with_version=True)
                seen.append((ver, np.asarray(pred)))
        except Exception as e:                    # pragma: no cover
            errors.append(e)

    threads = [threading.Thread(target=reader) for _ in range(2)]
    for th in threads:
        th.start()
    svc.start(idle_poll_s=0.005)
    for i in range(3):
        Xb, yb = _sep_data(100 + i, 96)
        svc.submit("t", Xb, yb)
    assert svc.wait_idle(timeout_s=120)
    stop.set()
    svc.stop()
    for th in threads:
        th.join(timeout=30)

    assert not errors
    assert svc.snapshot("t").version == 3
    history = svc.history("t")
    expected = {v: np.asarray(predict(snap.model, Xq, cfg,
                                      params=snap.params))
                for v, snap in history.items()}
    assert len(seen) > 0
    for ver, pred in seen:
        assert ver in expected
        np.testing.assert_array_equal(pred, expected[ver])


# ---------------------------------------------------------------------------
# (d) bugfix regressions
# ---------------------------------------------------------------------------

def test_update_mapreduce_threads_solver_params(stream_cfg):
    """Regression: update_mapreduce used to drop SolverParams — a
    sweep-trained model (traced C) was re-fit with config defaults.
    With params threaded, the update is exactly a fit_mapreduce on
    (new ∪ SVs) at the SAME hyper-params."""
    cfg = stream_cfg
    X0, y0 = _sep_data(6, 256)
    p = cfg.svm.params()._replace(C=jnp.asarray(0.05, jnp.float32))
    m0 = fit_mapreduce(X0, y0, 4, cfg, params=p)
    Xn, yn = _sep_data(7, 128)

    upd = update_mapreduce(m0, Xn, yn, 4, cfg, params=p)
    Xref = jnp.concatenate([Xn, m0.sv.x])
    yref = jnp.concatenate([yn, m0.sv.y])
    mref = jnp.concatenate([jnp.ones((128,)), m0.sv.mask])
    ref = fit_mapreduce(Xref, yref, 4, cfg, mask=mref, params=p)
    np.testing.assert_allclose(np.asarray(upd.final.alpha),
                               np.asarray(ref.final.alpha),
                               rtol=1e-5, atol=1e-6)
    # and the C actually bit: defaults give a different solution
    no_p = fit_mapreduce(Xref, yref, 4, cfg, mask=mref)
    assert not np.allclose(np.asarray(upd.final.alpha),
                           np.asarray(no_p.final.alpha))


def test_sweep_trained_model_roundtrips_without_kernel_drift(stream_cfg):
    """Acceptance: an rbf model selected by a gamma sweep keeps its
    kernel scale through update_mapreduce (the old code re-fit carried
    SVs at the default gamma)."""
    from repro.core import KernelConfig
    rng = np.random.default_rng(0)
    X = jnp.asarray(rng.normal(0, 1, (256, 2)).astype(np.float32))
    y = jnp.sign(X[:, 0] * X[:, 1])              # XOR: needs the rbf scale
    cfg = MRSVMConfig(sv_capacity=64, max_rounds=3,
                      svm=SVMConfig(C=10.0, max_epochs=20,
                                    kernel=KernelConfig("rbf", gamma=0.05)))
    params = sweep_grid(cfg.svm, gamma=[0.05, 1.0])
    res = fit_mapreduce_sweep(X, y, 4, cfg, params)
    best = res.best
    assert float(params.gamma[best]) == pytest.approx(1.0)  # sweep picked γ≠default
    p_best = jax.tree_util.tree_map(lambda a: a[best], params)
    m = fit_mapreduce(X, y, 4, cfg, params=p_best)

    Xn = jnp.asarray(rng.normal(0, 1, (128, 2)).astype(np.float32))
    yn = jnp.sign(Xn[:, 0] * Xn[:, 1])
    upd = update_mapreduce(m, Xn, yn, 4, cfg, params=p_best)
    acc = float(jnp.mean(predict(upd, Xn, cfg, params=p_best) == yn))
    assert acc > 0.85                            # γ=0.05 refit can't do this


def test_update_mapreduce_rejects_feature_dim_mismatch(stream_cfg):
    cfg = stream_cfg
    X0, y0 = _sep_data(8, 128)
    m = fit_mapreduce(X0, y0, 4, cfg)
    Xbad = jnp.ones((32, 8))
    with pytest.raises(ValueError, match="featurizer"):
        update_mapreduce(m, Xbad, jnp.ones((32,)), 4, cfg)


def test_scheduler_death_surfaces_instead_of_hanging():
    """A fold error must not kill the background thread silently: the
    service records it, wait_idle raises, stop re-raises."""
    # sv_capacity=36 does not divide 8 partitions → the first wave's
    # mapreduce_round raises inside the scheduler thread.
    bad_cfg = MRSVMConfig(sv_capacity=36, max_rounds=2,
                          svm=SVMConfig(C=1.0, max_epochs=5))
    X0, y0 = _sep_data(9, 128)
    svc = StreamingSVMService(bad_cfg, num_partitions=8)
    svc.register("t", fit_mapreduce(X0, y0, 4, bad_cfg))   # 4 divides 36
    svc.start(idle_poll_s=0.005)
    svc.submit("t", X0, y0)
    with pytest.raises(RuntimeError, match="scheduler died"):
        svc.wait_idle(timeout_s=60)
    assert isinstance(svc.scheduler_error, ValueError)
    with pytest.raises(RuntimeError, match="scheduler died"):
        svc.stop()


def test_service_submit_rejects_feature_dim_mismatch(stream_cfg):
    cfg = stream_cfg
    X0, y0 = _sep_data(8, 128)
    svc = StreamingSVMService(cfg, num_partitions=4)
    svc.register("t", fit_mapreduce(X0, y0, 4, cfg))
    with pytest.raises(ValueError, match="featurizer"):
        svc.submit("t", jnp.ones((16, 9)), jnp.ones((16,)))


def test_zero_one_loss_boundary_matches_predict():
    """Regression: sign(0) counted a boundary score as an error against
    BOTH classes; predict maps 0 → +1, and the loss must agree."""
    scores = jnp.asarray([0.0, 0.0, 2.0, -2.0])
    y = jnp.asarray([1.0, -1.0, 1.0, 1.0])
    loss = np.asarray(zero_one_loss(scores, y))
    np.testing.assert_array_equal(loss, [0.0, 1.0, 0.0, 1.0])
    # eq. 6 risk under 'zero_one' == served error rate of predict_sign
    pred = jnp.where(scores >= 0, 1.0, -1.0)
    served_err = float(jnp.mean((pred != y).astype(jnp.float32)))
    assert float(empirical_risk(scores, y, loss="zero_one")) == \
        pytest.approx(served_err)


def test_scheduler_per_slot_latency(served_model_latency):
    """Regression: every request in a wave used to be stamped with the
    whole-wave wall time; a slot finishing at its own EOS step must
    report a smaller latency than the wave's longest request."""
    model, params = served_model_latency
    from repro.serving import BatchScheduler, Request
    sched = BatchScheduler(model, params, batch_size=2, cache_len=96)
    sched.submit(Request(uid=0, prompt=[1, 2], max_new_tokens=2))
    sched.submit(Request(uid=1, prompt=[3, 4], max_new_tokens=24))
    done = {r.uid: r for r in sched.run()}
    wave = sched.stats[0]
    assert done[0].latency_s < done[1].latency_s
    assert done[1].latency_s <= wave.wall_s + 1e-6
    assert sched.throughput_report()["mean_latency_s"] > 0


@pytest.fixture(scope="module")
def served_model_latency():
    from repro.configs import get_config
    from repro.models.config import smoke_variant
    from repro.models.transformer import build_model
    cfg = smoke_variant(get_config("tinyllama-1.1b"))
    model = build_model(cfg)
    return model, model.init(jax.random.PRNGKey(0))


# ---------------------------------------------------------------------------
# sharded per-stream-data path (the serve-wave device program)
# ---------------------------------------------------------------------------

_SHARDED_STREAM_SCRIPT = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro import compat
from repro.core import (MRSVMConfig, SVMConfig, stack_params,
                        build_sharded_sweep_round, run_sharded_sweep,
                        fit_mapreduce_sweep)

S, n, d = 3, 256, 12
cfg = MRSVMConfig(sv_capacity=64, gamma=1e-4, max_rounds=3,
                  svm=SVMConfig(C=1.0, max_epochs=15))
Xs, ys, ms = [], [], []
for s in range(S):
    X = jax.random.normal(jax.random.PRNGKey(s), (n, d))
    w = jax.random.normal(jax.random.PRNGKey(100 + s), (d,))
    Xs.append(X); ys.append(jnp.sign(X @ w))
    ms.append(jnp.where(jnp.arange(n) < n - 16 * s, 1.0, 0.0))
Xb, yb, mb = jnp.stack(Xs), jnp.stack(ys), jnp.stack(ms)
params = stack_params([cfg.svm.params()] * S)

mesh = compat.make_mesh((8,), ("data",))
fn = build_sharded_sweep_round(mesh, ("data",), cfg, n // 8,
                               per_config_data=True)
sh = run_sharded_sweep(fn, Xb, yb, mb, cfg, params)

fres = fit_mapreduce_sweep(Xb, yb, 8, cfg, params, mask=mb)
np.testing.assert_allclose(np.asarray(sh.risks), np.asarray(fres.risks),
                           rtol=1e-4, atol=1e-5)
np.testing.assert_allclose(np.asarray(sh.ws), np.asarray(fres.ws),
                           rtol=1e-4, atol=1e-5)
np.testing.assert_array_equal(np.asarray(sh.sv.ids), np.asarray(fres.sv.ids))
print("SHARDED_STREAM_OK")
"""


def test_sharded_per_stream_round_matches_functional():
    """per_config_data=True (each stream its own rows/labels/mask,
    sharded over 8 devices) must equal the functional per-job sweep —
    the device program behind launch.steps.build_svm_serve_step."""
    from conftest import subprocess_env
    r = subprocess.run([sys.executable, "-c", _SHARDED_STREAM_SCRIPT],
                       capture_output=True, text=True, timeout=600,
                       env=subprocess_env(PYTHONPATH=str(REPO / "src")))
    assert "SHARDED_STREAM_OK" in r.stdout, r.stdout + r.stderr


@pytest.mark.slow
def test_launcher_serve_mode():
    """`repro.launch.serve --arch svm-tfidf` drives the streaming
    service end to end: stale vs folded accuracy per wave."""
    from conftest import subprocess_env
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--arch", "svm-tfidf",
         "--smoke", "--streams", "2", "--waves", "2"],
        capture_output=True, text=True, timeout=600, cwd=str(REPO),
        env=subprocess_env(PYTHONPATH=str(REPO / "src")))
    assert r.stdout.count("folded acc=") == 2, r.stdout + r.stderr
    assert "'batches': 4" in r.stdout
