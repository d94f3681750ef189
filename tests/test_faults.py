"""Fault-injection harness + degraded-mode hardening (ISSUE 9).

Unit/regression legs of the survived-vs-detected contract: plan
determinism, seam firing semantics, retry/backoff typing, the
collective watchdog, generation fallback in both the flat checkpointer
and the streaming service, quarantine at submit(), and the doomed-wait
detectors. The end-to-end sweep lives in ``make test-chaos``
(repro.faults.chaos + the 2-process leg in test_multihost.py).
"""
import json
import os
import tempfile
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import faults, sparse
from repro.ckpt import checkpoint as ckpt
from repro.core import MRSVMConfig, SVMConfig, fit_mapreduce
from repro.serving import StreamingSVMService


def _sep_data(seed, n, d=16, w_key=9):
    w = jax.random.normal(jax.random.PRNGKey(w_key), (d,))
    X = jax.random.normal(jax.random.PRNGKey(seed), (n, d))
    return X, jnp.sign(X @ w)


@pytest.fixture(scope="module")
def svc_cfg():
    return MRSVMConfig(sv_capacity=64, gamma=1e-4, max_rounds=3,
                       svm=SVMConfig(C=1.0, max_epochs=15))


def _tree(seed=0):
    rng = np.random.default_rng(seed)
    return {"w": rng.normal(size=(8, 4)).astype(np.float32),
            "b": rng.normal(size=(4,)).astype(np.float32),
            "ids": rng.integers(0, 100, size=(8,)).astype(np.int32)}


# ---------------------------------------------------------------------------
# plan: determinism + seam semantics
# ---------------------------------------------------------------------------

def test_plan_single_is_deterministic():
    for kind in sorted(faults.KINDS):
        a = faults.FaultPlan.single(kind, seed=7)
        b = faults.FaultPlan.single(kind, seed=7)
        assert a == b
        np.testing.assert_array_equal(a.rng("salt").integers(0, 99, 16),
                                      b.rng("salt").integers(0, 99, 16))
    # different seeds draw different schedules somewhere in the sweep
    whens = {faults.FaultPlan.single("ring_garble", seed=s).specs[0].when
             for s in range(16)}
    assert len(whens) > 1


def test_fire_consumes_counts_and_matches_when():
    plan = faults.FaultPlan(
        seed=0, specs=(faults.FaultSpec("transport_exc", when=2, count=2),))
    with faults.inject(plan) as armed:
        assert faults.fire("s", ("transport_exc",), when=1) is None
        assert faults.fire("s", ("transport_exc",), when=2) is not None
        assert faults.fire("s", ("transport_exc",), when=2) is not None
        assert faults.fire("s", ("transport_exc",), when=2) is None
        assert len(armed.fired) == 2 and armed.remaining == [0]
    # disarmed: the seam is free
    assert faults.fire("s", ("transport_exc",), when=2) is None


def test_maybe_raise_error_typing():
    cases = [("ckpt_write_fail", faults.InjectedWriteError),
             ("transport_exc", faults.TransientFault),
             ("handshake_flake", faults.TransientFault),
             ("scheduler_kill", faults.InjectedFault)]
    for kind, exc_type in cases:
        plan = faults.FaultPlan(seed=0, specs=(faults.FaultSpec(kind),))
        with faults.inject(plan):
            with pytest.raises(exc_type):
                faults.maybe_raise("s", kinds=(kind,))
    assert issubclass(faults.InjectedWriteError, OSError)


def test_unknown_kind_rejected():
    with pytest.raises(ValueError, match="unknown fault kind"):
        faults.FaultSpec("cosmic_ray")


# ---------------------------------------------------------------------------
# retry with backoff
# ---------------------------------------------------------------------------

def test_retry_absorbs_transient_failures():
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise OSError("transient")
        return "ok"

    seen = []
    before = faults.counters().get("retries", 0)
    out = faults.retry_with_backoff(flaky, attempts=4, base_s=0.001,
                                    retry_on=OSError,
                                    on_retry=lambda i, e: seen.append(i))
    assert out == "ok" and calls["n"] == 3 and seen == [0, 1]
    assert faults.counters()["retries"] == before + 2


def test_retry_exhaustion_raises_typed_and_chained():
    def doomed():
        raise OSError("disk on fire")

    with pytest.raises(faults.FaultDetected) as ei:
        faults.retry_with_backoff(doomed, attempts=2, base_s=0.001,
                                  retry_on=OSError, layer="ckpt",
                                  cause="snapshot write", action="fix disk")
    assert ei.value.layer == "ckpt"
    assert "2 attempts" in str(ei.value)
    assert isinstance(ei.value.__cause__, OSError)


def test_retry_does_not_swallow_foreign_errors():
    def buggy():
        raise ValueError("a validation error is not a flaky wire")

    with pytest.raises(ValueError):
        faults.retry_with_backoff(buggy, attempts=5, base_s=0.001,
                                  retry_on=OSError)


# ---------------------------------------------------------------------------
# collective watchdog
# ---------------------------------------------------------------------------

def test_watchdog_fires_writes_heartbeat_and_check_raises(tmp_path):
    hb = str(tmp_path / "hb.json")
    fired = []
    with faults.CollectiveWatchdog(0.15, heartbeat_path=hb,
                                   layer="serving", cause="test fold",
                                   on_timeout=fired.append) as wd:
        time.sleep(0.5)                      # strand: no beat
    assert wd.fired and fired and fired[0]["layer"] == "serving"
    with open(hb) as f:
        payload = json.load(f)
    assert payload["status"] == "timeout"
    with pytest.raises(faults.FaultDetected, match="test fold"):
        wd.check()


def test_watchdog_beats_keep_it_quiet(tmp_path):
    hb = str(tmp_path / "hb.json")
    with faults.CollectiveWatchdog(0.25, heartbeat_path=hb,
                                   on_timeout=lambda info: None) as wd:
        for _ in range(5):
            time.sleep(0.1)
            wd.beat()                        # progress inside the deadline
    assert not wd.fired
    wd.check()                               # no raise
    with open(hb) as f:
        assert json.load(f)["status"] == "alive"


def test_watchdog_rejects_nonpositive_deadline():
    with pytest.raises(ValueError):
        faults.CollectiveWatchdog(0.0)


# ---------------------------------------------------------------------------
# host readback detection
# ---------------------------------------------------------------------------

def test_check_finite_risks_arms():
    faults.check_finite_risks(np.ones((3, 4)))          # silent
    with pytest.raises(faults.FaultDetected) as ei:
        faults.check_finite_risks(np.array([1.0, np.inf]))
    assert ei.value.layer == "transport"                # wire checksum
    with pytest.raises(faults.FaultDetected) as ei:
        faults.check_finite_risks(np.array([1.0, np.nan]))
    assert ei.value.layer == "core"                     # poisoned rows
    # masked-out lanes don't count (parked sweep configs hold junk)
    faults.check_finite_risks(np.array([1.0, np.inf]),
                              mask=np.array([True, False]))


# ---------------------------------------------------------------------------
# checkpoint: durability, generations, fallback
# ---------------------------------------------------------------------------

def test_atomic_write_json_retries_injected_write_failures(tmp_path):
    path = str(tmp_path / "meta.json")
    plan = faults.FaultPlan(
        seed=0, specs=(faults.FaultSpec("ckpt_write_fail", count=2),))
    with faults.inject(plan) as armed:
        ckpt.atomic_write_json(path, {"ok": 1})
        assert armed.remaining == [0]        # both injected failures fired
    with open(path) as f:
        assert json.load(f) == {"ok": 1}
    # exhaustion is typed: more failures than attempts
    plan = faults.FaultPlan(
        seed=0, specs=(faults.FaultSpec("ckpt_write_fail", count=5),))
    with faults.inject(plan):
        with pytest.raises(faults.FaultDetected) as ei:
            ckpt.atomic_write_json(str(tmp_path / "m2.json"), {})
    assert ei.value.layer == "ckpt"


def test_generations_prune_and_gc(tmp_path):
    d = str(tmp_path)
    for t in range(5):
        ckpt.save(os.path.join(d, f"s_{t}.npz"), _tree(t), step=t, keep=3)
    meta = json.load(open(os.path.join(d, "ckpt_meta.json")))
    assert [g["step"] for g in meta["generations"]] == [2, 3, 4]
    assert meta["latest_step"] == 4                     # flat compat pointer
    kept = sorted(f for f in os.listdir(d) if f.endswith(".npz"))
    assert kept == ["s_2.npz", "s_3.npz", "s_4.npz"]    # older media GC'd
    assert ckpt.latest_step(d) == 4
    assert ckpt.latest_path(d).endswith("s_4.npz")


def test_latest_path_falls_back_past_corrupt_generations(tmp_path):
    d = str(tmp_path)
    for t in range(3):
        ckpt.save(os.path.join(d, f"s_{t}.npz"), _tree(t), step=t)

    def flip(path):
        with open(path, "r+b") as f:
            f.seek(os.path.getsize(path) // 2)
            b = f.read(1)
            f.seek(-1, 1)
            f.write(bytes([b[0] ^ 0x10]))

    before = faults.counters().get("ckpt_fallbacks", 0)
    flip(os.path.join(d, "s_2.npz"))
    assert ckpt.latest_step(d) == 1                     # skipped newest
    assert ckpt.latest_path(d).endswith("s_1.npz")
    assert faults.counters()["ckpt_fallbacks"] > before
    os.remove(os.path.join(d, "s_1.npz"))               # missing ≡ corrupt
    assert ckpt.latest_step(d) == 0
    flip(os.path.join(d, "s_0.npz"))
    assert ckpt.latest_step(d) is None                  # nothing intact left
    assert ckpt.latest_path(d) is None


def test_restore_verifies_leaf_checksums(tmp_path):
    path = str(tmp_path / "t.npz")
    tree = _tree(1)
    ckpt.save(path, tree)
    sums = ckpt.leaf_checksums(tree)
    out = ckpt.restore(path, tree, checksums=sums)
    for k in tree:
        np.testing.assert_array_equal(np.asarray(out[k]), tree[k])
    # same container, different payload, ORIGINAL checksums → detected
    evil = dict(tree, w=tree["w"] + 1)
    ckpt.save(path, evil)
    with pytest.raises(ckpt.CorruptCheckpointError, match="checksum"):
        ckpt.restore(path, tree, checksums=sums)


def test_ckpt_media_corruption_seam_breaks_the_crc(tmp_path):
    """The injected corruption lands AFTER the crc is recorded — so the
    generation it produced is exactly the kind restore must skip."""
    d = str(tmp_path)
    ckpt.save(os.path.join(d, "s_0.npz"), _tree(0), step=0)
    plan = faults.FaultPlan(seed=3,
                            specs=(faults.FaultSpec("ckpt_corrupt",
                                                    param=2),))
    with faults.inject(plan):
        ckpt.save(os.path.join(d, "s_1.npz"), _tree(1), step=1)
    assert ckpt.latest_step(d) == 0          # corrupt gen 1 skipped


# ---------------------------------------------------------------------------
# property: a snapshot restores bit-exact or raises — never silently wrong
# ---------------------------------------------------------------------------

def _corrupt_roundtrip_case(seed: int, frac: float, bit: int) -> None:
    tree = _tree(seed)
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "t.npz")
        ckpt.save(path, tree)
        sums = ckpt.leaf_checksums(tree)
        size = os.path.getsize(path)
        off = min(int(frac * size), size - 1)
        with open(path, "r+b") as f:
            f.seek(off)
            b = f.read(1)
            f.seek(off)
            f.write(bytes([b[0] ^ (1 << bit)]))
        try:
            out = ckpt.restore(path, tree, checksums=sums)
        except Exception:
            return          # detected: container or leaf refused to load
        for k in tree:      # …or the flip missed every stored payload bit
            np.testing.assert_array_equal(np.asarray(out[k]), tree[k])


try:
    from hypothesis import given, settings, strategies as st
except ImportError:
    # the container has no hypothesis (requirements-dev.txt): run the
    # same property over a seeded sample so the invariant stays tested
    def test_single_byte_corruption_never_restores_silently():
        rng = np.random.default_rng(2026)
        for _ in range(30):
            _corrupt_roundtrip_case(int(rng.integers(0, 2 ** 16)),
                                    float(rng.uniform()),
                                    int(rng.integers(0, 8)))
else:
    @given(seed=st.integers(0, 2 ** 16), frac=st.floats(0.0, 1.0),
           bit=st.integers(0, 7))
    @settings(max_examples=30, deadline=None)
    def test_single_byte_corruption_never_restores_silently(seed, frac, bit):
        _corrupt_roundtrip_case(seed, frac, bit)


# ---------------------------------------------------------------------------
# serving: quarantine, doomed waits, generation fallback
# ---------------------------------------------------------------------------

def test_quarantine_diverts_nonfinite_batches(svc_cfg):
    X0, y0 = _sep_data(0, 128)
    svc = StreamingSVMService(svc_cfg, num_partitions=4)
    svc.register("t", fit_mapreduce(X0, y0, 4, svc_cfg))
    Xp = np.array(_sep_data(1, 64)[0])
    Xp[3, 2] = np.nan
    uid = svc.submit("t", jnp.asarray(Xp), _sep_data(1, 64)[1])
    assert uid > 0 and svc.pending() == 0    # acknowledged but diverted
    assert len(svc.quarantined) == 1
    assert svc.run_wave() is None            # nothing poisoned to fold
    assert svc.snapshot("t").version == 0
    assert svc.throughput_report()["quarantined"] == 1
    # opt-out: a service folding raw firehose data can accept them
    svc2 = StreamingSVMService(svc_cfg, num_partitions=4, quarantine=False)
    svc2.register("t", fit_mapreduce(X0, y0, 4, svc_cfg))
    svc2.submit("t", jnp.asarray(Xp), _sep_data(1, 64)[1])
    assert svc2.pending() == 1


@pytest.fixture(scope="module")
def gate_models(svc_cfg):
    """One fitted dense and one blocked-CSR stream model, shared by the
    gate's cases (registering a model does not change it)."""
    sp_cfg = MRSVMConfig(sv_capacity=64, gamma=1e-4, max_rounds=3,
                         svm=SVMConfig(C=1.0, max_epochs=15,
                                       row_format="sparse_csr", nnz_cap=8))
    X0, y0 = _sep_data(0, 128)
    Xs0 = sparse.from_dense(X0 * (jnp.abs(X0) > 1.0), 8)
    return {"dense": (svc_cfg, fit_mapreduce(X0, y0, 4, svc_cfg)),
            "csr": (sp_cfg, fit_mapreduce(Xs0, y0, 4, sp_cfg))}


def _gate_batch(fmt, poison):
    X, y = (np.array(a) for a in _sep_data(1, 64))
    bad = {"nan_x": (X, (3, 2), np.nan), "posinf_x": (X, (5, 0), np.inf),
           "neginf_y": (y, (7,), -np.inf)}.get(poison)
    if bad:
        a, idx, value = bad
        a[idx] = value
    if fmt == "dense":
        return jnp.asarray(X), jnp.asarray(y)
    Xs = sparse.from_dense(jnp.asarray(X * (np.abs(X) > 1.0)), 8)
    if poison == "nan_values":
        Xs = sparse.SparseRows(Xs.indices, Xs.values.at[3, 0].set(jnp.nan),
                               Xs.d)
    return Xs, jnp.asarray(y)


@pytest.mark.parametrize("fmt,poison", [
    ("dense", "nan_x"), ("dense", "posinf_x"), ("dense", "neginf_y"),
    ("csr", "nan_values"), ("dense", None), ("csr", None)])
def test_quarantine_gate_by_format_and_value(gate_models, fmt, poison):
    cfg, model = gate_models[fmt]
    svc = StreamingSVMService(cfg, num_partitions=4)
    svc.register("t", model)
    before = faults.counters().get("quarantined", 0)
    uid = svc.submit("t", *_gate_batch(fmt, poison))
    assert uid > 0                           # acknowledged either way
    diverted = poison is not None
    assert len(svc.quarantined) == int(diverted)
    assert svc.pending() == int(not diverted)
    assert faults.counters().get("quarantined", 0) - before == int(diverted)
    assert svc.throughput_report()["quarantined"] == int(diverted)


def test_quarantine_diverts_only_the_poisoned_batch_of_a_submit(gate_models):
    cfg, model = gate_models["dense"]
    svc = StreamingSVMService(cfg, num_partitions=4)
    svc.register("t", model)
    clean = _gate_batch("dense", None)
    uids = svc.submit_many([("t",) + clean,
                            ("t",) + _gate_batch("dense", "nan_x")])
    assert len(set(uids)) == 2
    assert [mb.uid for mb in svc.quarantined] == [uids[1]]
    assert svc.pending() == 1
    assert svc.run_wave() is not None        # the clean batch folds
    assert [mb.uid for mb in svc.done] == [uids[0]]
    assert svc.pending() == 0 and svc.snapshot("t").version == 1
    assert svc.throughput_report()["quarantined"] == 1


def test_quarantine_gate_reads_back_one_scalar():
    """The gate's device program returns a 0-d bool, so the host reads
    one flag a batch and never the rows."""
    from repro.serving.svm_stream import _finite_flag
    X, y = _gate_batch("dense", None)
    Xs, ys = _gate_batch("csr", None)
    for vals, labels in ((X, y), (Xs.values, ys)):
        (aval,) = jax.make_jaxpr(_finite_flag)(vals, labels).out_avals
        assert aval.shape == () and aval.dtype == jnp.bool_


def test_injected_poison_rows_are_quarantined(svc_cfg):
    X0, y0 = _sep_data(0, 128)
    svc = StreamingSVMService(svc_cfg, num_partitions=4)
    svc.register("t", fit_mapreduce(X0, y0, 4, svc_cfg))
    Xc, yc = _sep_data(2, 64)
    plan = faults.FaultPlan.single("poison_rows", seed=5)
    with faults.inject(plan) as armed:
        svc.submit("t", Xc, yc)
        assert armed.fired                   # the seam poisoned the batch
    assert len(svc.quarantined) == 1 and svc.pending() == 0


def test_wait_idle_surfaces_doomed_states(svc_cfg):
    X0, y0 = _sep_data(0, 128)
    svc = StreamingSVMService(svc_cfg, num_partitions=4)
    svc.register("t", fit_mapreduce(X0, y0, 4, svc_cfg))
    svc.submit("t", *_sep_data(1, 64))
    # queued work, no scheduler: raise now, don't burn the timeout
    with pytest.raises(RuntimeError, match="no scheduler is running"):
        svc.wait_idle(timeout_s=30.0)
    # a scheduler killed mid-wave records its error; doomed waits and
    # later submits surface it instead of queueing forever
    with faults.inject(faults.FaultPlan.single("scheduler_kill", seed=1)):
        svc.start(idle_poll_s=0.01)
        with pytest.raises(RuntimeError, match="scheduler died"):
            svc.wait_idle(timeout_s=30.0)
    with pytest.raises(RuntimeError, match="scheduler died"):
        svc.submit("t", *_sep_data(3, 64))
    with pytest.raises(RuntimeError, match="scheduler died"):
        svc.stop()
    assert svc.pending() >= 1                # the wave was requeued intact


def test_stall_watchdog_detects_stuck_fold(svc_cfg):
    X0, y0 = _sep_data(0, 128)
    fires = []
    svc = StreamingSVMService(svc_cfg, num_partitions=4,
                              fold_deadline_s=0.2,
                              watchdog_handler=fires.append)
    svc.register("t", fit_mapreduce(X0, y0, 4, svc_cfg))
    svc.submit("t", *_sep_data(1, 64))
    with faults.inject(faults.FaultPlan.single("stall", seed=0)):
        with pytest.raises(faults.FaultDetected, match="fold"):
            svc.run_wave()
    assert fires and svc.throughput_report()["watchdog_fires"] == 1
    # the fold itself finished and PUBLISHED before the deadline check
    # raised — exactly-once keeps the batch completed, not requeued
    assert svc.pending() == 0
    assert svc.snapshot("t").version == 1
    # healthy folds pass under the same watchdog
    svc.submit("t", *_sep_data(2, 64))
    assert svc.run_wave() is not None
    assert svc.snapshot("t").version == 2


def test_service_restore_falls_back_past_corrupt_generation(
        svc_cfg, tmp_path):
    d = str(tmp_path / "ck")
    X0, y0 = _sep_data(0, 128)
    svc = StreamingSVMService(svc_cfg, num_partitions=4, checkpoint_dir=d,
                              checkpoint_every_waves=1)
    svc.register("t", fit_mapreduce(X0, y0, 4, svc_cfg))   # generation 0
    svc.submit("t", *_sep_data(1, 64))
    assert svc.run_wave() is not None                      # generation 1
    man = json.load(open(os.path.join(d, "service_manifest.json")))
    assert man["format"] == 2 and len(man["generations"]) == 2
    newest = man["generations"][-1]["streams"]["t"]["file"]
    with open(os.path.join(d, newest), "r+b") as f:
        f.seek(os.path.getsize(os.path.join(d, newest)) // 2)
        b = f.read(1)
        f.seek(-1, 1)
        f.write(bytes([b[0] ^ 0x20]))
    r = StreamingSVMService.restore(svc_cfg, d)
    assert r.restore_fallbacks == 1
    assert r.snapshot("t").version == 0      # the intact generation 0
    # every generation corrupt → typed, named, actionable
    for fn in os.listdir(d):
        if fn.endswith(".npz"):
            with open(os.path.join(d, fn), "r+b") as f:
                f.truncate(8)
    with pytest.raises(faults.FaultDetected) as ei:
        StreamingSVMService.restore(svc_cfg, d)
    assert ei.value.layer == "ckpt"
    assert "no intact snapshot generation" in str(ei.value)


def test_stop_detects_refused_to_die_thread(svc_cfg):
    X0, y0 = _sep_data(0, 128)
    svc = StreamingSVMService(svc_cfg, num_partitions=4)
    svc.register("t", fit_mapreduce(X0, y0, 4, svc_cfg))
    release = threading.Event()
    svc._thread = threading.Thread(target=release.wait, daemon=True)
    svc._thread.start()                      # a "stranded" scheduler stub
    try:
        with pytest.raises(faults.FaultDetected, match="refused to die"):
            svc.stop(timeout_s=0.2)
    finally:
        release.set()
        svc._thread.join(timeout=5)
        svc._thread = None
