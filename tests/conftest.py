"""Shared fixtures. The dual-CD ``fori_loop`` reducers dominate suite
wall-clock, so convergence-insensitive tests take their solver/driver
configs from the session-scoped fast fixtures below instead of
hand-rolling slow ones (ISSUE 1 satellite)."""
import os

import pytest


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: dry-run subprocess tests")


def subprocess_env(**overrides):
    """Minimal env for subprocess-based tests (fake-device runs need a
    fresh backend init). The tests run on the CPU backend
    (``JAX_PLATFORMS=cpu``, faked devices via XLA_FLAGS), and the child
    keeps that platform: these forced-host-device runs are CPU by
    construction. On a TPU the system runs through ``chip_smoke.py``,
    in one process."""
    env = {"PYTHONPATH": "src",
           "PATH": os.environ.get("PATH", "/usr/bin:/bin"),
           "HOME": os.environ.get("HOME", "/root"),
           "JAX_PLATFORMS": os.environ.get("JAX_PLATFORMS", "cpu")}
    env.update(overrides)
    return env


@pytest.fixture(scope="session")
def fast_svm_cfg():
    """Small-epoch reducer solver: enough to find the support set on the
    synthetic separable problems, ~2-3× cheaper than the defaults."""
    from repro.core import SVMConfig
    return SVMConfig(C=1.0, max_epochs=12, tol=5e-3)


@pytest.fixture(scope="session")
def fast_mr_cfg(fast_svm_cfg):
    """Small-capacity MapReduce driver riding on ``fast_svm_cfg``."""
    from repro.core import MRSVMConfig
    return MRSVMConfig(sv_capacity=32, max_rounds=3, svm=fast_svm_cfg)
