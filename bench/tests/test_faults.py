"""The harness, driven past its look for a chip, sees ``correct`` come
out false when the timed path is broken underneath it by each of the
cell's faults (``bench.faults``), and true when it is not."""
import pytest

from bench import faults
from bench.tests import tiny

FAULTS = {"d.fold": faults.BY_MODE["closed"],
          "d.train": faults.BY_MODE["train"],
          "c.fold": faults.BY_MODE["closed"],
          "c.train": faults.BY_MODE["train"]}


@pytest.mark.parametrize("cell,fault", [(c, f) for c, fs in FAULTS.items()
                                        for f in fs],
                         ids=lambda v: getattr(v, "__name__", v))
def test_fault_is_not_correct(tiny_bench, monkeypatch, cell, fault):
    """On CSR cells the program is given float32 values, so that the
    sound run it is set against reads ``correct``
    (``test_sound_csr_run_is_correct_with_float32_values``)."""
    if cell.startswith("c."):
        tiny.widen_csr_values(monkeypatch)
    for target, attr, value in fault():
        monkeypatch.setattr(target, attr, value)
    out = tiny.run(tiny_bench, cell, 31, seconds=1.0)
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("cell", ["d.fold", "d.train"])
def test_sound_run_is_correct(tiny_bench, cell):
    out = tiny.run(tiny_bench, cell, 31, seconds=2.0)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["metrics"]["setup_s"]["value"] > 0
    assert list(out)[-1] == "checks"
    calls = out["reference"]
    assert calls["sv_gap"] is None or calls["sv_gap"] > 0
    assert calls["sv_ties"] >= 0


@pytest.mark.parametrize("cell", ["c.fold", "c.train"])
def test_sound_csr_run_is_correct_with_float32_values(tiny_bench,
                                                      monkeypatch, cell):
    """The harness's CSR path end to end (rows, program row type, chain,
    reference) agrees with the program once the program's values are
    float32. With the configuration's bfloat16 values the program
    departs from the reference on some seeds (PERF.md, open question
    0c), which is the program's to fix."""
    tiny.widen_csr_values(monkeypatch)
    out = tiny.run(tiny_bench, cell, 31, seconds=2.0)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["checks"]["w"]["value"] < 1e-5
