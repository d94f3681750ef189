"""The comparison passes the program and fails its control, the
reference computed in bfloat16 in the program's place; and its pieces
do what they say."""
import pytest

from bench import compare


@pytest.mark.parametrize("cell", ["d.fold", "d.train"])
def test_program_passes_and_lower_precision_fails(tiny_bench, cell):
    """``bench.control``'s readings of one run: the program within the
    cell's limits, the control in its place outside them."""
    import json

    import jax

    from bench import control, spec
    bench = json.loads((tiny_bench / "BENCHMARK.json").read_text())
    lines = control.readings(bench, spec.cell(bench, cell), 21, 1.0,
                             tiny_bench.parent, jax.devices()[:1],
                             who="control", bench_dir=tiny_bench)
    by = {line["who"]: line for line in lines}
    assert by["program"]["correct"], by["program"]["checks"]
    assert not by["control"]["correct"], by["control"]["checks"]
    assert by["control"]["numbers"]["unchecked"] == 0


def test_control_reads_planted_faults(tiny_bench):
    import json

    import jax

    from bench import control, faults, spec
    bench = json.loads((tiny_bench / "BENCHMARK.json").read_text())
    line, = control.readings(bench, spec.cell(bench, "d.train"), 22, 1.0,
                             tiny_bench.parent, jax.devices()[:1],
                             fault=faults.half_fit, bench_dir=tiny_bench)
    assert line["who"] == "half_fit" and not line["correct"]
    line, = control.readings(bench, spec.cell(bench, "d.train"), 22, 1.0,
                             tiny_bench.parent, jax.devices()[:1],
                             bench_dir=tiny_bench)
    assert line["who"] == "program" and line["correct"], line["checks"]


@pytest.mark.parametrize("cell", ["c.fold", "c.train"])
def test_lower_precision_fails_on_csr_cells(tiny_bench, cell):
    """The control on CSR rows: the same reference with its values, its
    ``Q_ii`` and its state in bfloat16, outside the cell's limits."""
    import json

    import jax

    from bench import control, spec
    bench = json.loads((tiny_bench / "BENCHMARK.json").read_text())
    lines = control.readings(bench, spec.cell(bench, cell), 21, 1.0,
                             tiny_bench.parent, jax.devices()[:1],
                             who="control", bench_dir=tiny_bench)
    by = {line["who"]: line for line in lines}
    assert not by["control"]["correct"], by["control"]["checks"]
    assert by["control"]["numbers"]["unchecked"] == 0


@pytest.mark.parametrize("cell,fault", [("c.train", "half_fit"),
                                        ("c.fold", "half_fold")])
def test_control_reads_planted_faults_on_csr(tiny_bench, monkeypatch, cell,
                                            fault):
    """As ``test_control_reads_planted_faults``, on CSR cells, with the
    program given float32 values (``tiny.widen_csr_values``)."""
    import json

    import jax

    from bench import control, faults, spec
    from bench.tests import tiny
    tiny.widen_csr_values(monkeypatch)
    bench = json.loads((tiny_bench / "BENCHMARK.json").read_text())
    line, = control.readings(bench, spec.cell(bench, cell), 22, 1.0,
                             tiny_bench.parent, jax.devices()[:1],
                             fault=getattr(faults, fault),
                             bench_dir=tiny_bench)
    assert line["who"] == fault and not line["correct"]
    line, = control.readings(bench, spec.cell(bench, cell), 22, 1.0,
                             tiny_bench.parent, jax.devices()[:1],
                             bench_dir=tiny_bench)
    assert line["who"] == "program" and line["correct"], line["checks"]


def test_merge_gap_is_the_closest_call():
    """``sv_gap`` is the least α kept less the most left out, over the
    partitions whose first row left out would also have been an SV."""
    import jax.numpy as jnp

    from bench import reference as ref
    ev = jnp.asarray([[0.9, 0.5, 0.45, 0.0],      # 0.5 kept, 0.45 out
                      [1.0, 1.0, 1.0, 0.1],       # a tie at C = 1
                      [0.3, 0.2, 0.0, 0.0]])      # nothing left out
    gap, ties = ref.closest_call(ev, 2, 1e-6, 1.0)
    assert float(gap) == pytest.approx(0.05) and int(ties) == 1
    gap, ties = ref.closest_call(ev[2:], 2, 1e-6, 1.0)
    assert float(gap) == float("inf") and int(ties) == 0
    assert float(ref.closest_call(ev, 4, 1e-6, 1.0)[0]) == float("inf")


def test_verdict_needs_every_number_within_its_limit():
    ok, checks = compare.verdict({"sv": 0.01, "final": 0.2},
                                 {"sv": 0.02, "final": 0.1})
    assert not ok and list(checks) == ["sv", "final"]
    assert compare.verdict({"sv": 0.0}, {"sv": 0.0})[0]
    assert not compare.verdict({"sv": 0.0}, {"sv": 0.0, "w": 1.0})[0]
    assert not compare.verdict({"sv": float("nan")}, {"sv": 1.0})[0]


def test_id_mismatch_counts_both_sides():
    assert compare.id_mismatch([1, 2, 3, -1], [1, 2, 4, -1]) == 2 / 3
    assert compare.id_mismatch([-1, -1], [-1, -1]) == 0.0
