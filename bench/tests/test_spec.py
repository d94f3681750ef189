"""BENCHMARK.json keeps its contract, and every piece is found by
name: a configuration, a traffic mix, the limits of a cell and a
metric's reader are files, so adding one edits no code."""
import json
import re
from pathlib import Path

import pytest

from bench import spec

ROOT = Path(__file__).resolve().parents[2]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    return spec.load_benchmark(ROOT)


def test_keys_and_names(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["bench"]
    assert 1 <= bench["run_seconds"] <= 51
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in bench[group]:
            assert NAME.match(e["name"]), e["name"]
            names.append((group, e["name"]))
    assert len(set(n for _, n in names if _ in ("end_to_end", "per_layer"))
               ) == len(bench["end_to_end"]) + len(bench["per_layer"])
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")


def test_every_piece_is_a_file(bench):
    cfgs = {c["name"]: c for c in bench["configs"]}
    for c in bench["configs"]:
        body = json.loads((ROOT / c["file"]).read_text())
        assert c["file"].startswith("bench/configs/")
        assert set(c["reduced"]) == set(body["reduced"])
    for w in bench["workloads"]:
        assert w["config"] in cfgs and w["chips"] in (1, 4)
        spec.traffic(w["traffic"])
        assert "unchecked" in spec.limits(w["name"])
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(spec.reader(m["name"]))


def test_every_cell_reports_enough(bench):
    e2e = {m["name"] for m in bench["end_to_end"]}
    for w in bench["workloads"]:
        mine = {m["name"] for m in spec.metrics_for(bench, w["name"],
                                                    "end_to_end")}
        assert "setup_s" in mine and len(mine) >= 2
        layer = spec.metrics_for(bench, w["name"], "per_layer")
        assert layer
        for m in layer:
            assert m["moves"] in mine | ({"setup_s"} & e2e)


def test_a_new_metric_is_a_file_and_an_entry(tiny_bench):
    """A per-layer metric that no code names: its reader file and its
    entry are enough for a traced run to report it."""
    from bench.tests import tiny
    (tiny_bench / "metrics" / "dummy_waves.py").write_text(
        "def read(run):\n"
        "    return float(len(run.records['waves']))\n")
    b = json.loads((tiny_bench / "BENCHMARK.json").read_text())
    b["per_layer"].append({"name": "dummy_waves", "unit": "waves",
                           "better": "higher", "source": "program_counter",
                           "layer": "service", "moves": "fold_rows_per_s",
                           "workloads": ["d.fold"]})
    (tiny_bench / "BENCHMARK.json").write_text(json.dumps(b))
    out = tiny.run(tiny_bench, "d.fold", 5, seconds=1.0, trace=True)
    assert out["metrics"]["dummy_waves"]["value"] >= 2
    assert out["metrics"]["dummy_waves"]["unit"] == "waves"
    assert out["correct"]


def test_peaks_by_device_kind():
    p = spec.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(spec.SpecError):
        spec.peaks("cpu")
