"""The reduction from a trace to the per-layer numbers, on a small
recorded trace (``data/small_trace.json``: one device with its module
and op lines, and host threads, as ``bench.trace.load`` reads them)."""
import json
from pathlib import Path

import pytest

from bench import trace

DATA = Path(__file__).resolve().parent / "data" / "small_trace.json"


@pytest.fixture(scope="module")
def tr():
    d = json.loads(DATA.read_text())
    E = lambda evs: [trace.Event(*e) for e in evs]
    devices = {p: {ln: E(evs) for ln, evs in lines.items()}
               for p, lines in d["devices"].items()}
    return trace.from_events(devices, E(d["host"]))


def test_window_from_annotation(tr):
    lo, hi = tr.window
    assert (lo, hi) == pytest.approx((1.0, 3.0))
    assert trace.window_s(tr) == pytest.approx(2.0)


def test_busy_is_union_clipped_to_window(tr):
    # ops: [0.5,1.5] clipped to [1,1.5], [1.4,1.9] overlaps it, [2.2,2.4],
    # [2.9,3.5] clipped to [2.9,3.0]
    assert trace.busy_s(tr) == pytest.approx(0.9 + 0.2 + 0.1)


def test_program_seconds_by_stable_name(tr):
    secs = trace.program_seconds(tr)
    assert secs["jit__sweep_round_jit"] == pytest.approx(0.9)
    assert secs["jit__sweep_final_jit"] == pytest.approx(0.2 + 0.1)
    runs = trace.program_runs(tr)
    assert runs == {"jit__sweep_final_jit": 2}      # the round began before


def test_breakdown(tr):
    ops = dict(trace.top_ops(tr))
    assert ops["fusion.1"] == pytest.approx(0.5 + 0.5)
    gaps = trace.idle_gaps(tr)
    assert gaps[0] == ["bench.wait", pytest.approx(0.5)]   # [2.4, 2.9]
    assert [g[1] for g in gaps] == sorted((g[1] for g in gaps), reverse=True)
    assert sum(g[1] for g in gaps) == pytest.approx(2.0 - 1.2)


def test_program_name():
    assert trace.program_name("jit__round_jit(12)") == "jit__round_jit"
    assert trace.program_name("jit_add") == "jit_add"


@pytest.fixture(scope="module")
def tpu():
    """20 ms recorded on a TPU v5 lite at the opening of a traced
    ``dense.train`` window: the fit's padding copies, then its first
    ``_round_jit``."""
    d = json.loads((DATA.parent / "tpu_trace.json").read_text())
    E = lambda evs: [trace.Event(*e) for e in evs]
    devices = {p: {ln: E(evs) for ln, evs in lines.items()}
               for p, lines in d["devices"].items()}
    return trace.from_events(devices, E(d["host"]))


def test_recorded_busy_matches_a_grid_count(tpu):
    import numpy as np
    lo, hi = tpu.window
    grid = np.zeros(int(round((hi - lo) * 1e6)), bool)       # 1 µs bins
    for e in tpu.devices["/device:TPU:0"]["XLA Ops"]:
        a = int(np.floor((max(e.start, lo) - lo) * 1e6))
        b = int(np.ceil((min(e.start + e.dur, hi) - lo) * 1e6))
        grid[max(a, 0):max(b, 0)] = True
    busy = trace.busy_s(tpu)
    assert 0 < busy <= trace.window_s(tpu)
    assert busy == pytest.approx(grid.sum() * 1e-6, abs=2e-6 * 46)
    gaps = trace.idle_gaps(tpu, k=1000)
    assert sum(g[1] for g in gaps) == pytest.approx(
        trace.window_s(tpu) - busy, abs=1e-9)


def test_recorded_programs(tpu):
    secs = trace.program_seconds(tpu)
    assert {"jit__pad", "jit_reshape", "jit__round_jit"} <= set(secs)
    lo, hi = tpu.window
    rnd = [e for e in tpu.devices["/device:TPU:0"]["XLA Modules"]
           if e.name.startswith("jit__round_jit")]
    assert secs["jit__round_jit"] == pytest.approx(
        sum(min(e.start + e.dur, hi) - max(e.start, lo) for e in rnd))
    assert trace.program_runs(tpu)["jit__round_jit"] == 1
    assert sum(secs.values()) <= trace.window_s(tpu) + 1e-9


def test_window_between_open_and_close_marks():
    """A trace the harness stopped early: the window runs from the
    ``bench.open`` mark to the ``bench.close`` mark, and the marks name
    no idle gap."""
    d = json.loads(DATA.read_text())
    E = lambda evs: [trace.Event(*e) for e in evs]
    devices = {p: {ln: E(evs) for ln, evs in lines.items()}
               for p, lines in d["devices"].items()}
    host = [e for e in E(d["host"]) if e.name != "bench.window"]
    host += [trace.Event("bench.open", 1.0, 0.0),
             trace.Event("bench.close", 2.0, 0.0)]
    tr = trace.from_events(devices, host)
    assert tr.window == pytest.approx((1.0, 2.0))
    assert trace.busy_s(tr) == pytest.approx(0.9)
    assert all(g[0] not in ("bench.open", "bench.close")
               for g in trace.idle_gaps(tr))
