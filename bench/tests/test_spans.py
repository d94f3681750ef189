"""The program's host spans (DESIGN.md §17) in a traced run of the tiny
cells on the CPU: each nests where it should, on its own thread, and
its keyword arguments join it to the run's records."""
import glob
import os
from typing import NamedTuple

import pytest

from bench import run as run_lib
from bench.tests import tiny


class Span(NamedTuple):
    name: str
    thread: tuple
    start: int
    end: int
    args: dict


def host_spans(trace_dir):
    from jax.profiler import ProfileData
    path = max(glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                         recursive=True), key=os.path.getmtime)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:CPU"):
            for i, line in enumerate(plane.lines):
                out.extend(Span(e.name, (plane.name, i), e.start_ns,
                                e.end_ns, dict(e.stats))
                           for e in line.events
                           if e.name.startswith(("svc.", "mr.")))
    return sorted(out, key=lambda s: s.start)


def inside(parent, spans, name):
    return [s for s in spans if s.name == name and s.thread == parent.thread
            and parent.start <= s.start and s.end <= parent.end]


def traced(bd, cell, monkeypatch):
    """A traced tiny run: its result line, records and host spans."""
    seen = {}

    def spy(*a, **k):
        seen.update(execute(*a, **k))
        return seen

    execute = run_lib.execute
    monkeypatch.setattr(run_lib, "execute", spy)
    out = tiny.run(bd, cell, 23, seconds=1.0, trace=True)
    return out, seen["records"], host_spans(seen["trace_dir"])


def test_fold_span_tree(tiny_bench, monkeypatch):
    out, records, spans = traced(tiny_bench, "d.fold", monkeypatch)
    assert out["correct"]
    assert out["metrics"]["quarantine_s"]["value"] > 0
    tenants = ["t000", "t001"]

    submits = [s for s in spans if s.name == "svc.submit"]
    assert submits
    for sub in submits:
        qs = inside(sub, spans, "svc.quarantine")
        assert len(qs) == sub.args["batches"] == len(tenants)
        assert all(q.args["rows"] == 64 and q.args["bytes"] > 0 for q in qs)

    waves = [s for s in spans if s.name == "svc.wave"]
    ids = [w.args["wave"] for w in waves]
    assert ids and ids == [w["wave"] for w in records["waves"]][
        1:1 + len(ids)]                           # wave 0 warmed up
    uids_of = {}
    for b in records["batches"]:
        uids_of.setdefault(b["wave"], []).append(b["uid"])
    versions = records["versions"]
    for w in waves:
        (admit,) = inside(w, spans, "svc.admit")
        (stack,) = inside(w, spans, "svc.stack")
        (fit,) = inside(w, spans, "mr.fit")
        (final,) = inside(w, spans, "mr.final")
        rounds = inside(w, spans, "mr.round")
        swaps = inside(w, spans, "svc.swap")
        assert admit.args["wave"] == w.args["wave"]
        assert sorted(map(int, str(admit.args["uids"]).split())) == sorted(
            uids_of[w.args["wave"]])
        assert stack.args["width"] == len(tenants)
        assert [r.args["round"] for r in rounds] == list(range(len(rounds)))
        for r in rounds:
            assert len(inside(r, spans, "mr.eq8")) == 1
        assert (admit.end <= stack.start <= fit.start <= rounds[0].start
                and rounds[-1].end <= final.start and final.end <= fit.end)
        assert sorted(s.args["stream"] for s in swaps) == tenants
        for s in swaps:
            assert fit.end <= s.start
            assert versions[s.args["stream"]][s.args["version"]][
                "rounds"] == len(rounds)


def test_train_span_tree(tiny_bench, monkeypatch):
    out, records, spans = traced(tiny_bench, "d.train", monkeypatch)
    assert out["correct"]
    assert "quarantine_s" not in out["metrics"]
    fits = [s for s in spans if s.name == "mr.fit"]
    assert fits
    assert len(fits) <= len(records["fits"]) + 1     # the last may be cut
    for fit, rec in zip(fits, records["fits"]):
        rounds = inside(fit, spans, "mr.round")
        assert len(rounds) == rec["rounds"]
        for r in rounds:
            assert len(inside(r, spans, "mr.eq8")) == 1
        (final,) = inside(fit, spans, "mr.final")
        assert rounds[-1].end <= final.start
