"""The device ops' scope paths and their union (``bench/metrics/
_scopes.py``): on a recorded TPU v5 lite excerpt (``data/
tpu_scopes.json``: ``while`` ops and the fusions they hold, each with
the scope path its metadata carried, if any), on a trace file's event
metadata built field by field, and on made-up profiler lines."""
import json
import struct
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from bench import trace
from bench.metrics import _scopes

DATA = Path(__file__).resolve().parent / "data" / "tpu_scopes.json"
SCOPES = ("svm.solve", "mr.merge", "mr.score")


@pytest.fixture(scope="module")
def excerpt():
    """The excerpt's ops as the reader names them: its ``%while`` ops
    (``hlo_category`` while, no ``tf_op``) take their body's scope."""
    d = json.loads(DATA.read_text())
    ops = [trace.Event(_scopes.LOOP if name.startswith("%while") else path,
                       s, dur) for name, path, s, dur in d["ops"]]
    return d, {"/device:TPU:0": _scopes.loops_take_their_body_scope(ops)}, \
        tuple(d["window"])


def _grid(events, lo, hi):
    grid = np.zeros(int(round((hi - lo) * 1e7)), bool)       # 0.1 µs bins
    for e in events:
        a = int(np.floor((max(e.start, lo) - lo) * 1e7))
        b = int(np.ceil((min(e.start + e.dur, hi) - lo) * 1e7))
        grid[max(a, 0):max(b, 0)] = True
    return grid.sum() * 1e-7


@pytest.mark.parametrize("scope", SCOPES)
def test_scope_union_matches_a_grid_count(excerpt, scope):
    d, ops, (lo, hi) = excerpt
    evs = [e for e in ops["/device:TPU:0"] if scope in e.name]
    assert evs, scope
    secs = _scopes.scope_seconds(ops, scope, (lo, hi))
    assert 0 < secs <= hi - lo
    assert secs == pytest.approx(_grid(evs, lo, hi), abs=1e-7 * 2 * len(evs))


def test_loops_are_the_solvers_and_not_summed(excerpt):
    """The solver's loops are ``while`` ops whose intervals hold their
    body's ops: a union counts that time once, a sum twice. The trace
    gives the loop ops no scope; named by their body, they carry
    ``svm.solve``, and the solve's time up to its last op is all
    scoped: the body ops alone miss the loop's own steps."""
    d, ops, (lo, hi) = excerpt
    evs = ops["/device:TPU:0"]
    loops = [e for e, o in zip(evs, d["ops"]) if o[0].startswith("%while")]
    assert loops and all("svm.solve" in e.name for e in loops)
    assert all(not o[1] for o in d["ops"] if o[0].startswith("%while"))
    clipped = sum(min(e.start + e.dur, hi) - max(e.start, lo) for e in evs
                  if min(e.start + e.dur, hi) > max(e.start, lo))
    busy = _scopes.scope_seconds(ops, "", (lo, hi))     # "" is in every path
    assert busy < clipped
    end = max(s + dur for _, path, s, dur in d["ops"] if "svm.solve" in path)
    body = {"t": [trace.Event(path, s, dur) for _, path, s, dur in d["ops"]]}
    assert _scopes.scope_seconds(ops, "svm.solve", (lo, end)) == \
        pytest.approx(end - lo)
    assert 0.99 * (end - lo) < _scopes.scope_seconds(
        body, "svm.solve", (lo, end)) < end - lo
    assert sum(_scopes.scope_seconds(ops, s, (lo, hi)) for s in SCOPES) \
        <= busy + 1e-9


# -- a trace file's event metadata, built field by field --------------------

def _varint(n):
    n %= 1 << 64
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _int(num, n):
    return _varint(num << 3) + _varint(n)


def _msg(num, payload):
    payload = payload.encode() if isinstance(payload, str) else payload
    return _varint(num << 3 | 2) + _varint(len(payload)) + payload


def _stat(mid, *, s=None, ref=None, u=None, i=None, d=None):
    out = _int(1, mid)
    if d is not None:
        out += _varint(2 << 3 | 1) + struct.pack("<d", d)
    if u is not None:
        out += _int(3, u)
    if i is not None:
        out += _int(4, i)
    if s is not None:
        out += _msg(5, s)
    if ref is not None:
        out += _int(7, ref)
    return out


def _plane(name, stat_names, metas, lines=b""):
    out = _int(1, 7) + _msg(2, name) + lines
    for mid, (mname, stats) in metas.items():
        body = _int(1, mid) + _msg(2, mname) + _msg(4, mname.split()[0])
        body += b"".join(_msg(5, st) for st in stats)
        out += _msg(4, _int(1, mid) + _msg(2, body))
    for sid, sname in stat_names.items():
        out += _msg(5, _int(1, sid) + _msg(2, _int(1, sid) + _msg(2, sname)))
    return out


def test_op_scopes_reads_the_device_planes_metadata():
    pid = 14163331976767123122              # above 2**63: int64 negative
    names = {1: "hlo_category", 2: "program_id", 3: "tf_op",
             4: "jit(_round_jit)/vmap(svm.solve)/while/body/add"}
    metas = {
        10: ("%while.1 = (f32[8]) while(...)", [
            _stat(1, s="while"), _stat(2, u=pid),
            _stat(3, s="jit(_round_jit)/vmap(svm.solve)/while")]),
        14: ("%while.5 = (f32[8]) while(...)", [       # the v5e's: no tf_op
            _stat(1, s="while"), _stat(2, u=pid)]),
        11: ("%fusion.2 = f32[8] fusion(...)", [
            _stat(2, i=pid - (1 << 64)), _stat(3, ref=4), _stat(9, d=1.5)]),
        12: ("%fusion.2 = f32[8] fusion(...)", [
            _stat(2, u=77), _stat(3, s="jit(_final_fit_jit)/copy")]),
        13: ("%copy = f32[8] copy(...)", [_stat(2, u=77)]),     # no scope
    }
    line = _msg(3, _int(1, 1) + _msg(2, "XLA Ops")
                + _msg(4, _int(1, 10) + _int(2, 5) + _int(3, 9)))
    space = (_msg(1, _plane("/host:CPU", names, metas))
             + _msg(1, _plane("/device:TPU:0", names, metas, line))
             + _msg(2, "an error"))
    assert _scopes.op_scopes(space) == {"/device:TPU:0": {
        (pid, "%while.1 = (f32[8]) while(...)"):
            "jit(_round_jit)/vmap(svm.solve)/while",
        (pid, "%fusion.2 = f32[8] fusion(...)"):
            "jit(_round_jit)/vmap(svm.solve)/while/body/add",
        (77, "%fusion.2 = f32[8] fusion(...)"): "jit(_final_fit_jit)/copy",
        (pid, "%while.5 = (f32[8]) while(...)"): _scopes.LOOP,
    }}


# -- the join of op events to their metadata ---------------------------------

def _line(name, events):
    return SimpleNamespace(name=name, events=[
        SimpleNamespace(name=n, start_ns=s, duration_ns=d) for n, s, d in
        events])


def test_ops_join_their_metadata_by_program_and_name():
    """An op's name is unique in its program only: ``%fusion.2`` of the
    round program and of the final solve carry different scopes."""
    scopes = {(1, "%while.0"): _scopes.LOOP, (1, "%while.1"): _scopes.LOOP,
              (1, "%fusion.2"): "jit(_round_jit)/vmap(svm.solve)/while/dot",
              (1, "%fusion.3"): "jit(_round_jit)/mr.merge/top_k",
              (2, "%fusion.2"): "jit(_final_fit_jit)/copy",
              (2, "%while.7"): _scopes.LOOP}
    lines = {
        "XLA Modules": _line("XLA Modules", [("jit__round_jit(1)", 0, 100),
                                             ("jit__final_fit_jit(2)", 200,
                                              50)]),
        "XLA Ops": _line("XLA Ops", [
            ("%while.0", 0, 80), ("%while.1", 2, 40), ("%fusion.2", 10, 5),
            ("%fusion.2", 20, 5), ("%fusion.3", 85, 5),
            ("%while.7", 205, 3), ("%fusion.2", 210, 5),
            ("%fusion.2", 150, 5)]),                  # between programs
    }
    ops = _scopes._named_by_scope(lines, scopes)
    solve = scopes[1, "%fusion.2"]
    assert [e.name for e in ops] == [         # loops: their first body op's
        solve, solve, solve, solve, scopes[1, "%fusion.3"], "",
        scopes[2, "%fusion.2"], ""]
    assert ops[0].start == 0 and ops[0].dur == pytest.approx(80e-9)
    assert _scopes.scope_seconds({"d": ops}, "svm.solve", (0, 1)) == \
        pytest.approx(80e-9)
    assert _scopes.scope_seconds({}, "svm.solve", (0, 1)) == 0.0
