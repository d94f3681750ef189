"""Device operations by the scope path they were traced under.

The reduced trace (``bench.trace``) keeps event names only, and
``jax.profiler.ProfileData`` gives a device op event its times alone.
The program's ``jax.named_scope`` names reach each op's ``op_name``
metadata, which the profiler keeps as the ``tf_op`` stat of the op's
event *metadata* (on the TPU v5 lite), beside its ``program_id``. So
this reads the newest ``.xplane.pb`` under
``<checkout>/bench_out/trace/<cell>`` (where ``bench.run`` writes it)
once per run: the device planes' event metadata straight from the
file's protobuf fields, the events through ``ProfileData``. An op event
is joined to its metadata by its program (the ``XLA Modules`` event
that holds it, whose name ends in the program id) and its name, which
is the metadata's name.

The v5e gives a ``while`` op (``hlo_category`` ``while``) no ``tf_op``.
Its interval holds its body's ops, and the loop's own time between them
(the condition, the step) is the loop's: so a loop takes the scope of
the first op it runs."""
from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, List, Tuple

from bench import trace as trace_lib

STAT = "tf_op"
LOOP = None                     # the scope of a loop op, found from its body
_PROGRAM = re.compile(r"\((\d+)\)$")

# Field numbers of tsl/profiler/protobuf/xplane.proto.
_SPACE_PLANES = 1
_PLANE_NAME, _PLANE_EVENT_META, _PLANE_STAT_META = 2, 4, 5
_ENTRY_KEY, _ENTRY_VALUE = 1, 2                # of a map<int64, message>
_META_NAME, _META_STATS = 2, 5                 # XEventMetadata, XStatMetadata
_STAT_META_ID, _STAT_STR, _STAT_REF = 1, 5, 7
_STAT_INTS = (3, 4)                            # uint64_value, int64_value


def device_ops(run, checkout) -> Dict[str, List[trace_lib.Event]]:
    """Each device plane's ``XLA Ops`` events, named by their scope
    path ("" where the trace holds none), kept on the run."""
    cached = getattr(run, "scoped_ops", None)
    if cached is not None:
        return cached
    out: Dict[str, List[trace_lib.Event]] = {}
    paths = glob.glob(os.path.join(str(checkout), "bench_out", "trace",
                                   run.cell["name"], "**", "*.xplane.pb"),
                      recursive=True)
    if paths:
        from jax.profiler import ProfileData
        path = max(paths, key=os.path.getmtime)
        with open(path, "rb") as f:
            scopes = op_scopes(f.read())
        for plane in ProfileData.from_file(path).planes:
            if plane.name in scopes:
                lines = {ln.name: ln for ln in plane.lines}
                if "XLA Ops" in lines:
                    out[plane.name] = _named_by_scope(lines,
                                                      scopes[plane.name])
    run.scoped_ops = out
    return out


def _named_by_scope(lines, scopes) -> List[trace_lib.Event]:
    mods = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                  for e in (lines["XLA Modules"].events
                            if "XLA Modules" in lines else ()))
    starts = [m[0] for m in mods]
    ids = [int(m.group(1)) if m else None
           for m in (_PROGRAM.search(n) for _, _, n in mods)]
    ops = []
    for e in lines["XLA Ops"].events:
        s = e.start_ns
        i = bisect.bisect_right(starts, s) - 1     # programs never overlap
        pid = ids[i] if i >= 0 and s < mods[i][1] else None
        ops.append(trace_lib.Event(scopes.get((pid, e.name), ""),
                                   s * 1e-9, e.duration_ns * 1e-9))
    return loops_take_their_body_scope(ops)


def loops_take_their_body_scope(ops: List[trace_lib.Event]):
    """Each ``LOOP`` op (in start order) named by the first scoped op
    that starts inside it; "" where none does."""
    out = list(ops)
    for i, e in enumerate(out):
        if e.name is not LOOP:
            continue
        path, end, j = "", e.start + e.dur, i + 1
        while j < len(out) and out[j].start < end:
            if out[j].name:
                path = out[j].name
                break
            j += 1
        out[i] = e._replace(name=path)
    return out


def scope_seconds(ops: Dict[str, List[trace_lib.Event]], scope: str,
                  window) -> float:
    """Seconds of ``window`` in which an op whose scope path contains
    ``scope`` ran, averaged over the devices: the union of their
    intervals, since a ``while`` op's interval holds its body's ops."""
    lo, hi = window
    if not ops:
        return 0.0
    tot = 0.0
    for evs in ops.values():
        spans = [(max(e.start, lo), min(e.start + e.dur, hi))
                 for e in evs if scope in e.name]
        tot += sum(t - s for s, t in trace_lib.union(
            (s, t) for s, t in spans if t > s))
    return tot / len(ops)


# -- the trace file's device-plane event metadata ---------------------------

def op_scopes(data: bytes) -> Dict[str, Dict[Tuple[int, str], str]]:
    """``{device plane: {(program id, op name): scope path}}`` from a
    serialized ``XSpace``: each event metadata's name, its
    ``program_id`` stat and its ``tf_op`` stat (a string, or a
    reference to a stat metadata whose name is the string); ``LOOP``
    for a ``while`` op, which has none."""
    out = {}
    for num, plane in _fields(data, 0, len(data)):
        if num != _SPACE_PLANES:
            continue
        name, metas, stat_names = "", [], {}
        for f, v in _fields(data, *plane):
            if f == _PLANE_NAME:
                name = _str(data, v)
            elif f == _PLANE_EVENT_META:
                metas.append(_entry(data, v)[1])
            elif f == _PLANE_STAT_META:
                sid, body = _entry(data, v)
                stat_names[sid] = next((_str(data, x) for g, x in
                                        _fields(data, *body)
                                        if g == _META_NAME), "")
        if not name.startswith("/device:"):
            continue
        ids = {v: k for k, v in stat_names.items()}
        scope_id, pid_id, cat_id = (ids.get(k, -1) for k in
                                    (STAT, "program_id", "hlo_category"))
        table = {}
        for body in metas:
            op, pid, path, loop = "", None, "", False
            for f, v in _fields(data, *body):
                if f == _META_NAME:
                    op = _str(data, v)
                elif f == _META_STATS:
                    mid, num_v, str_v, ref = _stat(data, v)
                    text = str_v if str_v is not None else \
                        stat_names.get(ref, "")
                    if mid == scope_id:
                        path = text
                    elif mid == pid_id:
                        pid = num_v
                    elif mid == cat_id:
                        loop = text == "while"
            if path or loop:
                table[(pid, op)] = path or LOOP
        out[name] = table
    return out


def _varint(buf, i):
    v = shift = 0
    while True:
        c = buf[i]
        i += 1
        v |= (c & 0x7F) << shift
        if c < 0x80:
            return v, i
        shift += 7


def _fields(buf, i, end):
    """``(field number, value)`` of each field of the message
    ``buf[i:end]``: an int for a varint, a ``(start, end)`` span for a
    length-delimited field; fixed-width fields are skipped."""
    while i < end:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(buf, i)
            yield num, v
        elif wire == 2:
            n, i = _varint(buf, i)
            yield num, (i, i + n)
            i += n
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")


def _str(buf, span) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _entry(buf, span):
    """The key and the value's span of a ``map<int64, message>`` entry."""
    key, val = None, (0, 0)
    for f, v in _fields(buf, *span):
        if f == _ENTRY_KEY:
            key = v
        elif f == _ENTRY_VALUE:
            val = v
    return key, val


def _stat(buf, span):
    """``(metadata id, integer value, string value, reference)`` of an
    ``XStat``."""
    mid = num_v = str_v = ref = None
    for f, v in _fields(buf, *span):
        if f == _STAT_META_ID:
            mid = v
        elif f in _STAT_INTS:
            num_v = v
        elif f == _STAT_STR:
            str_v = _str(buf, v)
        elif f == _STAT_REF:
            ref = v
    return mid, num_v, str_v, ref
