"""Reduce a profiler trace to the numbers the per-layer metrics read.

The JAX profiler writes ``<dir>/plugins/profile/<time>/*.xplane.pb``.
Device planes are named ``/device:TPU:<n>``; on each, the line
``XLA Modules`` holds one event per execution of a compiled program,
named after its jit (``jit__sweep_round_jit(…)``), and ``XLA Ops`` one
event per operation. Host threads are lines of ``/host:CPU``; the
harness marks its own steps there with ``bench.*`` annotations.

* busy: the union of the operation intervals on a device (of the
  module intervals where a device has no op line), averaged over the
  devices used;
* program time: the summed device durations of one program's module
  events, keyed by the jit name without its numeric suffix;
* breakdown: the ten operations that took most device time, and the
  ten longest idle gaps, each named by the host event that overlaps it
  most (the innermost, where several overlap alike).
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, NamedTuple, Tuple

_SUFFIX = re.compile(r"\(\d+\)$")


class Event(NamedTuple):
    name: str
    start: float        # seconds, on the trace's clock
    dur: float


class Trace(NamedTuple):
    devices: Dict[str, Dict[str, List[Event]]]   # plane -> line -> events
    host: List[Event]
    window: Tuple[float, float]                  # the traced window


def program_name(event_name: str) -> str:
    """``jit__sweep_round_jit(3)`` → ``jit__sweep_round_jit``."""
    return _SUFFIX.sub("", event_name.strip())


def load(trace_dir: str) -> Trace:
    """Read the newest ``.xplane.pb`` under ``trace_dir``. The traced
    window runs from the harness's ``bench.open`` mark to its
    ``bench.close`` mark (the harness stops the profiler at the first
    step that ends ``trace_s`` into the window); in a trace without
    them, it is the ``bench.window`` annotation."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return from_profile(ProfileData.from_file(paths[-1]))


def from_profile(pd) -> Trace:
    devices, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            lines = {}
            for line in plane.lines:
                if line.name in ("XLA Ops", "XLA Modules"):
                    lines[line.name] = [
                        Event(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                        for e in line.events]
            if lines:
                devices[plane.name] = lines
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                host.extend(Event(e.name, e.start_ns * 1e-9,
                                  e.duration_ns * 1e-9) for e in line.events)
    return from_events(devices, host)


def from_events(devices, host) -> Trace:
    opened = [e.start for e in host if e.name == "bench.open"]
    closed = [e.start for e in host if e.name == "bench.close"]
    if opened and closed:
        return Trace(devices, list(host), (min(opened), max(closed)))
    marks = [e for e in host if e.name == "bench.window"]
    if not marks:
        raise ValueError("the trace has no bench.window annotation")
    return Trace(devices, list(host),
                 (marks[0].start, marks[0].start + marks[0].dur))


def _clip(events: Iterable[Event], lo: float, hi: float):
    for e in events:
        s, t = max(e.start, lo), min(e.start + e.dur, hi)
        if t > s:
            yield s, t


def union(intervals) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, t in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], t)
        else:
            out.append([s, t])
    return [(s, t) for s, t in out]


def _busy_intervals(lines) -> List[Tuple[float, float]]:
    events = lines.get("XLA Ops") or lines.get("XLA Modules") or []
    return events


def busy_s(tr: Trace) -> float:
    """Seconds of the window in which an operation ran, averaged over
    the devices in the trace."""
    lo, hi = tr.window
    if not tr.devices:
        return 0.0
    tot = 0.0
    for lines in tr.devices.values():
        tot += sum(t - s for s, t in union(_clip(_busy_intervals(lines),
                                                 lo, hi)))
    return tot / len(tr.devices)


def window_s(tr: Trace) -> float:
    return tr.window[1] - tr.window[0]


def program_seconds(tr: Trace) -> Dict[str, float]:
    """Device seconds per program inside the window, summed over the
    devices and divided by their number."""
    lo, hi = tr.window
    out: Dict[str, float] = {}
    for lines in tr.devices.values():
        for e in lines.get("XLA Modules", []):
            for s, t in _clip([e], lo, hi):
                k = program_name(e.name)
                out[k] = out.get(k, 0.0) + (t - s)
    n = max(len(tr.devices), 1)
    return {k: v / n for k, v in out.items()}


def program_runs(tr: Trace) -> Dict[str, int]:
    """Executions per program that started inside the window (on the
    first device)."""
    lo, hi = tr.window
    out: Dict[str, int] = {}
    for lines in list(tr.devices.values())[:1]:
        for e in lines.get("XLA Modules", []):
            if lo <= e.start < hi:
                k = program_name(e.name)
                out[k] = out.get(k, 0) + 1
    return out


def top_ops(tr: Trace, k: int = 10) -> List[List]:
    lo, hi = tr.window
    tot: Dict[str, float] = {}
    for lines in tr.devices.values():
        for e in lines.get("XLA Ops", []):
            for s, t in _clip([e], lo, hi):
                tot[e.name] = tot.get(e.name, 0.0) + (t - s)
    n = max(len(tr.devices), 1)
    return [[name, v / n] for name, v in
            sorted(tot.items(), key=lambda kv: -kv[1])[:k]]


def idle_gaps(tr: Trace, k: int = 10) -> List[List]:
    """The ``k`` longest gaps on the first device, each named by the
    host event that overlaps it most."""
    lo, hi = tr.window
    if not tr.devices:
        return []
    lines = next(iter(tr.devices.values()))
    busy = union(_clip(_busy_intervals(lines), lo, hi))
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:k]
    host = [e for e in tr.host
            if e.name not in ("bench.window", "bench.open", "bench.close")]
    out = []
    for s, t in gaps:
        best, key = "host idle", (0.0, 0.0)
        for e in host:
            ov = min(t, e.start + e.dur) - max(s, e.start)
            if ov > 0 and (ov, -e.dur) > key:
                best, key = e.name, (ov, -e.dur)
        out.append([best, t - s])
    return out
