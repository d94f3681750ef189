"""Readings for the limits of the comparison, on the chip at a cell's
own size, all in one process. For every seed of ``--seeds``: a run of
the cell (set-up and a short window) and the numbers its compared jobs
give for the program; for each seed of ``--control-seeds`` also those of
the control — the reference in the program's place, computed in
bfloat16, the precision below the configuration's float32 solver state;
and for each seed of ``--fault-seeds`` a run with each of the cell's
faults (``bench.faults``) planted in the program. Every set of numbers
is judged against the cell's committed limits (``compare.verdict``),
as a run of the benchmark judges it.

    python3 -m bench.control --workload <cell> --seconds 6 \\
        --seeds 1,2,3 --control-seeds 1,2,3 --fault-seeds 4,5,6

Prints one JSON line per reading: ``{"seed", "who", "numbers",
"correct", "checks"}``, ``who`` being ``program``, ``control`` or the
fault's name. The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import json
import sys
import time
from pathlib import Path
from unittest import mock

if __package__ in (None, ""):
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import run as run_lib, spec  # noqa: E402


def _seeds(text: str):
    return [int(s) for s in text.split(",") if s]


def _planted(fault):
    stack = contextlib.ExitStack()
    if fault is not None:
        for target, attr, value in fault():
            stack.enter_context(mock.patch.object(target, attr, value))
    return stack


def readings(bench, cell, seed, seconds, root, devices, who="program",
             fault=None, bench_dir=spec.BENCH):
    """The readings of one run of ``cell``: the program's (with
    ``fault`` planted, if any) and, for ``who == "control"``, the
    control's on the same jobs."""
    import jax
    import jax.numpy as jnp
    from bench import check, compare
    limits = spec.limits(cell["name"], bench_dir)
    t0 = time.time()
    with _planted(fault):
        ex = run_lib.execute(bench, cell, seed, seconds, False, root, t0,
                             devices, bench_dir)
    gc.collect()
    out = []
    with jax.default_device(devices[0]):
        nums, calls = check.numbers(ex["plan"], ex["cfg"])
        out.append(("program" if fault is None else fault.__name__, nums))
        if who == "control":
            out.append(("control", check.control_numbers(
                ex["plan"], ex["cfg"], jnp.bfloat16)))
    lines = []
    for name, nums in out:
        ok, checks = compare.verdict(nums, limits)
        lines.append(dict({"seed": seed, "who": name, "numbers": nums,
                           "correct": ok, "checks": checks,
                           "seconds": time.time() - t0}, **calls))
    return lines


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--fault-seeds", default="")
    args = ap.parse_args(argv)
    root = Path.cwd()
    bench = spec.load_benchmark(root)
    cell = spec.cell(bench, args.workload)
    run_lib.prepare(root)
    from bench import faults
    devices = run_lib.devices_for(int(cell["chips"]))
    ctrl = set(_seeds(args.control_seeds))
    for seed in sorted(set(_seeds(args.seeds)) | ctrl):
        who = "control" if seed in ctrl else "program"
        for line in readings(bench, cell, seed, args.seconds, root, devices,
                             who):
            print(json.dumps(line), flush=True)
    mode = spec.traffic(cell["traffic"])["mode"]
    for seed in _seeds(args.fault_seeds):
        for fault in faults.BY_MODE[mode]:
            for line in readings(bench, cell, seed, args.seconds, root,
                                 devices, fault=fault):
                print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
