"""Run one cell of the benchmark on the chip it finds.

    python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout that holds ``BENCHMARK.json``, ``bench/``
and the program (``src/repro``). The cell names a configuration, a
traffic mix and the chips it needs; inputs are made on the device from
``--seed``. Set-up (loading, making the inputs, the first fits and
every warm-up) is timed as ``setup_s``; then the window runs for
``--seconds``, with the profiler on where ``--trace 1``. After the
window the peak device memory is read, the service is stopped, and a
sample of the window's folds or fits drawn from the seed is compared
with the plain reference (``bench.compare``).

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics
with ``--trace 0``, its per-layer metrics with ``--trace 1``),
``device``, with ``--trace 1`` ``breakdown``, the set-up's compile
record, the reference's seconds and its closest calls of the top-k merge
(``bench.compare``), and last ``checks``: each compared number beside
its limit, which also close standard error. A
run that finds no TPU, fewer chips than the cell needs, or no program
beside it exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import shutil
import sys
import time
from pathlib import Path

if __package__ in (None, ""):          # run as a file: python3 bench/run.py
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import spec  # noqa: E402


class RunError(RuntimeError):
    pass


class Run:
    """What the metric readers read: ``records`` of the driver, the
    ``setup`` timing, the cell, and the reduced ``trace`` (or None)."""

    def __init__(self, cell, cfg, tr, seconds, records, setup, trace):
        self.cell, self.cfg, self.tr = cell, cfg, tr
        self.seconds, self.records, self.setup = seconds, records, setup
        self.trace = trace


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def enable_cache(root: Path) -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, for every program however short its compile."""
    import jax
    path = str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def devices_for(chips: int, platform: str = "tpu"):
    import jax
    devs = jax.devices()
    if devs[0].platform != platform:
        raise RunError(f"JAX found no {platform.upper()} "
                       f"(platform {devs[0].platform})")
    if len(devs) < chips:
        raise RunError(f"the cell needs {chips} chip(s), JAX sees "
                       f"{len(devs)}")
    return devs[:chips]


class Profile:
    """The profiler over the first steps of the window: from the
    ``bench.open`` mark to the ``bench.close`` mark set at the end of
    the first step (a wave or a fit) that ends ``span`` seconds or more
    into the window. A device records an event per operation, millions
    a second in the solver's loops, so a whole window's trace takes
    minutes to write and read. Device operations and the host's
    annotations only: no Python tracer, no runtime events."""

    def __init__(self, trace_dir: Path, span: float):
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        self.span, self.t0, self.on = span, None, True

    @staticmethod
    def _mark(name: str):
        import jax
        with jax.profiler.TraceAnnotation(name):
            pass

    def open(self):
        self._mark("bench.open")
        self.t0 = time.time()

    def step(self):
        if self.on and time.time() - self.t0 >= self.span:
            self.close()

    def close(self):
        import jax
        if self.on:
            self._mark("bench.close")
            jax.profiler.stop_trace()
            self.on = False


def execute(bench: dict, cell: dict, seed: int, seconds: float,
            trace: bool, root: Path, t_start: float, devices,
            bench_dir: Path = spec.BENCH) -> dict:
    """Set up, run the window, read the peak memory, and collect the
    jobs to compare (which frees the program's state)."""
    import jax
    from bench import check, clock, gen

    cfg = spec.config(cell["config"], bench_dir)
    tr = spec.traffic(cell["traffic"], bench_dir)
    clk = clock.clock()
    c0 = clk.read()
    rows = gen.RowModel(cfg, seed)
    if tr["mode"] == "train":
        from bench.train import TrainRun
        driver = TrainRun(cfg, tr, seed, rows, trace)
    else:
        from bench.serve import ServiceRun
        driver = ServiceRun(cfg, tr, seed, rows, trace)
    with jax.default_device(devices[0]):
        driver.setup()
        setup = clock.since(c0, clk.read())
        setup["setup_s"] = time.time() - t_start
        trace_dir = root / "bench_out" / "trace" / cell["name"]
        profile = None
        if trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            profile = Profile(trace_dir, float(tr.get("trace_s", seconds)))
            driver.after_step = profile.step
        c1 = clk.read()
        try:
            if profile:
                profile.open()
            driver.window(seconds)
        finally:
            if profile:
                profile.close()
        window_compiles = clock.since(c1, clk.read())["compiles"]
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devices)
        records = driver.records()
        if window_compiles:
            raise RunError(f"{window_compiles} program(s) compiled inside "
                           "the window")
        plan = check.collect(driver, records, cfg, tr, seed)
    return {"cfg": cfg, "tr": tr, "setup": setup, "peak": peak,
            "records": records, "plan": plan,
            "trace_dir": trace_dir if trace else None}


def run_cell(bench: dict, cell: dict, seed: int, seconds: float,
             trace: bool, root: Path, t_start: float, devices,
             bench_dir: Path = spec.BENCH) -> dict:
    import jax
    from bench import check, compare
    from bench import trace as trace_lib

    limits = spec.limits(cell["name"], bench_dir)
    ex = execute(bench, cell, seed, seconds, trace, root, t_start, devices,
                 bench_dir)
    gc.collect()
    cfg, plan = ex["cfg"], ex["plan"]
    t_ref = time.time()
    with jax.default_device(devices[0]):
        numbers, calls = check.numbers(plan, cfg)
    t_ref = time.time() - t_ref
    correct, checks = compare.verdict(numbers, limits)
    tr_red = trace_lib.load(str(ex["trace_dir"])) if trace else None
    run = Run(cell, cfg, ex["tr"], seconds, ex["records"], ex["setup"],
              tr_red)
    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in spec.metrics_for(bench, cell["name"], kind):
        v = spec.reader(m["name"], bench_dir)(run)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices), "memory_peak_bytes": int(ex["peak"])}
    out = {"correct": bool(correct), "attempted": int(plan.attempted),
           "failed": int(plan.failed), "metrics": metrics, "device": device}
    if tr_red is not None:
        device["busy_s"] = trace_lib.busy_s(tr_red)
        device["window_s"] = trace_lib.window_s(tr_red)
        out["breakdown"] = {"device_ops": trace_lib.top_ops(tr_red),
                            "idle_gaps": trace_lib.idle_gaps(tr_red)}
    out["setup"] = ex["setup"]
    out["reference"] = dict(calls, seconds=t_ref)
    out["checks"] = checks
    return out


def prepare(root: Path):
    """Make the program importable and point JAX's caches and logs
    inside the checkout; raises ``SpecError`` where the program is
    missing."""
    sys.path.insert(0, str(root / "src"))
    try:
        import repro  # noqa: F401
    except ImportError as e:
        raise spec.SpecError(f"the program is not in this checkout ({e})")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    enable_cache(root)


def main(argv=None) -> int:
    t_start = time.time()
    args = parse(argv)
    root = Path.cwd()
    try:
        bench = spec.load_benchmark(root)
        cell = spec.cell(bench, args.workload)
    except spec.SpecError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    try:
        prepare(root)
    except spec.SpecError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    try:
        devices = devices_for(int(cell["chips"]))
        out = run_cell(bench, cell, args.seed, args.seconds,
                       bool(args.trace), root, t_start, devices)
    except RunError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    for k, c in out["checks"].items():
        print(f"check {k}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
