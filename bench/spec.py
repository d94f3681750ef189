"""What a run is made of, found by name: ``BENCHMARK.json`` at the root
of the checkout, the configuration ``configs/<config>.json``, the
traffic mix ``traffic/<mix>.json``, one reader ``metrics/<metric>.py``
per metric, the limits of the comparison ``limits/<cell>.json`` and the
chips' peaks ``peaks.json``.
A later cell, mix or metric is a new file and a new entry; nothing
here changes."""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent


class SpecError(RuntimeError):
    pass


def _json(path: Path) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise SpecError(f"{path} is missing") from None


def load_benchmark(root: Path) -> dict:
    return _json(root / "BENCHMARK.json")


def cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise SpecError(f"no workload {name!r} in BENCHMARK.json")


def config(name: str, bench_dir: Path = BENCH) -> dict:
    cfg = _json(bench_dir / "configs" / f"{name}.json")
    cfg.setdefault("name", name)
    return cfg


def traffic(name: str, bench_dir: Path = BENCH) -> dict:
    return _json(bench_dir / "traffic" / f"{name}.json")


def limits(cell_name: str, bench_dir: Path = BENCH) -> dict:
    return _json(bench_dir / "limits" / f"{cell_name}.json")


def metrics_for(bench: dict, cell_name: str, kind: str) -> list:
    """The metrics of ``kind`` (``end_to_end`` or ``per_layer``) that a
    cell reports: those that list it, and those that list no cells."""
    return [m for m in bench[kind]
            if cell_name in m.get("workloads", [cell_name])]


def peaks(device_kind: str, bench_dir: Path = BENCH) -> dict:
    """The published peaks of one chip, by JAX's ``device_kind``. A
    device that is not in ``peaks.json`` is an error, not a default."""
    table = _json(bench_dir / "peaks.json")["devices"]
    if device_kind not in table:
        raise SpecError(f"no peaks for device kind {device_kind!r} in "
                        "peaks.json")
    return table[device_kind]


def reader(name: str, bench_dir: Path = BENCH):
    """The ``read(run)`` function of ``metrics/<name>.py``."""
    path = bench_dir / "metrics" / f"{name}.py"
    if not path.exists():
        raise SpecError(f"metric {name!r} has no reader at {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
