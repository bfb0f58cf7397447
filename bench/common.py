"""Where the harness finds its data: ``BENCHMARK.json`` at the root of the
checkout, one file per configuration, per cell and per per-layer metric.

Everything is found by the name ``BENCHMARK.json`` gives it, so a later
change adds a cell, a configuration or a metric by adding files.
"""

from __future__ import annotations

import importlib.util
import json
import pathlib

ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def config(name: str) -> dict:
    return load_json(BENCH / "configs" / f"{name}.json")


def workload(name: str) -> dict:
    return load_json(BENCH / "workloads" / f"{name}.json")


def cell_entry(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"no workload {name!r} in BENCHMARK.json")


def cell_metrics(bench: dict, cell: str, kind: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics this cell reports: those
    that list it under ``workloads``, and those with no such list."""
    return [m for m in bench[kind] if cell in m.get("workloads", [cell])]


def load_module(path: pathlib.Path):
    """Import a file by path (metric readers are named after their metric,
    and metric names hold dots)."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def driver(name: str):
    return load_module(BENCH / "traffic" / f"{name}.py")


def layer_reader(metric: str):
    return load_module(BENCH / "layer_metrics" / f"{metric}.py")
