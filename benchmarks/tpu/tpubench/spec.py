"""Finds what ``BENCHMARK.json`` names: configurations, traffic mixes,
per-cell limits and per-layer metric readers, each a file of its own."""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parents[1]


class SpecError(ValueError):
    pass


def _read_json(path: Path) -> dict:
    if not path.is_file():
        raise SpecError(f"{path} is missing")
    return json.loads(path.read_text())


def load_benchmark(root: Path = ROOT) -> dict:
    return _read_json(Path(root) / "BENCHMARK.json")


def workload(bench: dict, name: str) -> dict:
    for wl in bench["workloads"]:
        if wl["name"] == name:
            return wl
    raise SpecError(f"no workload {name!r} in BENCHMARK.json")


def config_entry(bench: dict, name: str) -> dict:
    for cfg in bench["configs"]:
        if cfg["name"] == name:
            return cfg
    raise SpecError(f"no config {name!r} in BENCHMARK.json")


def load_config(bench: dict, name: str, root: Path = ROOT) -> dict:
    """The configuration file named by the ``configs`` entry."""
    return _read_json(Path(root) / config_entry(bench, name)["file"])


def load_traffic(name: str, bench_dir: Path = BENCH_DIR) -> dict:
    return _read_json(Path(bench_dir) / "traffic" / f"{name}.json")


def load_limits(cell: str, bench_dir: Path = BENCH_DIR) -> dict:
    """``{number: limit}`` for the cell's correctness comparison."""
    return _read_json(Path(bench_dir) / "limits" / f"{cell}.json")["limits"]


def load_reader(metric: str, bench_dir: Path = BENCH_DIR):
    """The ``read(ctx)`` function of ``metrics/<metric>.py``."""
    path = Path(bench_dir) / "metrics" / f"{metric}.py"
    if not path.is_file():
        raise SpecError(f"{path} is missing")
    spec = importlib.util.spec_from_file_location(f"tpubench_metric_{metric}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def cell_metrics(bench: dict, cell: str, trace: bool) -> list:
    """The metric entries a run of ``cell`` reports: end-to-end ones with
    ``trace`` off, per-layer ones with it on; an entry with a
    ``workloads`` key applies only to the cells it lists."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]
