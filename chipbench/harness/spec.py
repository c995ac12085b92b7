"""Find a cell's configuration, traffic mix and metric readers by name."""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


def load_benchmark(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _by_name(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def config_file(name: str, bench_dir: Path = BENCH_DIR) -> Path:
    return bench_dir / "configs" / f"{name}.json"


def traffic_file(name: str, bench_dir: Path = BENCH_DIR) -> Path:
    return bench_dir / "traffic" / f"{name}.json"


def metric_file(name: str, bench_dir: Path = BENCH_DIR) -> Path:
    return bench_dir / "metrics" / f"{name}.py"


def load_metric_reader(name: str, bench_dir: Path = BENCH_DIR):
    """The ``read(run)`` function of ``metrics/<name>.py``."""
    path = metric_file(name, bench_dir)
    spec = importlib.util.spec_from_file_location(f"chipbench_metric_{name}", path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class Cell:
    """One workload of ``BENCHMARK.json`` with everything it names."""

    def __init__(self, bench: dict, workload: str, bench_dir: Path = BENCH_DIR):
        self.workload = _by_name(bench["workloads"], workload, "workload")
        self.name = workload
        self.chips = int(self.workload["chips"])
        cfg_entry = _by_name(bench["configs"], self.workload["config"], "config")
        self.config = load_json(bench_dir.parent / cfg_entry["file"])
        self.traffic = load_json(traffic_file(self.workload["traffic"], bench_dir))
        self.end_to_end = [
            m for m in bench["end_to_end"] if workload in m.get("workloads", [workload])
        ]
        # every per-layer metric lists the cells it is read in
        self.per_layer = [m for m in bench["per_layer"] if workload in m["workloads"]]
        self.bench_dir = bench_dir
