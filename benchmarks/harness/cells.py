"""Resolve a cell by name: ``BENCHMARK.json`` names a configuration and a
traffic mix, which are files; per-layer metrics are reader files.  Adding a
cell is adding files and one ``workloads`` entry: nothing here changes."""
from __future__ import annotations

import importlib
import json
import os
from dataclasses import dataclass
from types import ModuleType

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO_ROOT = os.path.dirname(BENCH_DIR)


def load_benchmark(path: str = "") -> dict:
    with open(path or os.path.join(REPO_ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _load_json(kind: str, name: str) -> dict:
    path = os.path.join(BENCH_DIR, kind, f"{name}.json")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind} file for {name!r}: expected {path}")
    with open(path) as f:
        return json.load(f)


def load_config(name: str) -> dict:
    return _load_json("configs", name)


def load_traffic(name: str) -> dict:
    return _load_json("traffic", name)


def load_family(name: str) -> ModuleType:
    if not os.path.isfile(os.path.join(BENCH_DIR, "families", f"{name}.py")):
        raise FileNotFoundError(f"no family builder benchmarks/families/{name}.py")
    return importlib.import_module(f"benchmarks.families.{name}")


def load_reader(metric: str) -> ModuleType:
    """The reader of one per-layer metric: ``layer_metrics/<name>.py`` with
    LAYER, UNIT, BETTER, SOURCE, MOVES and ``read(run) -> float | None``."""
    if not os.path.isfile(os.path.join(BENCH_DIR, "layer_metrics", f"{metric}.py")):
        raise FileNotFoundError(f"no reader benchmarks/layer_metrics/{metric}.py")
    mod = importlib.import_module(f"benchmarks.layer_metrics.{metric}")
    for attr in ("LAYER", "UNIT", "BETTER", "SOURCE", "MOVES", "read"):
        if not hasattr(mod, attr):
            raise AttributeError(f"reader {metric} lacks {attr}")
    return mod


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    family: ModuleType
    end_to_end: list[dict]  # the BENCHMARK.json entries this cell reports
    per_layer: list[dict]
    readers: dict[str, ModuleType]


def resolve(workload: str, bench: dict | None = None) -> Cell:
    bench = bench or load_benchmark()
    rows = [w for w in bench["workloads"] if w["name"] == workload]
    if not rows:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(have {[w['name'] for w in bench['workloads']]})")
    row = rows[0]
    config = load_config(row["config"])
    traffic = load_traffic(row["traffic"])

    def mine(metric: dict) -> bool:
        return "workloads" not in metric or workload in metric["workloads"]

    per_layer = [m for m in bench["per_layer"] if mine(m)]
    return Cell(
        name=workload, chips=int(row["chips"]), config=config, traffic=traffic,
        family=load_family(config["family"]),
        end_to_end=[m for m in bench["end_to_end"] if mine(m)],
        per_layer=per_layer,
        readers={m["name"]: load_reader(m["name"]) for m in per_layer},
    )
