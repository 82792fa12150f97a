"""Find a cell's configuration, traffic mix and metrics by name.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric sits in a file of its own under ``bench/``; this module
resolves the names ``BENCHMARK.json`` gives to those files, and a
configuration's architecture module by the name its file gives.
"""
from __future__ import annotations

import dataclasses
import importlib
import json
import pathlib
from typing import Any, Dict, List

BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


class SpecError(RuntimeError):
    pass


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]          # the configuration file, as run
    traffic: Dict[str, Any]         # the traffic file
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]


def load_benchmark(root: pathlib.Path = ROOT) -> Dict[str, Any]:
    path = root / "BENCHMARK.json"
    if not path.is_file():
        raise SpecError(f"{path} not found")
    return json.loads(path.read_text())


def _load_json(path: pathlib.Path) -> Dict[str, Any]:
    if not path.is_file():
        raise SpecError(f"{path} not found")
    return json.loads(path.read_text())


def traffic_path(name: str) -> pathlib.Path:
    return BENCH_DIR / "traffic" / f"{name}.json"


def metric_path(name: str) -> pathlib.Path:
    return BENCH_DIR / "metrics" / f"{name}.py"


def _applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(workload: str, root: pathlib.Path = ROOT) -> Cell:
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SpecError(f"no workload {workload!r} in BENCHMARK.json; "
                        f"known: {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _load_json(root / configs[w["config"]]["file"])
    return Cell(
        name=workload, chips=int(w["chips"]), config=config,
        traffic=_load_json(traffic_path(w["traffic"])),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, workload)])


def arch_module(config: Dict[str, Any]):
    """The configuration's architecture module, ``bench/arch/<name>.py``,
    by the name its file gives under ``reference``."""
    return importlib.import_module(f"bench.arch.{config['reference']}")


def model_config(config: Dict[str, Any]):
    """The program's ``ModelConfig`` for a configuration file, as its
    architecture module reads it."""
    return arch_module(config).model_config(config)
