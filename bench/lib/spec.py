"""Finding a cell's pieces by name.

``BENCHMARK.json`` names each cell's configuration and traffic mix. Every
piece sits in a file of its own, found by that name:

- a configuration: the ``file`` that ``BENCHMARK.json`` gives for it;
- a traffic mix: ``bench/traffic/<traffic>.json``, whose ``kind`` names
  the runner ``bench/kinds/<kind>.py``;
- a cell's correctness limits: ``bench/limits/<workload>.json``;
- a per-layer metric's reader: ``bench/metrics/<metric>.py``;
- a configuration's model family (its leaves, the program's tree for
  them, its reference layer and its work counts):
  ``bench/models/<model_type>.py``, by the file's ``model_type``.

Adding a cell, a configuration, a mix, a metric or a model family adds
files; nothing here changes.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Any, Dict, List, Optional

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]           # the configuration file, parsed
    traffic: Dict[str, Any]          # the traffic file, parsed
    limits: Dict[str, float]         # compared number -> its limit
    end_to_end: List[Dict[str, Any]]  # BENCHMARK.json entries this cell reports
    per_layer: List[Dict[str, Any]]

    @property
    def kind(self) -> str:
        return self.traffic["kind"]


def load_json(path: Path) -> Any:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path) -> ModuleType:
    """Import one file by path; its name is the file's stem."""
    spec = importlib.util.spec_from_file_location(
        "bench_piece_" + path.stem.replace(".", "_").replace("-", "_"), path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _reports(entry: Dict[str, Any], workload: str) -> bool:
    return "workloads" not in entry or workload in entry["workloads"]


def load_cell(workload: str, bench_json: Optional[Path] = None,
              bench_dir: Path = BENCH) -> Cell:
    """The cell named ``workload`` with its files read."""
    spec = load_json(bench_json or ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; have {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    config = load_json(bench_json.parent / configs[w["config"]]["file"]
                       if bench_json else ROOT / configs[w["config"]]["file"])
    traffic = load_json(bench_dir / "traffic" / f"{w['traffic']}.json")
    limits = load_json(bench_dir / "limits" / f"{workload}.json")
    return Cell(name=workload, chips=int(w["chips"]), config=config,
                traffic=traffic,
                limits={k: float(v["limit"]) for k, v in limits.items()},
                end_to_end=[m for m in spec["end_to_end"]
                            if _reports(m, workload)],
                per_layer=[m for m in spec["per_layer"]
                           if _reports(m, workload)])


def kind_module(kind: str, bench_dir: Path = BENCH) -> ModuleType:
    return load_module(bench_dir / "kinds" / f"{kind}.py")


def metric_reader(name: str, bench_dir: Path = BENCH) -> ModuleType:
    return load_module(bench_dir / "metrics" / f"{name}.py")


def family(c: Dict[str, Any], bench_dir: Path = BENCH) -> ModuleType:
    """The model family of configuration ``c``, by its ``model_type``."""
    return _family(c["model_type"], bench_dir)


@functools.lru_cache(maxsize=None)
def _family(model_type: str, bench_dir: Path) -> ModuleType:
    path = bench_dir / "models" / f"{model_type}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no model family {model_type!r}: "
                                f"{path} does not exist")
    return load_module(path)
