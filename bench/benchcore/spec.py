"""Resolve one cell of `BENCHMARK.json` into what a run needs.

A cell names a configuration and a traffic mix. Each is a data file found
by name: `configs/<config>.json` (the file that `BENCHMARK.json` lists for
the configuration; its `model` is the source's config, and
`departures.model` the numbers the program runs in their place, which
resolving applies) and `traffic/<mix>.json`. An optional
`cells/<cell name>.json` holds what belongs to the cell alone: overrides of
the deployment (`"deployment"`) and of the traffic parameters
(`"traffic"`), and the limits of the correctness comparison
(`"limits"`). Per-layer metrics are readers `metrics/<metric>.py`.
The configuration file names its model family (`"family"`), whose file
`families/<family>.py` holds what the harness knows of the model's
structure (the contract is in `family.py`). A cell is added by adding
data, and a model of a new family by adding its family file; no file
here changes.
"""
from __future__ import annotations

import copy
import importlib.util
import json
from dataclasses import dataclass, field
from pathlib import Path
from types import ModuleType
from typing import Callable, Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


def load_benchmark(path: Optional[Path] = None) -> dict:
    return json.loads(Path(path or ROOT / "BENCHMARK.json").read_text())


def _merge(base: dict, over: dict) -> dict:
    """Recursive dict merge: `over` wins, nested dicts merge key by key."""
    out = copy.deepcopy(base)
    for k, v in over.items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = _merge(out[k], v)
        else:
            out[k] = copy.deepcopy(v)
    return out


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict                 # the configuration file, deployment merged
    family: ModuleType           # families/<config["family"]>.py
    traffic_name: str
    traffic: dict                # the mix file, cell overrides merged
    limits: Dict[str, float]
    end_to_end: List[dict]
    per_layer: List[dict]
    readers: Dict[str, Callable] = field(default_factory=dict)

    @property
    def deployment(self) -> dict:
        return self.config["deployment"]


def _load_file(path: Path, module_name: str):
    spec = importlib.util.spec_from_file_location(
        module_name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(name: str, bench_dir: Path = BENCH_DIR) -> Callable:
    """`metrics/<name>.py`'s `read(run) -> float | None`."""
    return _load_file(bench_dir / "metrics" / f"{name}.py",
                      f"bench_metric_{name}").read


def load_family(name: str, bench_dir: Path = BENCH_DIR) -> ModuleType:
    """`families/<name>.py`, the model family (contract: `family.py`)."""
    path = Path(bench_dir) / "families" / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no model family {name!r}: {path}")
    return _load_file(path, f"bench_family_{name}")


def resolve(workload: str, benchmark: Optional[dict] = None,
            bench_dir: Path = BENCH_DIR, root: Path = ROOT) -> Cell:
    bm = benchmark if benchmark is not None else load_benchmark()
    cells = {w["name"]: w for w in bm["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    w = cells[workload]
    cfg_entry = {c["name"]: c for c in bm["configs"]}[w["config"]]
    config = json.loads((root / cfg_entry["file"]).read_text())
    if "family" not in config:
        raise KeyError(f"configuration {cfg_entry['file']} names no model "
                       f"family (\"family\")")
    # `model` holds the source's numbers; where the program runs others
    # (`departures`), those are what it is checked against and the
    # reference computes with
    config["model"] = {**config["model"],
                       **config.get("departures", {}).get("model", {})}
    traffic = json.loads(
        (bench_dir / "traffic" / f"{w['traffic']}.json").read_text())
    cell_file = bench_dir / "cells" / f"{workload}.json"
    over = json.loads(cell_file.read_text()) if cell_file.exists() else {}
    config = _merge(config, {"deployment": over.get("deployment", {})})
    traffic = _merge(traffic, over.get("traffic", {}))
    per_layer = [m for m in bm["per_layer"] if _applies(m, workload)]
    return Cell(
        name=workload, chips=int(w["chips"]),
        config_name=w["config"], config=config,
        family=load_family(config["family"], bench_dir),
        traffic_name=w["traffic"], traffic=traffic,
        limits=dict(over.get("limits", {})),
        end_to_end=[m for m in bm["end_to_end"] if _applies(m, workload)],
        per_layer=per_layer,
        readers={m["name"]: load_reader(m["name"], bench_dir)
                 for m in per_layer})
