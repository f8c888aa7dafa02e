"""Find what a cell names: its entry in ``BENCHMARK.json``, its
configuration and traffic files, and the modules of its reference,
metrics and kernels, each by name under ``bench/``."""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType
from typing import Dict, List

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_json(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str) -> ModuleType:
    """``bench/<kind>/<name>.py`` as a module (names may hold ``.``
    and ``-``, so they are loaded by path, not imported)."""
    path = BENCH / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} module {name!r} at {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name}".replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with everything it names."""
    name: str
    chips: int
    config: Dict
    traffic: Dict
    end_to_end: List[Dict]     # metric entries that this cell reports
    per_layer: List[Dict]

    def metrics(self, trace: bool) -> List[Dict]:
        return self.per_layer if trace else self.end_to_end


def _reported_in(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, benchmark: Dict = None) -> Cell:
    bm = benchmark if benchmark is not None \
        else load_json(ROOT / "BENCHMARK.json")
    entries = {w["name"]: w for w in bm["workloads"]}
    if name not in entries:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(entries)}")
    w = entries[name]
    configs = {c["name"]: c for c in bm["configs"]}
    config = load_json(ROOT / configs[w["config"]]["file"])
    traffic = load_json(BENCH / "traffic" / f"{w['traffic']}.json")
    return Cell(
        name=name, chips=int(w["chips"]), config=config, traffic=traffic,
        end_to_end=[m for m in bm["end_to_end"] if _reported_in(m, name)],
        per_layer=[m for m in bm["per_layer"] if _reported_in(m, name)])
