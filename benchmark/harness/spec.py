"""``BENCHMARK.json`` and the files it names, resolved for one cell.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric sits in a file of its own, found by the name in
``BENCHMARK.json``:

- a configuration: ``workloads[].config`` -> ``configs[].file``;
- a traffic mix: ``benchmark/traffic/<traffic>.json``;
- a per-layer metric: ``benchmark/metrics/<name>.json`` names its reader,
  ``benchmark/readers/<reader>.py``;
- a runner kind: the configuration's ``runner`` key,
  ``benchmark/runners/<runner>.py``;
- a plain reference: the configuration's ``reference`` key,
  ``benchmark/references/<reference>.py``.

Adding a cell is adding files and entries; no file that exists is edited.
"""
from __future__ import annotations

import dataclasses
import importlib
import json
import os
from typing import Any, Dict, List

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


class SpecError(ValueError):
    pass


def _load(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]     # the entries this cell reports
    per_layer: List[Dict[str, Any]]      # entry + its metrics/<name>.json


def _reports(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(workload: str, root: str = ROOT) -> Cell:
    bench = _load(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SpecError(f"no workload {workload!r} in BENCHMARK.json "
                        f"(have {sorted(cells)})")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _load(os.path.join(root, configs[w["config"]]["file"]))
    traffic = _load(os.path.join(BENCH_DIR, "traffic",
                                 w["traffic"] + ".json"))
    e2e = [m for m in bench["end_to_end"] if _reports(m, workload)]
    reported = {m["name"] for m in e2e}
    per_layer = []
    for m in bench["per_layer"]:
        if not _reports(m, workload):
            continue
        if "workloads" not in m and m["moves"] not in reported:
            continue
        desc = _load(os.path.join(BENCH_DIR, "metrics",
                                  m["name"] + ".json"))
        per_layer.append({**m, **desc})
    return Cell(workload, int(w["chips"]), w["config"], w["traffic"],
                config, traffic, e2e, per_layer)


def load_plugin(kind: str, name: str):
    """``benchmark/<kind>/<name>.py`` as a module: a runner, a reader or
    a reference joins the registry by being there."""
    if not name.replace("_", "").replace("-", "").isalnum():
        raise SpecError(f"bad {kind} name {name!r}")
    return importlib.import_module(f"benchmark.{kind}.{name}")
