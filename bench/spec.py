"""Loads ``BENCHMARK.json`` and finds everything of one cell by name.

A cell names a configuration (``bench/configs/<config>.json``, via the
``file`` of its entry) and a traffic mix (``bench/traffic/<traffic>.json``),
whose ``kind`` names the generator ``bench/traffic/<kind>.py``.  A
per-layer metric ``<name>`` is read by ``bench/layer_metrics/<name>.py``.
Adding a cell, a configuration, a mix or a metric is adding files and
entries: nothing here lists them.
"""
from __future__ import annotations

import importlib.util
import json
import re
from dataclasses import dataclass, field
from pathlib import Path
from types import ModuleType
from typing import Dict, List, Optional

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


class SpecError(ValueError):
    """BENCHMARK.json or a file it names is missing or malformed."""


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    readers: Dict[str, ModuleType] = field(default_factory=dict)


def check_name(name, what: str) -> str:
    if not isinstance(name, str) or not NAME_RE.match(name):
        raise SpecError(f"{what} {name!r} is not a valid name")
    return name


def check_unit(unit, what: str) -> str:
    if not isinstance(unit, str) or not UNIT_RE.match(unit):
        raise SpecError(f"unit {unit!r} of {what} is not a valid unit")
    return unit


def load_json(path: Path) -> dict:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError:
        raise SpecError(f"missing file {path}") from None
    except json.JSONDecodeError as e:
        raise SpecError(f"{path}: {e}") from None


def load_module(path: Path) -> ModuleType:
    """Import one file by path (names may hold dots, so no import name)."""
    if not path.is_file():
        raise SpecError(f"missing file {path}")
    mod_name = "bench_dyn_" + re.sub(r"\W", "_", str(path))
    spec = importlib.util.spec_from_file_location(mod_name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def metric_applies(metric: dict, cell_name: str) -> bool:
    cells = metric.get("workloads")
    return cells is None or cell_name in cells


def load_cell(root: Path, cell_name: str,
              bench_dir: Optional[Path] = None) -> Cell:
    """The cell ``cell_name`` of ``<root>/BENCHMARK.json`` with its
    configuration, its traffic mix and the readers of its per-layer
    metrics.  ``bench_dir`` is where traffic mixes, generators and
    readers live (``<root>/bench`` by default)."""
    bench_dir = Path(bench_dir) if bench_dir else Path(root) / "bench"
    doc = load_json(Path(root) / "BENCHMARK.json")
    for m in doc.get("end_to_end", []) + doc.get("per_layer", []):
        check_name(m.get("name"), "metric")
        check_unit(m.get("unit"), m.get("name"))
    cells = {check_name(w.get("name"), "workload"): w
             for w in doc.get("workloads", [])}
    if cell_name not in cells:
        raise SpecError(f"no workload {cell_name!r} in BENCHMARK.json "
                        f"(have {sorted(cells)})")
    w = cells[cell_name]
    configs = {check_name(c.get("name"), "config"): c
               for c in doc.get("configs", [])}
    config_name = check_name(w.get("config"), "config")
    if config_name not in configs:
        raise SpecError(f"workload {cell_name!r} names unknown config "
                        f"{config_name!r}")
    config = load_json(Path(root) / configs[config_name]["file"])
    traffic_name = check_name(w.get("traffic"), "traffic")
    traffic = load_json(bench_dir / "traffic" / f"{traffic_name}.json")
    check_name(traffic.get("kind"), "traffic kind")
    chips = int(w.get("chips", 1))
    e2e = [m for m in doc["end_to_end"] if metric_applies(m, cell_name)]
    per_layer = [m for m in doc.get("per_layer", [])
                 if metric_applies(m, cell_name)]
    cell = Cell(cell_name, chips, config_name, config, traffic_name,
                traffic, e2e, per_layer)
    for m in per_layer:
        cell.readers[m["name"]] = load_module(
            bench_dir / "layer_metrics" / f"{m['name']}.py")
    return cell


def load_generator(cell: Cell, bench_dir: Path) -> ModuleType:
    return load_module(Path(bench_dir) / "traffic" / f"{cell.traffic['kind']}.py")
