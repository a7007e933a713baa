"""Find a cell's files by name.

A cell (``workloads/<cell>.json``) names a deployment
(``configs/<config>.json``, whose ``reference`` names its plain reference
in ``references/<reference>.py``) and a traffic mix
(``traffic/<mix>.json``).
The deployment's ``kind`` names its driver (``drivers/<kind>.py``), and each
per-layer metric of ``BENCHMARK.json`` has its reader in
``metrics/<metric>.py``. Adding a cell or a metric adds files and entries;
nothing here is edited.
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Any, Dict, List

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def _json(path: Path) -> Dict[str, Any]:
    if not path.is_file():
        raise FileNotFoundError(f"benchmark file {path} is missing")
    with open(path) as f:
        return json.load(f)


def _module(path: Path, name: str) -> ModuleType:
    if not path.is_file():
        raise FileNotFoundError(f"benchmark file {path} is missing")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def benchmark() -> Dict[str, Any]:
    return _json(ROOT / "BENCHMARK.json")


def workload(name: str) -> Dict[str, Any]:
    """The cell with its deployment and traffic mix merged in:
    ``{"name", "config", "traffic", "chips", "mesh", "axes", "why",
    "deployment": {...}, "mix": {...}}``."""
    cell = _json(BENCH / "workloads" / f"{name}.json")
    cell["name"] = name
    cell["deployment"] = _json(BENCH / "configs" / f"{cell['config']}.json")
    cell["mix"] = _json(BENCH / "traffic" / f"{cell['traffic']}.json")
    return cell


def driver(kind: str) -> ModuleType:
    return _module(BENCH / "drivers" / f"{kind}.py", f"bench_driver_{kind}")


def reference(config: str) -> ModuleType:
    return _module(BENCH / "references" / f"{config}.py",
                   f"bench_reference_{config}")


def metric_reader(name: str) -> ModuleType:
    return _module(BENCH / "metrics" / f"{name}.py",
                   "bench_metric_" + name.replace(".", "_"))


def applies(metric: Dict[str, Any], cell: str,
            end_to_end: List[Dict[str, Any]]) -> bool:
    """Whether ``metric`` is reported in ``cell``: listed under its
    ``workloads``, or, without that key, wherever the end-to-end metric it
    moves is reported."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    moves = metric.get("moves")
    if moves is None:                       # an end-to-end metric
        return True
    return any(m["name"] == moves and applies(m, cell, end_to_end)
               for m in end_to_end)


def peaks(device_kind: str) -> Dict[str, Any]:
    """The chip's published peaks; an unknown device is an error."""
    table = _json(BENCH / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json (known: {sorted(table)})")
    return table[device_kind]
