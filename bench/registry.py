"""Configurations, cells and per-layer metrics, found by file name.

* ``configs/<name>.json`` — one deployment: the sweep grid's graph fields,
  with its source, what was assumed and what was cut;
* ``cells/<workload>.json`` — one cell: the configuration it runs and its
  traffic (link dynamics, algorithms, rounds), its sample for the check and
  the limits of each number compared;
* ``metrics/<metric>.py`` — one per-layer metric: ``LAYER``, ``UNIT``,
  ``BETTER``, ``SOURCE``, ``MOVES``, ``WORKLOADS`` and ``read(ctx)``, which
  returns the value or None where the run gave it nothing to read.

Adding a cell or a metric is adding a file; nothing here names one.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

HERE = Path(__file__).resolve().parent


def _load(path: Path) -> dict:
    with open(path) as fh:
        return json.load(fh)


def config_names(root: Path = HERE) -> list[str]:
    return sorted(p.stem for p in (root / "configs").glob("*.json"))


def workload_names(root: Path = HERE) -> list[str]:
    return sorted(p.name[:-len(".json")] for p in (root / "cells").glob("*.json"))


def config(name: str, root: Path = HERE) -> dict:
    return _load(root / "configs" / f"{name}.json")


def workload(name: str, root: Path = HERE) -> dict:
    """The cell ``name`` with its configuration under ``"config_data"``."""
    path = root / "cells" / f"{name}.json"
    if not path.is_file():
        raise KeyError(f"no cell file {path.name} (have {workload_names(root)})")
    cell = _load(path)
    cell["name"] = name
    cell["config_data"] = config(cell["config"], root)
    return cell


def metrics(root: Path = HERE) -> dict:
    """{name: module} of every per-layer metric file."""
    out = {}
    for path in sorted((root / "metrics").glob("*.py")):
        if path.name.startswith("_"):
            continue
        name = path.name[:-len(".py")]
        spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        out[name] = mod
    return out


def metrics_for(workload_name: str, root: Path = HERE) -> dict:
    return {k: m for k, m in metrics(root).items() if workload_name in m.WORKLOADS}
