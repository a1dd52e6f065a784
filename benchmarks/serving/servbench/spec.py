"""Where a cell's pieces live, and how they are found by name.

``BENCHMARK.json`` at the checkout's root names each cell's configuration
and traffic mix, and each metric.  Everything that belongs to one of them is
a file of its own under ``benchmarks/serving``:

* ``configs/<config>.json``      model sizes, cache ratio, pool, and the
                                 ``"model"`` module that builds it;
* ``servbench/models/<model>.py`` one module per architecture (its contract
                                 is in ``servbench/models/__init__.py``);
* ``traffic/<traffic>.json``     loop, lengths, sharing, scheduler settings;
* ``checks/<cell>.json``         the cell's correctness limit and sample;
* ``layer_metrics/<name>.py``    one reader per per-layer metric;
* ``peaks.json``                 the chip's peaks, keyed by ``device_kind``.

Adding a cell adds files and entries; no existing file changes.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
import re
from pathlib import Path
from typing import Any, Dict, List

HERE = Path(__file__).resolve().parents[1]          # benchmarks/serving
ROOT = HERE.parents[1]                              # the checkout
MODULE_NAME = re.compile(r"^[A-Za-z_][A-Za-z0-9_]*$")


def load_json(path: Path) -> Any:
    with open(path) as f:
        return json.load(f)


def benchmark() -> Dict[str, Any]:
    return load_json(ROOT / "BENCHMARK.json")


def workload(bench: Dict[str, Any], name: str) -> Dict[str, Any]:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config(name: str) -> Dict[str, Any]:
    return load_json(HERE / "configs" / f"{name}.json")


def traffic(name: str) -> Dict[str, Any]:
    return load_json(HERE / "traffic" / f"{name}.json")


def peaks(device_kind: str) -> Dict[str, Any]:
    table = load_json(HERE / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in peaks.json")
    return table[device_kind]


def metrics_for(bench: Dict[str, Any], section: str, cell: str) -> List[Dict]:
    """The metrics of ``section`` ("end_to_end" or "per_layer") that cell
    ``cell`` reports: those listing it, or listing no cells at all."""
    return [m for m in bench[section]
            if cell in m.get("workloads", [w["name"] for w in bench["workloads"]])]


def reader(metric: str):
    """The ``read(ctx)`` function of ``layer_metrics/<metric>.py``."""
    path = HERE / "layer_metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        "layer_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def model(name: str):
    """The model module ``servbench/models/<name>.py``."""
    if not MODULE_NAME.match(name):
        raise ValueError(f"model module name {name!r} is not an identifier")
    return importlib.import_module(f"servbench.models.{name}")
