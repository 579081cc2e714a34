"""Finds what a cell needs by the names in ``BENCHMARK.json``.

A workload names a configuration and a traffic mix; the configuration's
entry gives its file, the traffic mix is ``bench/traffic/<traffic>.json``
and each per-layer metric is read by ``bench/metrics/<metric>.py``, a
module with one function ``read(run) -> float | None``. Adding a cell or
a metric is adding files and entries; nothing here names one.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path


def load_benchmark(root: Path) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def find_cell(root: Path, bench: dict, workload: str):
    """(workload entry, configuration file, traffic file) of a cell."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; have "
                       f"{sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / configs[cell["config"]]["file"]).read_text())
    traffic = json.loads((root / "bench" / "traffic"
                          / f"{cell['traffic']}.json").read_text())
    return cell, config, traffic


def metrics_for(bench: dict, kind: str, workload: str) -> list:
    """Entries of ``end_to_end`` or ``per_layer`` that this cell reports."""
    return [m for m in bench[kind]
            if "workloads" not in m or workload in m["workloads"]]


def reader(root: Path, name: str):
    """The ``read`` function of per-layer metric ``name``."""
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read
