"""Find a cell's pieces by the names that ``BENCHMARK.json`` gives.

Each configuration, traffic mix, per-layer metric and correctness limit is a
file of its own, so a new cell is added by adding files and manifest
entries, never by editing one that is there:

* configuration ``<config>``: the ``file`` its manifest entry names (JSON);
* traffic mix ``<traffic>``: ``bench/traffic/<traffic>.json``;
* per-layer metric ``<metric>``: ``bench/metrics/<metric>.py``, whose
  ``read(run)`` returns a number or ``None``;
* the limits of cell ``<workload>``: ``bench/check/limits/<workload>.json``;
* a configuration's ``kind`` selects ``bench/drivers/<kind>.py`` and its
  ``family`` the plain reference ``bench/check/families/<family>.py``.
"""
from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass
from types import ModuleType

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


_MODULES: dict = {}


def load_module(path: str, name: str) -> ModuleType:
    """Import a file by path (metric names hold dots, so no package import);
    once per process, so that programs compiled for it are found again."""
    if path in _MODULES:
        return _MODULES[path]
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot import {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    _MODULES[path] = mod
    return mod


@dataclass(frozen=True)
class Cell:
    """Everything one workload of the manifest resolves to."""

    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    end_to_end: tuple          # manifest entries reported with --trace 0
    per_layer: tuple           # manifest entries reported with --trace 1
    limits: dict
    root: str

    def driver(self) -> ModuleType:
        kind = self.config["kind"]
        return load_module(
            os.path.join(self.root, "bench", "drivers", f"{kind}.py"),
            f"bench_driver_{kind}",
        )

    def family(self) -> ModuleType:
        fam = self.config["family"]
        return load_module(
            os.path.join(self.root, "bench", "check", "families", f"{fam}.py"),
            f"bench_family_{fam}",
        )

    def metric_reader(self, name: str) -> ModuleType:
        return load_module(
            os.path.join(self.root, "bench", "metrics", f"{name}.py"),
            f"bench_metric_{name.replace('.', '_')}",
        )


def _reports(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(workload: str, root: str = ROOT) -> Cell:
    """Resolve ``workload`` of ``<root>/BENCHMARK.json`` to its files."""
    man = _load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in man["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in man["configs"]}
    centry = configs[w["config"]]
    config = _load_json(os.path.join(root, centry["file"]))
    traffic = _load_json(
        os.path.join(root, "bench", "traffic", f"{w['traffic']}.json")
    )
    limits = _load_json(
        os.path.join(root, "bench", "check", "limits", f"{workload}.json")
    )
    return Cell(
        name=workload,
        chips=int(w["chips"]),
        config_name=w["config"],
        config=config,
        traffic_name=w["traffic"],
        traffic=traffic,
        end_to_end=tuple(m for m in man["end_to_end"] if _reports(m, workload)),
        per_layer=tuple(m for m in man["per_layer"] if _reports(m, workload)),
        limits=limits,
        root=root,
    )
