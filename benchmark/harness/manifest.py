"""Finds a cell's files by the names in ``BENCHMARK.json``.

A cell is ``workloads/<cell>.json`` (generator + parameters) over
``configs/<config>.json`` (the deployment).  A metric is
``end_to_end/<name>.json`` or ``layers/<name>.json`` (a reader + its
arguments).  Generators, value kinds, readers and deployments are named
in those files by dotted path (``harness.loadgen.FuturesOpen``), so a
later PR adds a cell, a configuration, a metric or the code one of them
needs as new files plus new ``BENCHMARK.json`` entries; no file here is
edited.
"""
from __future__ import annotations

import importlib
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO_DIR = os.path.dirname(BENCH_DIR)


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def resolve(dotted: str):
    """``package.module.name`` -> the object, as a configuration's
    ``state_machine`` has always been found."""
    mod, _, name = dotted.rpartition(".")
    return getattr(importlib.import_module(mod), name)


class Manifest:
    """``BENCHMARK.json`` plus the data files it names."""

    def __init__(self, repo_dir: str = REPO_DIR, bench_dir: str | None = None):
        self.repo_dir = repo_dir
        self.bench_dir = bench_dir or os.path.join(repo_dir, "benchmark")
        self.doc = _load(os.path.join(repo_dir, "BENCHMARK.json"))
        self.cells = {w["name"]: w for w in self.doc["workloads"]}
        self.configs = {c["name"]: c for c in self.doc["configs"]}

    def cell(self, name: str) -> dict:
        """The cell's ``BENCHMARK.json`` entry merged over its own file."""
        if name not in self.cells:
            raise KeyError(
                f"unknown workload {name!r}; BENCHMARK.json has "
                f"{sorted(self.cells)}"
            )
        entry = self.cells[name]
        wl = _load(os.path.join(self.bench_dir, "workloads", name + ".json"))
        if wl.get("config", entry["config"]) != entry["config"]:
            raise ValueError(
                f"{name}: workload file says config {wl['config']!r}, "
                f"BENCHMARK.json says {entry['config']!r}"
            )
        return {**wl, "name": name, "config": entry["config"],
                "chips": entry["chips"]}

    def config(self, name: str) -> dict:
        """The configuration as it is run: the file BENCHMARK.json names,
        laid key by key over the configuration it names as its ``base``."""
        cfg = _load(os.path.join(self.repo_dir, self.configs[name]["file"]))
        if "base" in cfg:
            cfg = {**self.config(cfg.pop("base")), **cfg}
        return cfg

    def _metrics(self, kind: str, folder: str, cell: str) -> list:
        """Declared metrics of ``kind`` that ``cell`` reports, each merged
        with its reader file."""
        out = []
        for m in self.doc[kind]:
            if "workloads" in m and cell not in m["workloads"]:
                continue
            path = os.path.join(self.bench_dir, folder, m["name"] + ".json")
            out.append({**_load(path), **m})
        return out

    def end_to_end(self, cell: str) -> list:
        return self._metrics("end_to_end", "end_to_end", cell)

    def per_layer(self, cell: str) -> list:
        return self._metrics("per_layer", "layers", cell)
