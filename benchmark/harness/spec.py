"""Finds a cell's files by the names ``BENCHMARK.json`` gives.

A cell (one entry of ``workloads``) names a configuration and a traffic
mix.  Each is a file of its own, as is the cell's deployment and each
per-layer metric's reader:

- ``configs/<config>.json``     the model's sizes as run, source, cuts
- ``traffic/<traffic>.json``    lengths, arrivals, loop kind
- ``cells/<workload>.json``     engine settings, offered load, limits
- ``metrics/<metric>.py``       ``read(run) -> float | None``

Adding a cell or a metric adds files and entries; nothing here names one.
"""
from __future__ import annotations

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def _load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


class Cell:
    """One workload of ``BENCHMARK.json`` with its three data files."""

    def __init__(self, workload: str, root: str = ROOT):
        self.bench = _load_json(root, "BENCHMARK.json")
        # the benchmark's data may live in a copy of the tree (tests add
        # throw-away cells to one); its code is always this package's
        self.dir = d = os.path.join(root, self.bench["paths"][0])
        entries = {w["name"]: w for w in self.bench["workloads"]}
        if workload not in entries:
            raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                           f"have {sorted(entries)}")
        self.entry = entries[workload]
        self.name = workload
        self.chips = int(self.entry["chips"])
        self.config = _load_json(d, "configs",
                                 self.entry["config"] + ".json")
        self.traffic = _load_json(d, "traffic",
                                  self.entry["traffic"] + ".json")
        self.deploy = _load_json(d, "cells", workload + ".json")

    def _metrics(self, group: str):
        """Entries of ``group`` that this cell reports: those that list
        it under ``workloads``, and those with no such key."""
        return [m for m in self.bench[group]
                if self.name in m.get("workloads", [self.name])]

    def end_to_end(self):
        return self._metrics("end_to_end")

    def per_layer(self):
        e2e = {m["name"] for m in self.end_to_end()}
        return [m for m in self._metrics("per_layer") if m["moves"] in e2e]


def metric_reader(name: str, bench_dir: str = BENCH_DIR):
    """The ``read`` function of ``metrics/<name>.py``."""
    path = os.path.join(bench_dir, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def reference_module(family: str):
    """``references/<family>.py``: the configuration's plain reference."""
    path = os.path.join(BENCH_DIR, "references", family + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_reference_" + family, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
