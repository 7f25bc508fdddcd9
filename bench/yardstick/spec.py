"""Resolve a benchmark cell by name from ``BENCHMARK.json``.

Everything that belongs to one configuration, traffic mix, cell or
per-layer metric sits in a file of its own, found by its name:

    bench/configs/<config>.json     the configuration (the file named
                                    in BENCHMARK.json's ``configs``)
    bench/models/<model>.py         the configuration's ``model``: its
                                    inputs from the seed and the
                                    program's task built over them
    bench/traffic/<traffic>.json    the traffic mix's parameters
    bench/references/<model>_<reference>.py
                                    the plain reference of the mix's
                                    protocol (its ``reference`` key)
                                    for that model
    bench/fleets/speeds/<kind>.py   the fleet's speeds and availability
    bench/fleets/availability/<kind>.py
                                    windows, as the reference draws them
    bench/limits/<workload>.json    the limits ``correct`` is held to
    bench/metrics/<metric>.py       the reader of one per-layer metric

so a cell, configuration, mix or metric is added with new files and new
entries, never by editing one that is there.
"""
from __future__ import annotations

import importlib.util
import json
import os
import re
import sys
from typing import Any, Dict, List

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def _load_json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def load_benchmark() -> Dict[str, Any]:
    return _load_json(os.path.join(ROOT, "BENCHMARK.json"))


def _named(entries: List[Dict[str, Any]], name: str, what: str):
    for e in entries:
        if e["name"] == name:
            return e
    have = ", ".join(e["name"] for e in entries)
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json "
                   f"(have {have})")


class Cell:
    """One entry of ``workloads`` with its configuration, traffic mix,
    limits and metrics resolved."""

    def __init__(self, name: str):
        bm = self.bm = load_benchmark()
        self.entry = _named(bm["workloads"], name, "workload")
        self.name = name
        self.chips = int(self.entry["chips"])
        cfg_entry = _named(bm["configs"], self.entry["config"],
                           "configuration")
        self.config = _load_json(os.path.join(ROOT, cfg_entry["file"]))
        self.traffic_name = self.entry["traffic"]
        self.traffic = _load_json(os.path.join(
            BENCH_DIR, "traffic", f"{self.traffic_name}.json"))
        self.limits = _load_json(os.path.join(
            BENCH_DIR, "limits", f"{name}.json"))

    def end_to_end(self) -> List[Dict[str, Any]]:
        return [m for m in self.bm["end_to_end"] if self._reports(m)]

    def per_layer(self) -> List[Dict[str, Any]]:
        e2e = {m["name"] for m in self.end_to_end()}
        return [m for m in self.bm["per_layer"]
                if m["moves"] in e2e and self._reports(m)]

    def _reports(self, metric: Dict[str, Any]) -> bool:
        cells = metric.get("workloads")
        return cells is None or self.name in cells


def load_module(kind: str, name: str):
    """The module in ``bench/<kind>/<name>.py``, loaded once a process."""
    path = os.path.join(BENCH_DIR, kind, f"{name}.py")
    if not os.path.exists(path):
        raise KeyError(f"no {kind} named {name!r}: {path} is missing")
    mod_name = "bench_" + re.sub(r"[^A-Za-z0-9_]", "_", f"{kind}/{name}")
    if mod_name not in sys.modules:
        spec = importlib.util.spec_from_file_location(mod_name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[mod_name] = mod
        try:
            spec.loader.exec_module(mod)
        except BaseException:
            del sys.modules[mod_name]
            raise
    return sys.modules[mod_name]


def model_of(config: Dict[str, Any]):
    """``bench/models/<config["model"]>.py``."""
    return load_module("models", config["model"])


def reference_of(config: Dict[str, Any], traffic: Dict[str, Any]):
    """``bench/references/<model>_<traffic["reference"]>.py``."""
    return load_module("references",
                       f"{config['model']}_{traffic['reference']}")


def metric_reader(name: str):
    """The ``read(ctx)`` function of ``bench/metrics/<name>.py``."""
    return load_module("metrics", name).read
