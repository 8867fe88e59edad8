"""Finds a cell's configuration, traffic mix and metrics by name.

``BENCHMARK.json`` at the root of the checkout names them; each lives in
a file of its own under ``bench/``:

- configuration ``<c>``: ``bench/configs/<c>.json``; its ``driver`` names
  the module ``bench/drivers/<driver>.py`` that sets up, measures and
  checks it;
- traffic mix ``<t>``: ``bench/traffic/<t>.json``; its ``kind`` names the
  generator ``bench/generators/<kind>.py``;
- per-layer metric ``<m>``: the reader ``bench/metrics/<m>.py``;
- peaks of a device: ``bench/peaks.json``, keyed by ``device_kind``.

So a new cell, mix or metric is new files and new entries, never an edit.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
import os
from dataclasses import dataclass

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


class UnknownName(LookupError):
    pass


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list    # BENCHMARK.json metric entries this cell reports
    per_layer: list

    def driver(self):
        return importlib.import_module(f"bench.drivers.{self.config['driver']}")

    def generator(self):
        return importlib.import_module(f"bench.generators.{self.traffic['kind']}")


def _read(path: str, what: str, name: str) -> dict:
    if not os.path.isfile(path):
        raise UnknownName(f"no {what} named {name!r} ({path})")
    with open(path) as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> dict:
    return _read(os.path.join(root, "BENCHMARK.json"), "benchmark",
                 "BENCHMARK.json")


def config(name: str) -> dict:
    return _read(os.path.join(BENCH, "configs", f"{name}.json"),
                 "configuration", name)


def traffic(name: str) -> dict:
    return _read(os.path.join(BENCH, "traffic", f"{name}.json"),
                 "traffic mix", name)


def reports(metric: dict, cell: str) -> bool:
    """Whether a BENCHMARK.json metric entry is reported in ``cell``."""
    return cell in metric.get("workloads", [cell])


def load_cell(name: str, root: str = ROOT) -> Cell:
    spec = benchmark(root)
    for w in spec["workloads"]:
        if w["name"] == name:
            return Cell(
                name=name, chips=int(w["chips"]),
                config=config(w["config"]), traffic=traffic(w["traffic"]),
                end_to_end=[m for m in spec["end_to_end"] if reports(m, name)],
                per_layer=[m for m in spec["per_layer"] if reports(m, name)])
    raise UnknownName(f"no workload named {name!r} in BENCHMARK.json")


def reader(metric: str):
    """The reader module of a per-layer metric: ``LAYER``, ``UNIT``,
    ``MOVES`` and ``read(ctx) -> float | None``."""
    path = os.path.join(BENCH, "metrics", f"{metric}.py")
    if not os.path.isfile(path):
        raise UnknownName(f"no reader for metric {metric!r} ({path})")
    spec = importlib.util.spec_from_file_location(
        f"bench.metrics.{metric}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def peaks(device_kind: str) -> dict:
    table = _read(os.path.join(BENCH, "peaks.json"), "peak table", "peaks")
    if device_kind not in table["devices"]:
        raise UnknownName(f"device kind {device_kind!r} is not in "
                          f"bench/peaks.json")
    return table["devices"][device_kind]
