"""``BENCHMARK.json`` and the data files it names, found by name.

A cell is one entry of ``workloads``: a configuration
(``configs/<name>.json``, via the ``file`` the entry of ``configs``
gives) under a traffic mix (``traffic/<name>.json``). A per-layer metric
is ``layer_metrics/<name>.json`` naming its reader module
(``readers/<reader>.py``); a configuration names its plain reference
(``reference/<reference>.py``). Adding any of them adds files and an
entry, never an edit here.
"""

from __future__ import annotations

import importlib
import json
import os
from dataclasses import dataclass

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def manifest() -> dict:
    return _load(os.path.join(ROOT, "BENCHMARK.json"))


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: tuple  # metric entries of BENCHMARK.json reported here
    per_layer: tuple

    @property
    def global_batch(self) -> int:
        return self.config["per_chip_batch"] * self.chips


def _reported(metric: dict, cell_name: str) -> bool:
    return cell_name in metric.get("workloads", [cell_name])


def load_cell(name: str, bench: dict = None) -> Cell:
    bench = manifest() if bench is None else bench
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        known = ", ".join(w["name"] for w in bench["workloads"])
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json "
                         f"(there are: {known})")
    cfg_entry = next(c for c in bench["configs"]
                     if c["name"] == entry["config"])
    return Cell(
        name=name,
        chips=int(entry["chips"]),
        config=_load(os.path.join(ROOT, cfg_entry["file"])),
        traffic=_load(os.path.join(BENCH_DIR, "traffic",
                                   entry["traffic"] + ".json")),
        end_to_end=tuple(m for m in bench["end_to_end"]
                         if _reported(m, name)),
        per_layer=tuple(m for m in bench["per_layer"]
                        if _reported(m, name)),
    )


def layer_metric(name: str) -> dict:
    return _load(os.path.join(BENCH_DIR, "layer_metrics", name + ".json"))


def reader(name: str):
    """The reader module of per-layer metric ``name``."""
    spec = layer_metric(name)
    return importlib.import_module(f"benchmark.readers.{spec['reader']}")


def reference(config: dict):
    """The plain-reference module a configuration names."""
    return importlib.import_module(
        f"benchmark.reference.{config['reference']}")


def peaks(device_kind: str) -> dict:
    table = _load(os.path.join(BENCH_DIR, "peaks.json"))["devices"]
    if device_kind not in table:
        raise SystemExit(f"device kind {device_kind!r} is not in "
                         f"benchmark/peaks.json: add it with its source, "
                         f"do not guess a peak")
    return table[device_kind]
