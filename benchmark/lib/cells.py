"""``BENCHMARK.json`` and the data files it names, found by name.

A cell is one entry of ``workloads``: a configuration
(``configs/<name>.json``, via the ``file`` the entry of ``configs``
gives) under a traffic mix (``traffic/<name>.json``). A per-layer metric
is ``layer_metrics/<name>.json`` naming its reader module
(``readers/<reader>.py``). Three more modules are found by a name in a
data file: the traffic file's ``data`` names its feed
(``feeds/<data>.py``), the configuration's ``reference`` its family
(``reference/<reference>.py``: the plain reference, its loss and its
operation count) and its ``optimizer.name`` its optimizer
(``reference/optimizers/<name>.py``). Adding any of them adds files and
an entry, never an edit here; a name that resolves to no module stops
the run with the names that exist.
"""

from __future__ import annotations

import importlib
import json
import os
from dataclasses import dataclass

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


@dataclass(frozen=True)
class Home:
    """Where the benchmark's data files and modules are looked up: a
    directory and the package it is imported as."""
    directory: str
    package: str


# the one place the lookups below take their directory and package from
# (a test points it at its fixtures)
HOME = Home(BENCH_DIR, "benchmark")

# what the harness calls of each kind of module it finds by name
CONTRACTS = {
    "feeds": ("KEYS", "argument", "epoch_order", "batch"),
    "reference": ("weight_spec", "trainable", "loss", "example_input",
                  "train_flops"),
    "reference/optimizers": ("init", "update", "trace1", "program_trace1",
                             "argv"),
    "readers": ("read",),
}


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

    @property
    def feed(self):
        return feed(self.traffic)

    @property
    def family(self):
        return reference(self.config)

    @property
    def optimizer(self):
        return optimizer(self.config)


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
    cell = Cell(
        name=name,
        chips=int(entry["chips"]),
        config=_load(os.path.join(ROOT, cfg_entry["file"])),
        traffic=_load(os.path.join(HOME.directory, "traffic",
                                   entry["traffic"] + ".json")),
        end_to_end=tuple(m for m in bench["end_to_end"]
                         if _reported(m, name)),
        per_layer=tuple(m for m in bench["per_layer"]
                        if _reported(m, name)),
    )
    # a name that resolves to nothing stops the run here, not after set-up
    for lookup, named_in in ((feed, cell.traffic), (reference, cell.config),
                             (optimizer, cell.config)):
        lookup(named_in)
    return cell


def _module(kind: str, name: str, named_by: str):
    """``<HOME>/<kind>/<name>.py`` as a module that keeps ``kind``'s
    contract, or no run: the message lists the names that exist."""
    directory = os.path.join(HOME.directory, kind)
    if not os.path.isfile(os.path.join(directory, f"{name}.py")):
        known = sorted(f[:-3] for f in os.listdir(directory)
                       if f.endswith(".py") and not f.startswith("_"))
        raise SystemExit(f"{named_by} names {name!r}, and there is no "
                         f"{kind}/{name}.py (there are: {', '.join(known)})")
    module = importlib.import_module(
        ".".join([HOME.package, *kind.split("/"), name]))
    missing = [a for a in CONTRACTS[kind] if not hasattr(module, a)]
    if missing:
        raise SystemExit(f"{kind}/{name}.py lacks {', '.join(missing)}: "
                         f"benchmark/README.md has the contract")
    return module


def layer_metric(name: str) -> dict:
    return _load(os.path.join(HOME.directory, "layer_metrics",
                              name + ".json"))


def reader(name: str):
    """The reader module of per-layer metric ``name``."""
    return _module("readers", layer_metric(name)["reader"],
                   f"layer_metrics/{name}.json")


def feed(traffic: dict):
    """The feed a traffic file's ``data`` names: the benchmark's own copy
    of one of the trainer's data sources."""
    return _module("feeds", traffic["data"], "the traffic file's `data`")


def reference(config: dict):
    """The family a configuration's ``reference`` names: its plain
    reference, loss and operation count."""
    return _module("reference", config["reference"],
                   "the configuration's `reference`")


def optimizer(config: dict):
    """The plain update a configuration's ``optimizer.name`` names."""
    return _module("reference/optimizers", config["optimizer"]["name"],
                   "the configuration's `optimizer.name`")


def peaks(device_kind: str) -> dict:
    table = _load(os.path.join(HOME.directory, "peaks.json"))["devices"]
    if device_kind not in table:
        raise SystemExit(f"device kind {device_kind!r} is not in "
                         f"benchmark/peaks.json: add it with its source, "
                         f"do not guess a peak")
    return table[device_kind]
