"""Resolve a cell's files by the names in ``BENCHMARK.json``.

The manifest at the root of the checkout is the single list of cells,
configurations and metrics. Everything that belongs to one of them sits
in a file of its own, found here by name, so that a later PR adds a
cell, a mix, a configuration or a metric as new files plus one manifest
entry and edits nothing that exists. An architecture comes the same way:
what is specific to it is code (its plain reference, what its attention
must read and multiply), so a configuration's file names a module for
each, and a new architecture is new modules.
"""

from __future__ import annotations

import dataclasses
import importlib
import json
import os
import re
from typing import Any, Dict, List

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
# key of a configuration's file -> the directory its module is found in
ARCHITECTURE_MODULES = {"reference": "references",
                        "attention_cost": "attention_costs"}
MODULE_NAME = re.compile(r"^[A-Za-z][A-Za-z0-9_]{0,63}$")


class ManifestError(Exception):
    pass


def _load_json(path: str) -> Any:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise ManifestError(f"missing file: {os.path.relpath(path, ROOT)}")


def load_manifest(root: str = ROOT) -> dict:
    return _load_json(os.path.join(root, "BENCHMARK.json"))


def _entry(entries: List[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise ManifestError(
        f"BENCHMARK.json has no {what} named {name!r} "
        f"(it has: {', '.join(e['name'] for e in entries)})")


def module_names(directory: str) -> List[str]:
    return sorted(f[:-3] for f in os.listdir(os.path.join(BENCH_DIR, directory))
                  if f.endswith(".py") and MODULE_NAME.match(f[:-3]))


def architecture_module_name(config: Dict[str, Any], config_name: str,
                             key: str) -> str:
    """``<directory>.<name>`` of the module a configuration's file names
    under ``key``. No name, or a name with no file, is an error and
    never a default."""
    directory = ARCHITECTURE_MODULES[key]
    name, there = config.get(key), module_names(directory)
    if name not in there:
        raise ManifestError(
            f"configuration {config_name!r}: its file has to name a module of "
            f"benchmark/{directory}/ under {key!r} and gives {name!r} "
            f"(there are: {', '.join(there)})")
    return f"{directory}.{name}"


def architecture_module(config: Dict[str, Any], config_name: str, key: str):
    return importlib.import_module(
        architecture_module_name(config, config_name, key))


def _in_cell(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


@dataclasses.dataclass
class Metric:
    """One manifest metric with its reader file (``<dir>/<name>.json``:
    ``reader`` names a module of ``benchmark/readers``, ``args`` is what
    that module's ``read`` is given)."""
    name: str
    unit: str
    better: str
    reader: str
    args: Dict[str, Any]
    moves: str = ""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    cell: Dict[str, Any]        # benchmark/cells/<cell>.json
    config_name: str
    config: Dict[str, Any]      # the configuration's file
    traffic_name: str
    traffic: Dict[str, Any]     # benchmark/traffic/<mix>.json
    end_to_end: List[Metric]
    per_layer: List[Metric]


def _metrics(entries: List[dict], cell: str, directory: str) -> List[Metric]:
    out = []
    for m in entries:
        if not _in_cell(m, cell):
            continue
        spec = _load_json(os.path.join(BENCH_DIR, directory, m["name"] + ".json"))
        out.append(Metric(
            name=m["name"], unit=m["unit"], better=m["better"],
            reader=spec["reader"], args=spec.get("args", {}),
            moves=m.get("moves", "")))
    return out


def _overlaid(data: dict, rehearsal: bool) -> dict:
    """A data file may carry a ``rehearsal`` group: what the CPU
    rehearsal of benchmark/tests replaces (tiny lengths, a low rate). A
    measured run drops it; a rehearsal lays it over the file's keys.
    (A configuration's group is applied in harness/server.py, because
    it is split into model and serve keys.)"""
    base = {k: v for k, v in data.items() if k != "rehearsal"}
    return {**base, **data.get("rehearsal", {})} if rehearsal else base


def load_cell(name: str, root: str = ROOT, rehearsal: bool = False) -> Cell:
    man = load_manifest(root)
    w = _entry(man["workloads"], name, "workload")
    c = _entry(man["configs"], w["config"], "configuration")
    cell = _overlaid(
        _load_json(os.path.join(BENCH_DIR, "cells", name + ".json")), rehearsal)
    end_to_end = _metrics(man["end_to_end"], name, "end_to_end")
    per_layer = _metrics(man["per_layer"], name, "layer_metrics")
    # a per-layer metric is reported only where the metric it moves is
    reported = {m.name for m in end_to_end}
    adrift = [m.name for m in per_layer if m.moves not in reported]
    if adrift:
        raise ManifestError(
            f"cell {name!r} lists per-layer metrics {adrift} but does not "
            "report the end-to-end metric they move")
    config = _load_json(os.path.join(root, c["file"]))
    for key in ARCHITECTURE_MODULES:     # refused before anything is served
        architecture_module_name(config, w["config"], key)
    return Cell(
        name=name, chips=int(w["chips"]), cell=cell,
        config_name=w["config"], config=config,
        traffic_name=w["traffic"],
        traffic=_overlaid(_load_json(os.path.join(
            BENCH_DIR, "traffic", w["traffic"] + ".json")), rehearsal),
        end_to_end=end_to_end, per_layer=per_layer,
    )
