"""Resolve a cell of ``BENCHMARK.json`` into the files that define it.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric sits in a file of its own under ``benchmarks/``; this
module finds each by the name ``BENCHMARK.json`` gives it and fails on a
name it cannot find.  ``root`` is the checkout (the directory that holds
``BENCHMARK.json``), so a test can resolve against a temporary copy.
"""

from __future__ import annotations

import importlib
import json
import os
from dataclasses import dataclass
from typing import Callable, Dict, List


class SpecError(Exception):
    """A name in ``BENCHMARK.json`` or in a data file resolves to nothing."""


def load_json(path: str) -> dict:
    if not os.path.isfile(path):
        raise SpecError(f"no such file: {path}")
    with open(path) as f:
        return json.load(f)


def resolve_dotted(path: str) -> Callable:
    """``"package.module:attr"`` -> the attribute."""
    module, sep, attr = path.partition(":")
    if not sep:
        raise SpecError(f"{path!r} is not of the form 'package.module:name'")
    try:
        return getattr(importlib.import_module(module), attr)
    except (ImportError, AttributeError) as e:
        raise SpecError(f"cannot resolve {path!r}: {e}") from e


def resolve_module(path: str):
    """A module a configuration names: its plain ``reference``, its
    ``work`` (the algorithm's operations and bytes)."""
    try:
        return importlib.import_module(path)
    except ImportError as e:
        raise SpecError(f"cannot import {path!r}: {e}") from e


@dataclass
class Cell:
    """One entry of ``workloads`` with everything it names loaded."""

    name: str
    chips: int
    config: dict           # benchmarks/configs/<config>.json
    traffic: dict          # benchmarks/traffic/<traffic>.json
    end_to_end: List[dict]  # BENCHMARK.json entries this cell reports
    per_layer: List[dict]   # layer_metrics/<name>.json of this cell's metrics
    run_seconds: int


def check_against_source(config: dict) -> None:
    """What a configuration's file says of its source: ``published``
    holds the source's values and the top level the values as run; they
    differ only in the keys ``reduced`` lists (whose note states the
    published value beside the one held), never in one of ``widths``;
    and every builder argument and ``harness`` key that ``from_source``
    maps to a key holds that key's value as run.  A preset of no source
    (``"preset": true``) has nothing published."""
    def refuse(what):
        raise SpecError(f"configuration {config.get('name')!r}: {what}")

    published, reduced = config["published"], config["reduced"]
    if bool(config.get("preset")) == bool(published):
        refuse("either a preset of no source or something published")
    if not set(config["widths"]) <= set(published):
        refuse(f"widths {config['widths']} are not all published keys")
    for key in reduced:
        if key in config["widths"] or key not in published:
            refuse(f"{key!r} is reduced: a width, or not a published key")
        if (config[key] == published[key]
                or str(published[key]) not in reduced[key]):
            refuse(f"the note on {key!r} has to state the published value "
                   f"{published[key]!r} beside another one held")
    for key, value in published.items():
        if key not in reduced and config.get(key) != value:
            refuse(f"{key!r} is {config.get(key)!r}, published {value!r}, "
                   f"and not in reduced")
    for block, mapping in config["from_source"].items():
        for name, key in mapping.items():
            if config[block].get(name) != config[key]:
                refuse(f"{block}.{name} is {config[block].get(name)!r}, "
                       f"{key!r} is {config[key]!r}")
    if published and not (config["widths"]
                          and all(config["from_source"].values())):
        refuse("a published configuration names its widths and maps its "
               "builder arguments and harness keys to the source's keys")


def _reported_by(metric: dict, cell_name: str) -> bool:
    cells = metric.get("workloads")
    return cells is None or cell_name in cells


def load_benchmark(root: str) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def resolve_cell(root: str, workload: str) -> Cell:
    bench = load_benchmark(root)
    entry = next((w for w in bench["workloads"] if w["name"] == workload),
                 None)
    if entry is None:
        raise SpecError(
            f"BENCHMARK.json has no workload {workload!r}; it has "
            f"{[w['name'] for w in bench['workloads']]}")
    cfg_entry = next((c for c in bench["configs"]
                      if c["name"] == entry["config"]), None)
    if cfg_entry is None:
        raise SpecError(f"workload {workload!r} names config "
                        f"{entry['config']!r}, which BENCHMARK.json lacks")
    config = load_json(os.path.join(root, cfg_entry["file"]))
    check_against_source(config)
    home = os.path.dirname(os.path.dirname(
        os.path.join(root, cfg_entry["file"])))
    traffic = load_json(os.path.join(home, "traffic",
                                     entry["traffic"] + ".json"))
    per_layer = []
    for m in bench["per_layer"]:
        if not _reported_by(m, workload):
            continue
        spec = load_json(os.path.join(home, "layer_metrics",
                                      m["name"] + ".json"))
        for key in ("name", "unit", "layer", "moves"):
            if spec.get(key) != m[key]:
                raise SpecError(
                    f"layer_metrics/{m['name']}.json says {key}="
                    f"{spec.get(key)!r}, BENCHMARK.json says {m[key]!r}")
        per_layer.append(spec)
    return Cell(
        name=workload, chips=entry["chips"], config=config, traffic=traffic,
        end_to_end=[m for m in bench["end_to_end"]
                    if _reported_by(m, workload)],
        per_layer=per_layer, run_seconds=bench["run_seconds"])


# traffic ``kind`` -> the driver that runs it
DRIVERS: Dict[str, str] = {
    "train_epochs": "benchmarks.harness.train:run",
    "closed_loop": "benchmarks.harness.serve:run",
    "open_loop": "benchmarks.harness.serve:run",
}
