"""From a cell's name to the files that define it.

Everything that belongs to one configuration, one job or one per-layer
metric sits in a file of its own and is found by the name written in
``BENCHMARK.json``; nothing here knows a cell, a model or a metric by name.
Adding one is new files plus new entries:

    workloads[name]  ->  config   ->  configs/<config>.json   (sizes; names
                                      its code in configs/ and its plain
                                      reference in reference/)
                         traffic  ->  jobs/<traffic>.json     (the job)
    per_layer[name]  ->  layer_metrics/<name>.json + <name>.py (a reader)

``rehearsal.json`` lists, in the same form, toy cells that are not measured:
they exist so that the harness can be rehearsed on the CPU, and only they
may run off a TPU.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import types

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)  # the checkout: BENCHMARK.json lives here


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_code(*parts: str) -> types.ModuleType:
    """A Python file by path: the names of configurations and metrics hold
    ``-`` and ``.``, so they are no module names."""
    path = os.path.join(*parts)
    name = "benchmark_file_" + "".join(
        c if c.isalnum() else "_" for c in os.path.relpath(path, HERE))
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def benchmark() -> dict:
    return load_json(ROOT, "BENCHMARK.json")


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    measured: bool  # listed in BENCHMARK.json: runs on a TPU or not at all
    config: dict
    job: dict
    code: types.ModuleType  # the configuration through the product's models
    reference: types.ModuleType  # the same mathematics in plain jax.numpy

    @property
    def rows(self) -> int:
        """Sequences or images in one step, over all the cell's chips."""
        return self.job["rows_per_chip"] * self.chips


def resolve(name: str) -> Cell:
    measured = {w["name"]: w for w in benchmark()["workloads"]}
    rehearsed = {w["name"]: w
                 for w in load_json(HERE, "rehearsal.json")["workloads"]}
    if name not in measured and name not in rehearsed:
        raise SystemExit(
            f"benchmark: no cell named {name!r}; BENCHMARK.json has "
            f"{sorted(measured)}, rehearsal.json has {sorted(rehearsed)}")
    entry = measured.get(name) or rehearsed[name]
    config = load_json(HERE, "configs", entry["config"] + ".json")
    return Cell(
        name=name, chips=entry["chips"], measured=name in measured,
        config=config,
        job=load_json(HERE, "jobs", entry["traffic"] + ".json"),
        code=load_code(HERE, "configs", config["code"]),
        reference=load_code(HERE, "reference", config["reference"]))


def layer_metrics(cell_name: str) -> list[tuple[dict, dict, types.ModuleType]]:
    """The per-layer metrics this cell reports: ``(entry of BENCHMARK.json,
    the metric's own parameters, its reader)``. An entry without
    ``workloads`` belongs to every cell."""
    found = []
    for entry in benchmark()["per_layer"]:
        if cell_name in entry.get("workloads", [cell_name]):
            found.append((
                entry,
                load_json(HERE, "layer_metrics", entry["name"] + ".json"),
                load_code(HERE, "layer_metrics", entry["name"] + ".py")))
    return found
