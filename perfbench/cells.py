"""Resolve a name in BENCHMARK.json to the files that define it.

A cell names a configuration and a traffic mix. The configuration's file
names its `family` (the builder in families/), the traffic file names its
`driver` (the loop in drivers/); per-layer metrics are the files in
layer_metrics/ whose name BENCHMARK.json lists. Nothing here is a table
to edit: a later PR adds files and BENCHMARK.json entries.
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class BenchError(Exception):
    """A cell that cannot be resolved, built or measured; exit non-zero."""


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_benchmark(root=ROOT):
    return load_json(os.path.join(root, "BENCHMARK.json"))


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list        # metric entries of BENCHMARK.json for this cell
    per_layer: list
    root: str

    @property
    def family(self):
        return importlib.import_module(
            f"perfbench.families.{self.config['family']}")

    @property
    def driver(self):
        return importlib.import_module(
            f"perfbench.drivers.{self.traffic['driver']}")


def _applies(metric, cell_name):
    return cell_name in metric.get("workloads", [cell_name])


def resolve(name, root=ROOT, bench=None):
    """The Cell called `name`, with every file it needs read and checked."""
    bench = bench or load_benchmark(root)
    rows = [w for w in bench["workloads"] if w["name"] == name]
    if not rows:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json (have: "
                         f"{[w['name'] for w in bench['workloads']]})")
    row = rows[0]
    cfg_rows = [c for c in bench["configs"] if c["name"] == row["config"]]
    if not cfg_rows:
        raise BenchError(f"workload {name!r} names config "
                         f"{row['config']!r}, which BENCHMARK.json lacks")
    config = load_json(os.path.join(root, cfg_rows[0]["file"]))
    traffic = load_json(os.path.join(root, "perfbench", "traffic",
                                     row["traffic"] + ".json"))
    for what, d, key, sub in (("config", config, "family", "families"),
                              ("traffic", traffic, "driver", "drivers")):
        path = os.path.join(root, "perfbench", sub, f"{d.get(key)}.py")
        if not os.path.isfile(path):
            raise BenchError(f"{what} of {name!r} names {key} "
                             f"{d.get(key)!r}: no file {path}")
    return Cell(
        name=name, chips=int(row["chips"]), config=config, traffic=traffic,
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)],
        root=root)


def layer_metric_reader(name, root=ROOT):
    """The reader for one per-layer metric: a function run -> value or
    None. `<name>.json` is read by the generic readers in counters.py;
    `<name>.py` has a `read(run)` of its own."""
    base = os.path.join(root, "perfbench", "layer_metrics", name)
    if os.path.isfile(base + ".json"):
        from . import counters
        spec = load_json(base + ".json")
        return lambda run: counters.read_data_metric(spec, run)
    if os.path.isfile(base + ".py"):
        spec = importlib.util.spec_from_file_location(
            f"perfbench.layer_metrics.{name.replace('-', '_')}", base + ".py")
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read
    raise BenchError(f"per-layer metric {name!r} has no file "
                     f"{base}.json or {base}.py")
