"""What a cell is made of, found by name from BENCHMARK.json.

A cell (workload) names a configuration and a traffic mix. The configuration's
file is the one BENCHMARK.json lists; the mix is <base>/traffic/<traffic>.json;
parameters of one cell alone (a paced cell's rate, read from its sweep) sit in
<base>/cells/<workload>.json and override the mix's. A per-layer metric is
<base>/metrics/<name>.json naming its reader, <base>/readers/<reader>.py.
<base> is the first of BENCHMARK.json's paths. Adding any of them is adding
files and entries; no file here lists them and nothing here tests a name.
"""

from __future__ import annotations

import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _merge(into: dict, over: dict) -> dict:
    for key, value in over.items():
        if isinstance(value, dict) and isinstance(into.get(key), dict):
            _merge(into[key], value)
        else:
            into[key] = value
    return into


def load_cell(workload: str, root: str = ROOT, rehearsal: bool = False) -> dict:
    """The cell's configuration, traffic parameters and metric entries."""
    bench = _load(os.path.join(root, "BENCHMARK.json"))
    cell = next((w for w in bench["workloads"] if w["name"] == workload), None)
    if cell is None:
        raise KeyError(
            f"no workload {workload!r} in BENCHMARK.json "
            f"(has: {[w['name'] for w in bench['workloads']]})"
        )
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = _load(os.path.join(root, entry["file"]))
    base = os.path.join(root, bench["paths"][0])
    traffic = _load(os.path.join(base, "traffic", cell["traffic"] + ".json"))
    own = os.path.join(base, "cells", workload + ".json")
    if os.path.exists(own):
        _merge(traffic, _load(own))
    if rehearsal:  # toy sizes: the control flow only, never a chip result
        _merge(config, config.get("rehearsal", {}))
        _merge(traffic, traffic.get("rehearsal", {}))

    def reported(metric: dict) -> bool:
        return workload in metric.get("workloads", [workload])

    end_to_end = [m for m in bench["end_to_end"] if reported(m)]
    names = {m["name"] for m in end_to_end}
    per_layer = [m for m in bench["per_layer"]
                 if reported(m) and m["moves"] in names]
    return dict(
        name=workload, chips=cell["chips"], config_name=cell["config"],
        traffic_name=cell["traffic"], config=config, traffic=traffic,
        end_to_end=end_to_end, per_layer=per_layer, base=base,
    )


def load_module(name: str, path: str):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_reference(root: str, config: dict):
    """The configuration's plain reference, as a module (run, PRIORITY,
    CONTROL_PRIORITY and, where the venue has rules of its own, Book)."""
    return load_module("benchmark_reference_" + config["name"],
                        os.path.join(root, config["reference"]))


def load_reader(base: str, metric_name: str):
    """(metric file, read function) of one per-layer metric."""
    meta = _load(os.path.join(base, "metrics", metric_name + ".json"))
    module = load_module(
        "benchmark_reader_" + meta["reader"],
        os.path.join(base, "readers", meta["reader"] + ".py"),
    )
    return meta, module.read


def span_names(base: str, metric_names) -> list[str]:
    """Host spans the trace reduction looks for: those the cell's metric
    files name."""
    names = set()
    for name in metric_names:
        names.update(_load(os.path.join(base, "metrics", name + ".json"))
                     .get("spans", []))
    return sorted(names)
